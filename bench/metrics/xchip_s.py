"""Device seconds per factorization of the program's cross-chip
collectives: the butterfly's and the heal's collective-permutes between
chips and the all-reduces of the R rows (``MeshComm.psum``, named ``psum``
in a TPU trace; ``all-reduce`` where XLA partitions one). Per chip, the
union of those operations' intervals inside the traced window, mean over
the chips, over the factorizations of the window.

A collective-permute comes as a ``-start`` and a ``-done`` event; both are
counted, so the time is from the transfer's issue to the end of the wait
for it. Not counted: the exchanges inside a chip (gathers), and the
``all-gather`` that XLA puts into the fault hook's eager poisoning of the
global arrays (the benchmark's injected death, no part of a deployment)."""
import re

from bench import trace_reduce

PATTERN = re.compile(r"^(collective-permute-(start|done)|psum|all-reduce)\b")


def read(ctx):
    t = ctx.telemetry
    if not t.get("chips") or not t.get("factorizations"):
        return None
    tr = ctx.trace
    lo, hi = tr.window()
    per_chip = []
    for dev in tr.devices:
        ivs = [(s, e) for name, s, e, d in tr.ops
               if d == dev and PATTERN.match(name)]
        ivs = trace_reduce.union(trace_reduce.clip(ivs, lo, hi))
        per_chip.append(sum(e - s for s, e in ivs))
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e9 / t["factorizations"]
