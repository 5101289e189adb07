#!/usr/bin/env python3
"""The program's own host spans (``ftqr.*``) against the device's idle time.

The sweep orchestrator (``repro.ft.online.orchestrator``) records
``jax.profiler.TraceAnnotation`` spans at the boundaries of its host loop:
``ftqr.sweep`` around one factorization and, inside it, ``ftqr.dispatch``
(the enqueue of one compiled segment), ``ftqr.poll`` (one detector poll)
and ``ftqr.heal`` (one REBUILD). The profiler writes them on the device
planes' clock. ``trace_reduce`` keeps only the benchmark's own ``bench.*``
spans; this module reads the program's spans beside a ``trace_reduce.Trace``
and splits the device's idle time in the traced window among them, by
interval intersection (a gap that straddles two spans is split between
them), averaged over devices:

- ``idle_share.dispatch`` / ``.poll`` / ``.heal``: device idle under the
  spans of that name, in % of the window;
- ``idle_share.loop``: device idle inside ``ftqr.sweep`` but under none of
  the three (boundary hooks, the loop's glue, the finalize dispatch);
- ``idle_share.outside``: device idle outside every ``ftqr.sweep``;
- ``heal_ops``: the device operations started inside each ``ftqr.heal``,
  median over the window's heals.

The five shares add up to ``trace_reduce.idle_share``. Run as a script::

    python3 bench/program_spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--out <dir>]

it runs the cell as ``run_cell.py --trace 1`` does (a TPU is required),
keeps the profile, prints the harness's result line and then one JSON line
of the partition above.
"""
from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Trace, clip, gaps, union  # noqa: E402

PROGRAM_PREFIX = "ftqr."
SWEEP, DISPATCH, POLL, HEAL = ("ftqr.sweep", "ftqr.dispatch", "ftqr.poll",
                               "ftqr.heal")

ProgramSpan = Tuple[str, int, int, dict]   # name, start_ns, end_ns, args


def load_xplane(path: str) -> List[ProgramSpan]:
    """The ``ftqr.*`` host events of a profiler ``.xplane.pb``, sorted by
    start; ``args`` are the span's arguments as the trace keeps them."""
    from jax.profiler import ProfileData

    spans: List[ProgramSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    return sorted(spans, key=lambda sp: sp[1])


def intersect(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
              ) -> List[Tuple[int, int]]:
    """The intersection of two lists of disjoint sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _under(program: Sequence[ProgramSpan], names: Sequence[str]
           ) -> List[Tuple[int, int]]:
    return union([(s, e) for n, s, e, _ in program if n in names])


def idle_overlap_s(trace: Trace, program: Sequence[ProgramSpan],
                   names: Sequence[str],
                   inside: Optional[Sequence[str]] = None
                   ) -> Optional[float]:
    """Seconds of device idle inside the window under the union of the
    program spans named ``names`` (restricted, when ``inside`` is given, to
    within the spans named ``inside``), averaged over devices. None with no
    device op."""
    if not trace.devices:
        return None
    lo, hi = trace.window()
    under = clip(_under(program, names), lo, hi)
    if inside is not None:
        under = intersect(under, _under(program, inside))
    idle = sum(e - s for d in trace.devices
               for s, e in intersect(gaps(trace, d), under))
    return idle / len(trace.devices) / 1e9


def ops_started_in(trace: Trace, program: Sequence[ProgramSpan], name: str
                   ) -> Optional[List[int]]:
    """For each program span named ``name`` that starts inside the window,
    the number of device operations (on any device) whose start lies in
    it. None with no device op."""
    if not trace.devices:
        return None
    lo, hi = trace.window()
    starts = sorted(s for _, s, _, _ in trace.ops)
    return [bisect.bisect_left(starts, e) - bisect.bisect_left(starts, s)
            for n, s, e, _ in program if n == name and lo <= s < hi]


def partition(trace: Trace, program: Sequence[ProgramSpan]
              ) -> Optional[Dict[str, object]]:
    """The window's device idle split by program span, in % of the window
    (see the module's docstring), with ``heal_ops`` and the span counts.
    None with no device op or no ``ftqr.sweep`` span."""
    if not trace.devices or not any(n == SWEEP for n, *_ in program):
        return None
    lo, hi = trace.window()
    pct = 100.0 * 1e9 / (hi - lo)

    def share(names, inside=None):
        return pct * idle_overlap_s(trace, program, names, inside)

    factor = 100.0 * trace_reduce.idle_share(trace)
    sweep = share([SWEEP])
    heals = ops_started_in(trace, program, HEAL)
    counts: Dict[str, int] = {}
    for n, s, _, _ in program:
        if lo <= s < hi:
            counts[n] = counts.get(n, 0) + 1
    return {
        "idle_share.factor": factor,
        "idle_share.dispatch": share([DISPATCH]),
        "idle_share.poll": share([POLL]),
        "idle_share.heal": share([HEAL]),
        "idle_share.loop": sweep - share([DISPATCH, POLL, HEAL],
                                         inside=[SWEEP]),
        "idle_share.outside": factor - sweep,
        "heal_ops": statistics.median(heals) if heals else None,
        "spans": counts,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="directory that keeps the profile (default: a "
                         "temporary one)")
    args = ap.parse_args(argv)
    from bench import run_cell

    out = args.out or tempfile.mkdtemp(prefix="trace_")
    try:
        result, _ = run_cell.run(args.workload, args.seed, args.seconds,
                                 True, trace_dir=out)
    except run_cell.NoAccelerator as e:
        print(f"program_spans: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    path = trace_reduce.find_xplane(out)
    split = partition(trace_reduce.load_xplane(path), load_xplane(path))
    if args.out is None:
        shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "partition": split}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
