"""The whole factorization's share of the chip's bf16 peak: the Householder
QR work of the cell's matrix, ``2 m n^2 - 2 n^3 / 3``, over the traced
window's length per factorization. It counts the same work whatever
implements it. The sweep runs float32 at HIGHEST, several bf16 passes per
product, so it reads far below 100% by construction."""
from bench import counts


def read(ctx):
    t = ctx.telemetry
    if not t.get("factorizations") or ctx.peaks is None:
        return None
    sh = t["shape"]
    lo, hi = ctx.trace.window()
    per_factor_s = (hi - lo) / 1e9 / t["factorizations"]
    flops = counts.qr_flops(sh["m"], sh["n"])
    return 100.0 * flops / per_factor_s / ctx.peaks["bf16_flops"]
