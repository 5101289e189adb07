"""Algorithm-level operation and byte counts of FT-CAQR, from shapes alone.

The counts are those of the algorithm (arXiv:1604.02504), not of whatever
implements it: padding, masking and the layout of a kernel do not enter.
Shapes: an ``m x n`` matrix, panel width ``b``, ``P`` lanes holding
``m / P`` rows each, ``L = log2(P)`` butterfly levels. Panel ``k`` starts
at column ``k b`` and is ``b_k = min(b, n - k b)`` wide (the last one may
be narrower); its live rows are ``m_k = m - k b`` and its trailing window
is the ``w_k = n - k b - b_k`` columns to its right. A lane is
active for panel ``k`` while it holds live rows. The butterfly is
redundant by design: at each level every active lane factors and applies
the stacked pair, not one lane of each pair.

Bytes are 4 per f32 element, each operand read once and each result
written once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

from bench import trace_reduce

F32 = 4


def qr_flops(m: int, n: int) -> float:
    """Householder QR of an ``m x n`` matrix, R only (LAPACK ``geqrf``)."""
    if m >= n:
        return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0
    return 2.0 * n * m * m - 2.0 * m ** 3 / 3.0


@dataclasses.dataclass(frozen=True)
class Shape:
    m: int
    n: int
    b: int
    lanes: int

    @property
    def levels(self) -> int:
        return self.lanes.bit_length() - 1

    @property
    def panels(self) -> int:
        return -(-min(self.m, self.n) // self.b)

    def width(self, k: int) -> int:
        return min(self.b, min(self.m, self.n) - k * self.b)

    def live_rows(self, k: int) -> int:
        return self.m - k * self.b

    def trailing_cols(self, k: int) -> int:
        return max(self.n - k * self.b - self.width(k), 0)

    def active_lanes(self, k: int) -> int:
        m_loc = self.m // self.lanes
        return self.lanes - (k * self.b) // m_loc


def panel_flops(s: Shape, k: int) -> float:
    """Panel ``k``: the leaf Householder QR of the live rows with its
    compact-WY ``T`` (``2 m_k b_k^2`` + ``m_k b_k^2``), and at every
    butterfly level, on every active lane, the QR of two stacked
    ``b_k x b_k`` triangles with its ``T`` (``2 (2b_k) b_k^2 - 2 b_k^3 / 3``
    + ``b_k^3``)."""
    mk, b = s.live_rows(k), s.width(k)
    leaf = 2.0 * mk * b * b + mk * b * b
    node = 2.0 * (2 * b) * b * b - 2.0 * b ** 3 / 3.0 + b ** 3
    return leaf + s.active_lanes(k) * s.levels * node


def panel_bytes(s: Shape, k: int) -> float:
    """Read the panel, write ``Y``; per node read two triangles, write
    ``Y2``, ``T`` and ``R``."""
    mk, b = s.live_rows(k), s.width(k)
    leaf = 2 * mk * b
    node = 2 * b * b + 3 * b * b
    return F32 * (leaf + s.active_lanes(k) * s.levels * node)


def trailing_flops(s: Shape, k: int) -> float:
    """Panel ``k``'s trailing update of its ``w_k`` columns: the leaf
    application ``C - Y (T^T (Y^T C))`` over the live rows, lane by lane,
    then at every level, on every active lane, the stacked application of
    ``[I; Y2]`` to the pair ``[C_top; C_bot]``."""
    mk, b, w = s.live_rows(k), s.width(k), s.trailing_cols(k)
    a = s.active_lanes(k)
    leaf = 2.0 * mk * b * w + a * 2.0 * b * b * w + 2.0 * mk * b * w
    node = 2.0 * (2 * b) * b * w + 2.0 * b * b * w + 2.0 * (2 * b) * b * w
    return leaf + a * s.levels * node


def trailing_bytes(s: Shape, k: int) -> float:
    """Leaf: read ``Y`` and ``C``, write ``C``. Node: read ``Y2``, ``T``,
    ``C_top`` and ``C_bot``; write both halves and the bundle ``W``."""
    mk, b, w = s.live_rows(k), s.width(k), s.trailing_cols(k)
    leaf = mk * b + 2 * mk * w
    node = 2 * b * b + 2 * b * w + 3 * b * w
    return F32 * (leaf + s.active_lanes(k) * s.levels * node)


def totals(s: Shape) -> Dict[str, float]:
    """Per-factorization sums over all panels."""
    ks = range(s.panels)
    return {
        "qr_flops": qr_flops(s.m, s.n),
        "panel_flops": sum(panel_flops(s, k) for k in ks),
        "panel_bytes": sum(panel_bytes(s, k) for k in ks),
        "trailing_flops": sum(trailing_flops(s, k) for k in ks),
        "trailing_bytes": sum(trailing_bytes(s, k) for k in ks),
    }


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float):
    """(share in %, bound) of work that took ``seconds`` on the device:
    the least time the chip could take — the larger of operations over
    peak and bytes over bandwidth — over the time taken."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def kernel_roofline(ctx, patterns: Sequence[str], part: str
                    ) -> Optional[float]:
    """Roofline share, in %, of the kernels whose trace events match
    ``patterns``, against the algorithm-level counts of ``part``
    (``"panel"`` or ``"trailing"``) for the factorizations of the traced
    window; ``None`` where the run holds nothing to read."""
    t = ctx.telemetry
    if not t.get("factorizations") or ctx.peaks is None:
        return None
    seconds = trace_reduce.kernel_seconds(ctx.trace, patterns)
    if seconds <= 0:
        return None
    tot = totals(Shape(**t["shape"]))
    share, _bound = roofline_share(
        t["factorizations"] * tot[f"{part}_flops"],
        t["factorizations"] * tot[f"{part}_bytes"], seconds,
        ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return share
