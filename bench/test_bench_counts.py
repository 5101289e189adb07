"""The algorithm-level counts against a direct sum: walk FT-CAQR's steps
panel by panel, lane by lane and level by level at a small shape, add up
``2 x y z`` for every matrix product and every operand and result once,
and compare with the closed forms of ``bench/counts.py``."""
from __future__ import annotations

import pytest

from bench import counts


def mm(x, y, z):
    """Operations of an (x, y) @ (y, z) product."""
    return 2.0 * x * y * z


def direct(s: counts.Shape, k: int):
    m_loc = s.m // s.lanes
    col0 = k * s.b
    b = min(s.b, s.n - col0)        # the last panel may be narrower
    w = s.n - col0 - b
    # live rows of each lane: rows at or below col0
    rows = [max(0, min(m_loc, (l + 1) * m_loc - col0))
            for l in range(s.lanes)]
    active = [r > 0 for r in rows]
    panel_f = panel_b = trail_f = trail_b = 0.0
    for r, act in zip(rows, active):
        if not act:
            continue
        # leaf QR: 2 r b^2 (Householder) + r b^2 (T's Gram, Y^T Y / 2 * 2)
        panel_f += 2.0 * r * b * b + r * b * b
        panel_b += counts.F32 * (r * b + r * b)
        # leaf apply C - Y (T^T (Y^T C)), C = (r, w)
        trail_f += mm(b, r, w) + mm(b, b, w) + mm(r, b, w)
        trail_b += counts.F32 * (r * b + 2 * r * w)
        for _ in range(s.levels):
            # stacked QR of two b x b triangles, with its T
            panel_f += (2.0 * (2 * b) * b * b - 2.0 * b ** 3 / 3.0
                        + b ** 3)
            panel_b += counts.F32 * (2 * b * b + 3 * b * b)
            # stacked apply of [I; Y2] to [C_top; C_bot]
            trail_f += mm(b, 2 * b, w) + mm(b, b, w) + mm(2 * b, b, w)
            trail_b += counts.F32 * (2 * b * b + 2 * b * w + 3 * b * w)
    return panel_f, panel_b, trail_f, trail_b


@pytest.mark.parametrize("shape", [(256, 64, 8, 4), (512, 96, 16, 8),
                                   (64, 64, 8, 2), (256, 60, 8, 4),
                                   (640, 30, 8, 16)])
def test_per_panel_counts_equal_direct_sum(shape):
    s = counts.Shape(*shape)
    for k in range(s.panels):
        pf, pb, tf, tb = direct(s, k)
        assert counts.panel_flops(s, k) == pytest.approx(pf, rel=1e-12)
        assert counts.panel_bytes(s, k) == pytest.approx(pb, rel=1e-12)
        assert counts.trailing_flops(s, k) == pytest.approx(tf, rel=1e-12)
        assert counts.trailing_bytes(s, k) == pytest.approx(tb, rel=1e-12)


def test_qr_flops_equal_column_by_column_householder():
    """``2 m n^2 - 2 n^3 / 3`` is the leading term of the column loop: for
    column j, the norm and scaling (3 (m - j)) and the update of the
    trailing columns (4 (m - j) (n - j - 1))."""
    m, n = 4096, 512
    direct_sum = sum(3.0 * (m - j) + 4.0 * (m - j) * (n - j - 1)
                     for j in range(n))
    assert counts.qr_flops(m, n) == pytest.approx(direct_sum, rel=1e-2)
    assert counts.qr_flops(n, m) == counts.qr_flops(m, n)  # wide costs as tall


def test_benchmark_shape_totals_and_bounds():
    s = counts.Shape(500000, 1000, 128, 16)
    assert s.panels == 8 and s.width(7) == 104 and s.trailing_cols(6) == 104
    tot = counts.totals(s)
    assert tot["qr_flops"] == pytest.approx(9.9933e11, rel=1e-4)
    # the leaf application does about b/2 operations per byte: below the
    # v5e ridge (197e12 / 819e9 = 240), so memory bounds it
    share, bound = counts.roofline_share(
        tot["trailing_flops"], tot["trailing_bytes"], 1.0, 197e12, 819e9)
    assert bound == "memory"
    assert 0 < share < 100
