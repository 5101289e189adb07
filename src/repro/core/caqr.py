"""FT-CAQR: fault-tolerant QR of general (2-D) matrices (paper §III-C).

1-D block-row layout, exactly the paper's setting: lane ``i`` owns rows
``[i*m_loc, (i+1)*m_loc)`` of an ``(P*m_loc, n)`` matrix. The factorization
sweeps panels left to right; each panel is factorized by FT-TSQR (§III-B)
and the trailing matrix updated by Algorithm 2 (§III-C).

Sweep bookkeeping the paper elides (it presents single-panel trees): the tree
of panel ``k`` is oriented so its root — the lane where the new R rows
deposit — is the owner of global rows ``[k*b, (k+1)*b)``. Lanes whose rows
are fully consumed contribute zero leaves and pass-through combines (encoded
as zeroed (Y2, T) factors), so the trailing update inherits the masking with
no extra logic.

General shapes (the paper's title): arbitrary ``m x n`` float matrices are
accepted. ``sweep_geometry`` computes the *static* padded geometry — per-lane
rows rounded up to a multiple of ``b`` (so every panel's diagonal block lives
whole inside one lane) and a ragged last panel rounded up to width ``b`` —
and the sweep runs on the zero-padded working array. This is the
``kernels/ops.py`` alignment contract applied at the core layer: zero
rows/columns are exact for every op in this family (they yield degenerate
reflectors with ``tau = 0`` and contribute nothing to any inner product), so
``R`` of the padded sweep is the ``R`` of the original matrix. Wide matrices
(``n > m``) factorize only the left ``min(m, n)`` columns into panels; the
remaining columns ride along in every trailing update and finish as the
``R2`` block of ``A = Q [R1 R2]``. Aligned shapes skip the padding entirely
and run the exact seed code path (bit-identical — regression-gated by
``tests/test_general_shapes.py``).

Because row permutations do not change the R factor, the final R here equals
(up to row signs) the R of any standard QR — validated against
``jnp.linalg.qr`` and via the Gram identity ``R^T R == A^T A``.

The stored per-panel factors form the implicit Q: ``caqr_apply_qt`` replays
them against any conforming matrix (used by tests to check ``Q^T A == [R;0]``
and by least-squares solves).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.comm import AxisComm
from repro.core.householder import householder_qr_masked
from repro.core.tsqr import DistTSQRFactors, ft_tsqr_combine
from repro.core.trailing import RecoveryBundle, trailing_update_ft


class PanelFactors(NamedTuple):
    """Implicit-Q factors of one panel, per lane (leading panel axis after
    the sweep; SimComm adds a lane axis on each leaf)."""

    leaf_Y: jax.Array   # (m_loc, b) masked WY vectors (zero on frozen rows)
    leaf_T: jax.Array   # (b, b)
    level_Y2: jax.Array  # (L, b, b) — zeroed == pass-through
    level_T: jax.Array   # (L, b, b)
    row_start: jax.Array  # () per-lane offset of this lane's C' block
    active: jax.Array     # () per-lane participation flag
    target: jax.Array     # () tree root lane (replicated)


class CAQRResult(NamedTuple):
    R: jax.Array                      # (min(m, n), n) upper trapezoidal,
                                      # replicated ([R1 R2] when m < n)
    factors: PanelFactors             # stacked over panels (leading axis)
    bundles: Optional[RecoveryBundle]  # stacked over panels, if requested


class SweepGeometry(NamedTuple):
    """Static geometry of a general-shape sweep (all Python ints).

    The sweep itself always runs at the *padded* shape ``(P*m_loc_pad,
    n_work)``: ``m_loc_pad`` is ``m_loc`` rounded up to a multiple of ``b``
    (>= b), so every panel's b diagonal rows live whole inside one lane and
    ``row_start`` clipping never engages; ``n_work`` rounds a ragged last
    panel up to width ``b``. Padding is with zeros — exact for every op in
    this family (see module docstring). ``n_panels`` covers only the left
    ``min(m, n)`` columns; for wide matrices the remaining columns are
    trailing-only riders (the ``R2`` block). ``k`` = ``min(m, n)`` is the
    row count of the returned R (rows beyond ``k`` in the padded assembly
    are rank-overshoot roundoff and are sliced away).
    """

    P: int
    b: int
    m_loc: int       # caller's per-lane rows
    n: int           # caller's columns
    m_loc_pad: int   # per-lane rows the sweep runs at (multiple of b, >= b)
    n_work: int      # column width the sweep runs at (>= n_panels * b)
    n_panels: int
    k: int           # min(P*m_loc, n): rows of the returned R

    @property
    def aligned(self) -> bool:
        """True iff no padding is needed (the seed-exact fast path)."""
        return self.m_loc_pad == self.m_loc and self.n_work == self.n

    @property
    def levels(self) -> int:
        """Tree levels of the P-lane butterfly (= log2 P). The online
        state machine's cursor arithmetic, boundary attribution, and
        REBUILD replay (``repro.ft.online``, ``repro.ft.driver``) all run
        off this."""
        assert self.P & (self.P - 1) == 0, self.P
        return self.P.bit_length() - 1


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def sweep_geometry(P: int, m_loc: int, n: int, b: int) -> SweepGeometry:
    """Padded sweep geometry for a general ``(P*m_loc) x n`` factorization."""
    assert m_loc >= 1 and n >= 1 and b >= 1, (m_loc, n, b)
    m_loc_pad = _ceil_to(m_loc, b)
    k = min(P * m_loc, n)
    n_panels = -(-k // b)
    n_work = max(n, n_panels * b)
    # P*m_loc_pad is a multiple of b and >= k, so the panel region fits.
    assert n_panels * b <= P * m_loc_pad
    return SweepGeometry(P=P, b=b, m_loc=m_loc, n=n, m_loc_pad=m_loc_pad,
                         n_work=n_work, n_panels=n_panels, k=k)


def pad_to_geometry(comm, A_local: jax.Array, geom: SweepGeometry) -> jax.Array:
    """Zero-pad each lane's block to the sweep's working shape (a no-op — the
    same array object — when the geometry is aligned)."""
    dr = geom.m_loc_pad - geom.m_loc
    dc = geom.n_work - geom.n
    if dr == 0 and dc == 0:
        return A_local
    return comm.map_local(lambda A: jnp.pad(A, ((0, dr), (0, dc))))(A_local)


def block_row_layout(A: jax.Array, P: int, m_loc: Optional[int] = None,
                     n: Optional[int] = None) -> jax.Array:
    """Distribute a whole ``(m, q)`` matrix into the 1-D block-row SimComm
    layout ``(P, m_loc, n)``: rows are zero-padded to ``P * m_loc`` and
    split contiguously, columns zero-padded to ``n``. Zero padding is exact
    for the sweep (DESIGN.md §7), so this is also the *bucket* embedding of
    the serving layer: pad every ragged request to one of a few compiled
    ``(m_loc, n)`` bucket shapes and batch them through the same program
    (``caqr_factorize_batched`` / ``repro.serve.qr_service``).

    ``m_loc`` defaults to ``ceil(m / P)`` (the tightest layout), ``n`` to
    the matrix's own width."""
    m, q = A.shape
    if m_loc is None:
        m_loc = -(-m // P)
    if n is None:
        n = q
    assert m <= P * m_loc and q <= n, (
        f"matrix ({m}, {q}) exceeds the ({P}x{m_loc}, {n}) bucket")
    A = jnp.pad(A, ((0, P * m_loc - m), (0, n - q)))
    return A.reshape(P, m_loc, n)


def panel_geometry(comm, k: int, b: int, m_loc: int):
    """Sweep bookkeeping of panel ``k`` (static): returns
    ``(col0, t_lane, row_start, active)``.

    ``col0``  — first column of the panel (the live-window start);
    ``t_lane``— owner of global rows [col0, col0+b): the tree root where the
                new R rows deposit;
    ``row_start`` / ``active`` — per-lane offset of the C' block and the
                participation flag (lanes whose rows are fully consumed by
                earlier panels are inactive).
    """
    idx = comm.axis_index()
    col0 = k * b
    t_lane = col0 // m_loc
    row_start_raw = col0 - idx * m_loc
    active = row_start_raw < m_loc
    row_start = jnp.clip(row_start_raw, 0, m_loc - b)
    return col0, t_lane, row_start, active


def lane_geometry(k: int, b: int, m_loc: int, lane: int):
    """``panel_geometry`` for one concrete lane, as Python scalars — the
    REBUILD replay (``repro.ft.driver``) recomputes a respawned lane's
    bookkeeping with this (it is static data, not lost state)."""
    col0 = k * b
    row_start_raw = col0 - lane * m_loc
    active = row_start_raw < m_loc
    row_start = min(max(row_start_raw, 0), m_loc - b)
    return col0, col0 // m_loc, row_start, active


def assemble_R(comm, R_rows: jax.Array, geom: SweepGeometry) -> jax.Array:
    """Stack per-panel replicated R row-blocks (n_panels, [P,] b, n_work)
    into the (k, n) upper-trapezoidal R (shared by the sweep and the FT
    driver). Rows beyond ``geom.k`` (rank overshoot of a padded or wide
    sweep) and zero-padded columns are sliced away; on aligned geometry both
    slices are no-ops and the assembly is bit-identical to the seed's."""
    rows = geom.n_panels * geom.b
    if comm.batched:
        lanes = R_rows.shape[1]   # P under SimComm, a chip's L under MeshComm
        R = R_rows.swapaxes(0, 1).reshape(lanes, rows, geom.n_work)
        return jnp.triu(R)[:, :geom.k, :geom.n]
    R = jnp.triu(R_rows.reshape(rows, geom.n_work))
    return R[:geom.k, :geom.n]


def advance_columns(comm, A_cur: jax.Array, window_next: jax.Array, col0: int):
    """Reattach the updated live window to the (untouched) dead columns."""
    return comm.map_local(
        lambda A, W: jnp.concatenate([A[:, :col0], W], axis=1)
    )(A_cur, window_next)


def extract_r_rows(comm, C_final: jax.Array, t_lane: int, col0: int):
    """The new R rows (global rows [col0, col0+b)) live at lane ``t_lane``'s
    final C' block; replicate them (one b x n all-reduce — the FT broadcast)
    and left-zero-pad back to full-width column indices."""
    idx = comm.axis_index()
    R_rows = comm.psum(
        comm.where(idx == t_lane, C_final, jnp.zeros_like(C_final))
    )
    return comm.map_local(lambda r: jnp.pad(r, ((0, 0), (col0, 0))))(R_rows)


def pad_bundle(bundle: RecoveryBundle, col0: int) -> RecoveryBundle:
    """Left-zero-pad a window-width recovery bundle to full width so the
    per-panel bundles stack (dead columns need no recovery)."""
    return RecoveryBundle(
        W=_pad_cols(bundle.W, col0),
        C_self=_pad_cols(bundle.C_self, col0),
        C_buddy=_pad_cols(bundle.C_buddy, col0),
        Y2=bundle.Y2, T=bundle.T, self_was_top=bundle.self_was_top,
    )


def make_panel_factors(
    comm, leaf_Y, leaf_T, level_Y2, level_T, row_start, active, t_lane
) -> PanelFactors:
    idx = comm.axis_index()
    return PanelFactors(
        leaf_Y=leaf_Y,
        leaf_T=leaf_T,
        level_Y2=level_Y2,
        level_T=level_T,
        row_start=row_start,
        active=active,
        target=jnp.broadcast_to(t_lane, jnp.shape(idx)),
    )


def _panel_step_windowed(comm, b: int, collect_bundles: bool, k: int, n: int):
    """One panel of the *windowed* right-looking sweep (static ``k``).

    The trailing update (leaf WY apply, per-level combines, writeback) is
    restricted to the live window ``A[:, k*b:]`` — the panel's own columns
    ride along because their C' rows ARE the R_kk deposit and the recovery
    bundle must cover them; the ``k*b`` already-factored columns to the left
    are dead (their R rows were extracted at their own panel step; what is
    left below the frontier is annihilated garbage) and are not touched.
    Per-column arithmetic is unchanged, so R and the live-window slice of
    every recovery bundle are bit-identical to the full-width sweep; R rows
    and bundles are zero-padded back to width ``n`` so the per-panel outputs
    stack (dead columns need no recovery — their bundle slots are zero).

    Fully-consumed lanes additionally skip their (identity) leaf apply via
    ``skip_consumed`` — the frozen-row skip.
    """
    def body(A_cur):
        m_loc, _n = comm.local_shape(A_cur)
        assert _n == n
        col0, t_lane, row_start, active = panel_geometry(comm, k, b, m_loc)

        window = comm.map_local(lambda A: A[:, col0:])(A_cur)
        panel = comm.map_local(lambda W: W[:, :b])(window)

        wy = comm.map_local(householder_qr_masked)(panel, row_start)
        leaf_Y = comm.where(active, wy.Y, jnp.zeros_like(wy.Y))
        leaf_T = comm.where(active, wy.T, jnp.zeros_like(wy.T))
        R_leaf = comm.where(active, wy.R, jnp.zeros_like(wy.R))

        level_Y2, level_T, _Rtree = ft_tsqr_combine(
            comm, R_leaf, t_lane, active_threshold=t_lane
        )
        factors = DistTSQRFactors(leaf_Y, leaf_T, level_Y2, level_T, R_leaf)

        win_next, bundle, C_final = trailing_update_ft(
            window, factors, comm, target=t_lane, row_start=row_start,
            active=active, dead_threshold=t_lane, skip_consumed=True,
        )
        A_next = advance_columns(comm, A_cur, win_next, col0)
        R_rows = extract_r_rows(comm, C_final, t_lane, col0)
        if collect_bundles:
            bundle = pad_bundle(bundle, col0)

        panel_factors = make_panel_factors(
            comm, leaf_Y, leaf_T, level_Y2, level_T, row_start, active, t_lane
        )
        out = (panel_factors, R_rows, bundle if collect_bundles else None)
        return A_next, out

    return body


def _pad_cols(x: jax.Array, left: int) -> jax.Array:
    """Left-pad the trailing (column) axis with zeros — realigns a windowed
    array with full-width column indices."""
    if left == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(left, 0)]
    return jnp.pad(x, pad)


def _panel_step(comm, b: int, collect_bundles: bool):
    """Returns the scan body for one panel of the sweep."""
    P = comm.axis_size()
    idx = comm.axis_index()

    def body(A_cur, k):
        m_loc, n = comm.local_shape(A_cur)
        col0 = k * b
        t_lane = (k * b) // m_loc  # owner of this panel's diagonal rows
        row_start_raw = k * b - idx * m_loc
        active = row_start_raw < m_loc
        row_start = jnp.clip(row_start_raw, 0, m_loc - b)

        panel = comm.map_local(
            lambda A, c: jax.lax.dynamic_slice_in_dim(A, c, b, axis=1)
        )(A_cur, jnp.broadcast_to(col0, jnp.shape(idx)))

        wy = comm.map_local(householder_qr_masked)(panel, row_start)
        leaf_Y = comm.where(active, wy.Y, jnp.zeros_like(wy.Y))
        leaf_T = comm.where(active, wy.T, jnp.zeros_like(wy.T))
        R_leaf = comm.where(active, wy.R, jnp.zeros_like(wy.R))

        level_Y2, level_T, _Rtree = ft_tsqr_combine(
            comm, R_leaf, t_lane, active_threshold=t_lane
        )
        factors = DistTSQRFactors(leaf_Y, leaf_T, level_Y2, level_T, R_leaf)

        A_next, bundle, C_final = trailing_update_ft(
            A_cur, factors, comm, target=t_lane, row_start=row_start,
            active=active, dead_threshold=t_lane,
        )
        # The new R rows (global rows [k*b, (k+1)*b)) live at lane t_lane's
        # C' block; replicate them (one b x n all-reduce — the FT broadcast).
        R_rows = comm.psum(
            comm.where(idx == t_lane, C_final, jnp.zeros_like(C_final))
        )

        panel_factors = PanelFactors(
            leaf_Y=leaf_Y,
            leaf_T=leaf_T,
            level_Y2=level_Y2,
            level_T=level_T,
            row_start=row_start,
            active=active,
            target=jnp.broadcast_to(t_lane, jnp.shape(idx)),
        )
        out = (panel_factors, R_rows, bundle if collect_bundles else None)
        return A_next, out

    return body


def caqr_factorize(
    A_local: jax.Array,
    comm,
    panel_width: int,
    collect_bundles: bool = False,
    use_scan: bool = True,
    windowed: Optional[bool] = None,
) -> CAQRResult:
    """FT-CAQR sweep of a general matrix. Returns replicated R plus
    implicit-Q panel factors.

    A_local: (m_loc, n) per lane (SimComm: (P, m_loc, n)). Any ``m x n``
        float shape is accepted — tall, wide, ragged (``n % b != 0``) and
        unaligned (``m_loc % b != 0``): the sweep runs at the zero-padded
        ``sweep_geometry`` shape (exact; see module docstring) and the
        returned R is ``(min(m, n), n)`` — square upper triangular when
        tall, ``[R1 R2]`` when wide. Factors and bundles live at the padded
        geometry (``caqr_apply_qt`` pads conforming inputs itself).
    panel_width: b.
    use_scan: True = lax.scan over panels (uniform per-iteration shapes,
        compile-time friendly; the trailing update spans all columns every
        panel). False = statically unrolled sweep — the performance variant.
    windowed: restrict panel k's trailing update to the live window
        ``A[:, k*b:]`` with *static* column slices, halving the sweep's
        trailing flops (see ``_panel_step_windowed``; outputs bit-identical
        to the full-width sweep). Requires the unrolled path; defaults to
        ``not use_scan``.
    """
    b = panel_width
    m_loc, n = comm.local_shape(A_local)
    P = comm.axis_size()
    geom = sweep_geometry(P, m_loc, n, b)
    A_work = pad_to_geometry(comm, A_local, geom)
    if windowed is None:
        windowed = not use_scan
    assert not (windowed and use_scan), \
        "the windowed sweep needs static column slices (use_scan=False)"
    n_panels, n_work = geom.n_panels, geom.n_work

    ks = jnp.arange(n_panels)
    if use_scan:
        body = _panel_step(comm, b, collect_bundles)
        _, (factors, R_rows, bundles) = jax.lax.scan(body, A_work, ks)
    else:
        outs = []
        A_cur = A_work
        body = None if windowed else _panel_step(comm, b, collect_bundles)
        for k in range(n_panels):
            if windowed:
                A_cur, out = _panel_step_windowed(
                    comm, b, collect_bundles, k, n_work
                )(A_cur)
            else:
                A_cur, out = body(A_cur, jnp.asarray(k))
            outs.append(out)
        factors = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
        R_rows = jnp.stack([o[1] for o in outs])
        bundles = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *[o[2] for o in outs])
            if collect_bundles
            else None
        )

    # R_rows: (n_panels, b, n_work) replicated (SimComm: (n_panels, P, b, n_work)).
    R = assemble_R(comm, R_rows, geom)
    return CAQRResult(R=R, factors=factors, bundles=bundles)


def caqr_apply_qt(
    B_local: jax.Array,
    factors: PanelFactors,
    comm,
    use_scan: bool = True,
) -> jax.Array:
    """Apply the implicit Q^T of a CAQR factorization to B (same row layout).

    Replays every panel's leaf WY + tree combine against B. For B = A this
    reproduces [R; 0] (up to the sweep's row bookkeeping) — the strongest
    internal consistency check of the stored factors.

    The factors of an unaligned factorization live at the padded
    ``sweep_geometry`` (see module docstring): B is zero-row-padded here to
    conform, and the result keeps the padded layout — R-row deposits of a
    ragged sweep land on pad-row positions, so slicing them off would lose
    them (``lstsq.caqr_lstsq`` collects deposits from exactly this layout).
    Aligned factors leave B untouched.
    """
    n_panels = jax.tree_util.tree_leaves(factors)[0].shape[0]
    m_fac = factors.leaf_Y.shape[-2]  # the factors' (padded) per-lane rows
    m_b = comm.local_shape(B_local)[0]
    if m_b != m_fac:
        assert m_b < m_fac, (m_b, m_fac)
        B_local = comm.map_local(
            lambda x: jnp.pad(x, ((0, m_fac - m_b), (0, 0)))
        )(B_local)

    def body(B_cur, pf: PanelFactors):
        dist = DistTSQRFactors(
            pf.leaf_Y, pf.leaf_T, pf.level_Y2, pf.level_T, pf.leaf_T
        )
        tgt = pf.target[0] if comm.batched else pf.target
        B_next, _, _ = trailing_update_ft(
            B_cur, dist, comm, target=tgt, row_start=pf.row_start,
            active=pf.active, dead_threshold=tgt,
        )
        return B_next, None

    if use_scan:
        B_out, _ = jax.lax.scan(body, B_local, factors)
    else:
        B_out = B_local
        for k in range(n_panels):
            pf = jax.tree_util.tree_map(lambda x: x[k], factors)
            B_out, _ = body(B_out, pf)
    return B_out


# Batched (vmap) front-end ---------------------------------------------------


def caqr_factorize_batched(
    A_batch: jax.Array, comm, panel_width: int, **kw
) -> CAQRResult:
    """Factorize a stack of independent same-shape problems in one call.

    A_batch carries a leading batch axis over ``caqr_factorize``'s layout:
    (batch, P, m_loc, n) under SimComm, (batch, m_loc, n) per lane under
    AxisComm. The whole sweep (any geometry — ragged, wide, scan or
    windowed) is ``jax.vmap``-ed, so the batch shares one compiled program;
    every field of the returned ``CAQRResult`` gains the leading batch axis.
    """
    return jax.vmap(
        lambda A: caqr_factorize(A, comm, panel_width, **kw)
    )(A_batch)


def caqr_apply_qt_batched(
    B_batch: jax.Array, factors: PanelFactors, comm, **kw
) -> jax.Array:
    """Batched companion of ``caqr_apply_qt``: replays a stack of
    factorizations (from ``caqr_factorize_batched``) against a conforming
    stack of right-hand sides."""
    return jax.vmap(
        lambda B, f: caqr_apply_qt(B, f, comm, **kw)
    )(B_batch, factors)


# SPMD wrapper ---------------------------------------------------------------


def caqr_factorize_spmd(A_local, axis_name: str, panel_width: int, **kw):
    return caqr_factorize(A_local, AxisComm(axis_name), panel_width, **kw)
