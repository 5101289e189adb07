"""Fused multi-point sweep megakernel: a whole panel in one launch.

The orchestrator's unfused execution of panel ``k`` issues ``1 + 2L``
``sweep_step`` dispatches (leaf, L butterfly levels, L trailing levels),
each a handful of XLA ops — O(points * ops) launches per segment. This
module collapses all of panel ``k``'s points into ONE launch.

Why whole-panel and not per-point pairs: trailing level 0 consumes the
**complete** stacked butterfly ladder (``level_Y2`` = all L levels), so no
pairwise (tsqr-l, trailing-l) fusion is possible — the first legal fusion
boundary after the leaf is the end of the panel. The panel-``(k-1)``
deposit stays *outside* the kernel (it belongs to the segment that ends at
``(k, leaf)`` — DESIGN.md §9), so fused boundary states remain exactly the
unfused ones.

Bit-compatibility: the kernel body executes the *same* core entry points
(``householder_qr_masked``, ``ft_tsqr_level``, ``_leaf_apply``,
``trailing_combine_level``) over an embedded ``SimComm`` that the unfused
``sweep_step`` path executes — one floating-point program, two launch
granularities. The Pallas interpreter and the ``xla`` engine both trace
that identical jaxpr, so fused output is bitwise-identical to stepping
(regression-gated in ``tests/test_fused_sweep.py``, the same discipline
that gated windowed-vs-seed in PR 1). The one thing fusion must NOT do is
re-tile the window across grid programs — a column split of the *leaf QR*
would regroup its row reductions. The megakernel therefore runs as a
single program over the resident window (grid ``()``); window VMEM budget
is the caller's responsibility (the live window shrinks as the sweep
advances, so the worst case is panel 0).

Also here: ``panel_qr_apply`` — the per-lane fused leaf (panel QR +
WY-apply over the window + C' extraction in one ``pallas_call``), the
lighter fusion entry exposed through ``core.householder.panel_qr_apply``
for callers that do not run a full sweep (tolerance-gated like the other
kernels, since it uses the kernel tile math rather than the core program).

Routing lives under the ``fused_sweep`` policy slot (see
``backend.kernel_mode``); the Pallas engines embed ``SimComm`` and are
SimComm-only — under ``AxisComm`` (shard_map) the caller uses the direct
math path, which is comm-generic.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.panel_qr import (
    householder_in_vmem,
    panel_qr_math,
    row_block,
    t_factor_in_vmem,
)
from repro.kernels.wy_apply import wy_apply_math

# Kernel-output field order of the fused panel (matches the SweepState
# in-flight fields it refills; ``tops`` is recomputed statically outside
# the kernel — see ``_tops``).
FUSED_FIELDS = (
    "leaf_Y", "leaf_T", "R_leaf", "R_carry",
    "level_Y2", "level_T", "C_local", "C_prime",
    "Ws", "Cs_self", "Cs_buddy",
)


# -- whole-panel megakernel ---------------------------------------------------


def fused_panel_math(comm, window, k: int, *, b: int, m_loc_pad: int,
                     levels: int) -> Dict[str, jax.Array]:
    """Panel ``k``'s full point sequence (leaf + L tsqr + L trailing) as one
    traced program over ``comm`` — literally the ``sweep_step`` bodies
    concatenated, minus the deposit. Comm-generic: the megakernel embeds it
    over ``SimComm``; the shard_map path calls it directly."""
    from repro.core.caqr import panel_geometry
    from repro.core.householder import householder_qr_masked
    from repro.core.trailing import _leaf_apply, trailing_combine_level
    from repro.core.tsqr import DistTSQRFactors, ft_tsqr_level

    col0 = k * b
    t_lane = col0 // m_loc_pad
    _c0, _t, row_start, active = panel_geometry(comm, k, b, m_loc_pad)

    # (k, leaf) — window panel QR, active-masked
    panel = comm.map_local(lambda W: W[:, :b])(window)
    wy = comm.map_local(householder_qr_masked)(panel, row_start)
    leaf_Y = comm.where(active, wy.Y, jnp.zeros_like(wy.Y))
    leaf_T = comm.where(active, wy.T, jnp.zeros_like(wy.T))
    R_leaf = comm.where(active, wy.R, jnp.zeros_like(wy.R))

    # (k, tsqr, 0..L-1) — the butterfly ladder
    carry = R_leaf
    Y2s, Ts = [], []
    for lvl in range(levels):
        carry, Y2, T = ft_tsqr_level(comm, carry, lvl, t_lane, t_lane)
        Y2s.append(Y2)
        Ts.append(T)
    level_Y2 = jnp.stack(Y2s)
    level_T = jnp.stack(Ts)

    # (k, trailing, 0) prologue — leaf-apply the live window
    dist = DistTSQRFactors(leaf_Y, leaf_T, level_Y2, level_T, R_leaf)
    C_local, C_prime = _leaf_apply(comm, dist, window, row_start,
                                   active=active, skip_consumed=True)
    C_prime = comm.where(active, C_prime, jnp.zeros_like(C_prime))

    # (k, trailing, 0..L-1) — the combine tree
    Ws, Cs_self, Cs_buddy, tops = [], [], [], []
    for lvl in range(levels):
        out = trailing_combine_level(
            comm, C_prime, level_Y2[lvl], level_T[lvl], lvl, t_lane, t_lane)
        C_prime = out.C_prime
        Ws.append(out.W)
        Cs_self.append(out.C_self)
        Cs_buddy.append(out.C_buddy)
        tops.append(out.is_top)

    return {
        "leaf_Y": leaf_Y, "leaf_T": leaf_T,
        "R_leaf": R_leaf, "R_carry": carry,
        "level_Y2": level_Y2, "level_T": level_T,
        "C_local": C_local, "C_prime": C_prime,
        "Ws": jnp.stack(Ws), "Cs_self": jnp.stack(Cs_self),
        "Cs_buddy": jnp.stack(Cs_buddy), "tops": tuple(tops),
    }


def _tops(P: int, t_lane: int, levels: int):
    """The per-level ``is_top`` flags, replicated outside the kernel: they
    depend only on static geometry (``is_top = ((idx >> lvl) & 1) ==
    ((t_lane >> lvl) & 1)``), so the megakernel need not emit bools."""
    idx = jnp.arange(P)
    return tuple(
        ((idx >> lvl) & 1) == ((t_lane >> lvl) & 1) for lvl in range(levels)
    )


@functools.partial(jax.jit,
                   static_argnames=("k", "b", "m_loc_pad", "levels",
                                    "interpret"))
def fused_panel_pallas(window: jax.Array, *, k: int, b: int, m_loc_pad: int,
                       levels: int, interpret: Optional[bool] = None
                       ) -> Dict[str, jax.Array]:
    """The megakernel: one ``pallas_call`` over the resident (P, m, w)
    window, SimComm embedded in the kernel body. SimComm-layout only."""
    from repro.core.comm import SimComm

    from repro.kernels import backend

    interpret = backend.resolve_interpret(interpret)
    P, m, w = window.shape
    assert levels >= 1, levels
    L = levels
    dt = window.dtype
    shapes = {
        "leaf_Y": (P, m, b), "leaf_T": (P, b, b),
        "R_leaf": (P, b, b), "R_carry": (P, b, b),
        "level_Y2": (L, P, b, b), "level_T": (L, P, b, b),
        "C_local": (P, m, w), "C_prime": (P, b, w),
        "Ws": (L, P, b, w), "Cs_self": (L, P, b, w), "Cs_buddy": (L, P, b, w),
    }

    def kernel(win_ref, *out_refs):
        res = fused_panel_math(SimComm(P), win_ref[...], k,
                               b=b, m_loc_pad=m_loc_pad, levels=levels)
        for name, ref in zip(FUSED_FIELDS, out_refs):
            ref[...] = res[name]

    outs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(shapes[f], dt) for f in FUSED_FIELDS],
        interpret=interpret,
        name="fused_sweep",
    )(window)
    result = dict(zip(FUSED_FIELDS, outs))
    result["tops"] = _tops(P, (k * b) // m_loc_pad, levels)
    return result


# -- per-lane fused leaf: panel QR + WY apply + C' extraction -----------------


def panel_qr_apply_math(W: jax.Array, row_start: jax.Array, *, b: int):
    """Tile program: QR the first ``b`` columns, apply Q^T to the whole
    window, extract the C' rows. Returns (Y, T, R, C, C_prime)."""
    Y, T, R = panel_qr_math(W[:, :b], row_start, num_cols=b)
    C = wy_apply_math(Y, T, W)
    Cp = jax.lax.dynamic_slice_in_dim(C, row_start, b, axis=0)
    return Y, T, R, C, Cp


def _panel_qr_apply_kernel(rs_ref, w_ref, y_ref, t_ref, r_ref, c_ref, cp_ref,
                           a_ref, *, b: int, chunk: int):
    # the in-VMEM panel program on a scratch copy of the panel columns,
    # the WY apply over the resident window, and the C' rows read back at
    # the traced row offset through the output ref. Not routed on TPU
    # (backend.PALLAS_ON_TPU): the window must fit VMEM whole, and Mosaic
    # wants the traced row offset of a multi-lane-tile load provably
    # 8-aligned.
    rs = rs_ref[0, 0]
    a_ref[...] = w_ref[:, :b]
    taus, R = householder_in_vmem(a_ref, y_ref, rs, chunk=chunk)
    T = t_factor_in_vmem(y_ref, taus, chunk=chunk)
    t_ref[...] = T
    r_ref[...] = R
    c_ref[...] = wy_apply_math(y_ref[...], T, w_ref[...])
    cp_ref[...] = c_ref[pl.ds(rs, b), :]


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def panel_qr_apply(W: jax.Array, row_start: jax.Array, b: int, *,
                   interpret: Optional[bool] = None):
    """One launch for the sweep's leaf step on one lane. W: (m, w), w >= b.

    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend

    interpret = backend.resolve_interpret(interpret)
    m, w = W.shape
    rs = jnp.asarray(row_start, jnp.int32).reshape((1, 1))
    kernel = functools.partial(_panel_qr_apply_kernel, b=b,
                               chunk=row_block(m))
    grid_spec = pl.GridSpec(
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, w), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m, b), lambda: (0, 0)),
            pl.BlockSpec((b, b), lambda: (0, 0)),
            pl.BlockSpec((b, b), lambda: (0, 0)),
            pl.BlockSpec((m, w), lambda: (0, 0)),
            pl.BlockSpec((b, w), lambda: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((m, b), W.dtype)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, b), W.dtype),
            jax.ShapeDtypeStruct((b, b), W.dtype),
            jax.ShapeDtypeStruct((b, b), W.dtype),
            jax.ShapeDtypeStruct((m, w), W.dtype),
            jax.ShapeDtypeStruct((b, w), W.dtype),
        ],
        interpret=interpret,
        name="fused_sweep",
    )(rs, W)


@functools.partial(jax.jit, static_argnames=("b",))
def panel_qr_apply_xla(W: jax.Array, row_start: jax.Array, b: int):
    """The ``xla`` compiled engine of the fused leaf (natural shapes)."""
    return panel_qr_apply_math(W, jnp.asarray(row_start, jnp.int32), b=b)


def panel_qr_apply_ref(W: jax.Array, row_start, b: int):
    """Oracle: the unfused composition of the pure core forms."""
    from repro.core import householder as hh

    rs = jnp.asarray(row_start, jnp.int32)
    wy = hh._householder_qr_masked(W[:, :b], rs)
    C = hh._apply_qt(wy.Y, wy.T, W)
    Cp = jax.lax.dynamic_slice_in_dim(C, rs, b, axis=0)
    return wy.Y, wy.T, wy.R, C, Cp
