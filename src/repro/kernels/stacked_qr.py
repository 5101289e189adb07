"""Pallas TPU kernel: structured QR of two stacked upper triangles.

The TSQR tree-combine (LAPACK ``tpqrt`` analogue): QR of [R_top; R_bot] where
both are (b, b) upper triangular. The Householder vectors have the structure
Y = [I; Y2] with Y2 upper triangular, so the kernel emits only (Y2, T, R).

Entirely VMEM-resident (everything is b x b; b <= 256 -> < 1 MiB); the value
of the kernel is latency: the combine sits on the critical path of every
TSQR tree level, so one pallas_call replaces ~6 XLA ops and their HBM
round-trips.

Also provides the fused *trailing combine* kernel (paper Alg. 2 inner body):
    W         = T^T (C_top + Y2^T C_bot)
    C_top_hat = C_top - W
    C_bot_hat = C_bot - Y2 W
tiled over the trailing dimension n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.householder import MATMUL_PRECISION
from repro.kernels.panel_qr import (
    householder_in_vmem,
    panel_qr_math,
    row_block,
    t_factor_in_vmem,
)


def stacked_qr_math(R_top: jax.Array, R_bot: jax.Array, *, b: int,
                    unroll: int = 1):
    """The ``xla`` engine's combine program on plain arrays: (Y2, T, R) of
    QR([R_top; R_bot]) — the masked panel program of ``panel_qr_math`` on
    the 2b x b stack, which preserves the triangular structure exactly (top
    block of Y is I, bottom block upper triangular)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)[:, 0]
    tri = cols[:, None] <= cols[None, :]
    S = jnp.concatenate(
        [jnp.where(tri, R_top, 0.0), jnp.where(tri, R_bot, 0.0)],
        axis=0,
    )
    Y, T, R = panel_qr_math(S, jnp.asarray(0, jnp.int32), num_cols=b,
                            unroll=unroll)
    return jnp.where(tri, Y[b:, :], 0.0), T, R


def _stacked_qr_kernel(rt_ref, rb_ref, y2_ref, t_ref, r_ref, s_ref, y_ref,
                       *, b: int):
    # the 2b x b stack in VMEM scratch, then the in-VMEM panel program
    tri = (jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (1, b), 1))
    s_ref[:b, :] = jnp.where(tri, rt_ref[...], 0.0)
    s_ref[b:, :] = jnp.where(tri, rb_ref[...], 0.0)
    chunk = row_block(2 * b)
    taus, R = householder_in_vmem(s_ref, y_ref, 0, chunk=chunk)
    y2_ref[...] = jnp.where(tri, y_ref[b:, :], 0.0)
    t_ref[...] = t_factor_in_vmem(y_ref, taus, chunk=chunk)
    r_ref[...] = R


@functools.partial(jax.jit, static_argnames=("unroll",))
def stacked_qr_xla(R_top: jax.Array, R_bot: jax.Array, *, unroll: int = 2):
    """The ``xla`` compiled engine for the tree combine (natural shapes);
    ``unroll`` is its autotune knob (column-loop unroll factor)."""
    return stacked_qr_math(R_top, R_bot, b=R_top.shape[0], unroll=unroll)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stacked_qr(R_top: jax.Array, R_bot: jax.Array, *, interpret: bool | None = None):
    """(Y2, T, R) of QR([R_top; R_bot]); all (b, b).

    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend
    interpret = backend.resolve_interpret(interpret)
    b = R_top.shape[0]
    kernel = functools.partial(_stacked_qr_kernel, b=b)
    spec = pl.BlockSpec((b, b), lambda: (0, 0))
    Y2, T, R = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, b), R_top.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((2 * b, b), R_top.dtype)] * 2,
        interpret=interpret,
        name="stacked_qr",
    )(R_top, R_bot)
    return Y2, T, R


def stacked_apply_math(Y2, T, Ct, Cb):
    """The trailing-combine tile program (f32 accumulation) on plain
    arrays; returns (Ct_hat, Cb_hat, W) in ``Ct.dtype``."""
    dot = functools.partial(jnp.dot, precision=MATMUL_PRECISION,
                            preferred_element_type=jnp.float32)
    inner = Ct + dot(Y2.T, Cb)
    W = dot(T.T, inner)
    ot = (Ct - W).astype(Ct.dtype)
    ob = (Cb - dot(Y2, W)).astype(Ct.dtype)
    return ot, ob, W.astype(Ct.dtype)


def _stacked_apply_kernel(y2_ref, t_ref, ct_ref, cb_ref, ot_ref, ob_ref, w_ref):
    ot, ob, W = stacked_apply_math(y2_ref[...], t_ref[...], ct_ref[...],
                                   cb_ref[...])
    ot_ref[...] = ot.astype(ot_ref.dtype)
    ob_ref[...] = ob.astype(ob_ref.dtype)
    w_ref[...] = W.astype(w_ref.dtype)


@jax.jit
def stacked_apply_xla(Y2, T, C_top, C_bot):
    """The ``xla`` compiled engine for the fused trailing combine. Column
    tiling is dropped: every op here is column-parallel (all reductions run
    over rows), so the untiled call is the same floating-point program."""
    return stacked_apply_math(Y2, T, C_top, C_bot)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stacked_apply(
    Y2: jax.Array,
    T: jax.Array,
    C_top: jax.Array,
    C_bot: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool | None = None,
):
    """Fused trailing combine (paper Alg. 2 body). Returns (Ct_hat, Cb_hat, W).

    Y2, T: (b, b); C_top, C_bot: (b, n). Tiled over n in ``block_n``
    columns; every op is column-parallel, so the last block may be partial
    (its out-of-range columns are never written).
    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend
    interpret = backend.resolve_interpret(interpret)
    b, n = C_top.shape
    bn = min(block_n, n)
    bspec = pl.BlockSpec((b, b), lambda j: (0, 0))
    cspec = pl.BlockSpec((b, bn), lambda j: (0, j))
    ot, ob, W = pl.pallas_call(
        _stacked_apply_kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[bspec, bspec, cspec, cspec],
        out_specs=[cspec, cspec, cspec],
        out_shape=[jax.ShapeDtypeStruct((b, n), C_top.dtype)] * 3,
        interpret=interpret,
        name="stacked_apply",
    )(Y2, T, C_top, C_bot)
    return ot, ob, W
