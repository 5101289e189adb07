"""Pallas TPU kernel: fused compact-WY application  C <- C - Y (T^T (Y^T C)).

This is the flop hot-spot of CAQR (the trailing-matrix update applies the
panel's Q^T to every trailing column) and of the CAQR-Muon optimizer: two
back-to-back GEMMs plus a rank-b update.

Tiling: grid ``(column block j, phase p, row block i)`` over (bm, bn) tiles
of C, so VMEM holds a few tiles whatever m is (a column block of C at the
production leaf, 8192 x 256 f32, is 8 MiB on its own):
    p = 0 : acc  += Y_i^T C_ij          (b, bn)  MXU, over all row blocks
    p = 1 : acc   = T^T acc  (at i = 0)  (b, bn)  — the block's W
            out_ij = C_ij - Y_i acc      (bm, bn) MXU
The output block index is ``(i * p, j)``: it stays on block 0 through phase
0, which writes nothing, and is first written at ``p = 1, i = 0``.

Every op is column-parallel (all reductions run over rows), so the column
grid may end in a partial block: its out-of-range columns compute garbage
that never reaches a valid column, and their writes are dropped. Row blocks
divide m exactly (``row_block``) — the row reduction must see no garbage.

C is read twice and written once; Y is read twice per column block. At
b = 128 the update is memory-bound on v5e (b/2 flops per byte of C against
a ridge near 240), which is why the three ops are fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.householder import MATMUL_PRECISION
from repro.kernels.panel_qr import row_block, vmem_limit

_dot = functools.partial(jnp.dot, precision=MATMUL_PRECISION,
                         preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """``a^T b`` (contracting the row axis of both) at the sweep's
    precision, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=MATMUL_PRECISION,
                               preferred_element_type=jnp.float32)


def wy_apply_math(Y, T, C):
    """The ``xla`` engine's program on plain arrays (f32 accumulation)."""
    W = _dot_t(T, _dot_t(Y, C))
    return (C - _dot(Y, W)).astype(C.dtype)


def _wy_apply_kernel(y_ref, t_ref, c_ref, o_ref, acc_ref):
    p = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when((p == 0) & (i == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p == 0)
    def _():
        acc_ref[...] += _dot_t(y_ref[...], c_ref[...])

    @pl.when((p == 1) & (i == 0))
    def _():
        acc_ref[...] = _dot_t(t_ref[...].astype(jnp.float32), acc_ref[...])

    @pl.when(p == 1)
    def _():
        YW = _dot(y_ref[...].astype(jnp.float32), acc_ref[...])
        o_ref[...] = (c_ref[...] - YW).astype(o_ref.dtype)


@jax.jit
def wy_apply_xla(Y, T, C):
    """The ``xla`` compiled engine: untiled (the engine off TPU)."""
    return wy_apply_math(Y, T, C)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def wy_apply(
    Y: jax.Array,
    T: jax.Array,
    C: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused Q^T C. Shapes: Y (m, b), T (b, b), C (m, n); returns (m, n).

    Tiles: ``block_n`` columns (the last block may be partial) by the
    largest power-of-two row block <= 512 dividing m (``row_block``).
    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend
    interpret = backend.resolve_interpret(interpret)
    m, b = Y.shape
    mC, n = C.shape
    assert mC == m, (m, mC)
    bm = row_block(m)
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn), 2, m // bm)
    itemsize = C.dtype.itemsize
    return pl.pallas_call(
        _wy_apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, b), lambda j, p, i: (i, 0)),
            pl.BlockSpec((b, b), lambda j, p, i: (0, 0)),
            pl.BlockSpec((bm, bn), lambda j, p, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, p, i: (i * p, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), C.dtype),
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                2 * itemsize * (bm * b + b * b + 2 * bm * bn) + 4 * b * bn)),
        interpret=interpret,
        name="wy_apply",
    )(Y, T, C)
