"""Pallas TPU kernel: Householder panel QR with compact-WY output.

The CAQR leaf hot-spot (LAPACK ``geqrt`` equivalent): factorize an (m, b)
panel tile entirely in VMEM, producing Y (unit-lower-trapezoidal Householder
vectors), T (upper triangular) and R.

TPU adaptation notes (vs. the CPU/GPU panel kernels the paper's MPI code
would call):
  * the whole tile is VMEM-resident — one HBM read of A, one write of
    (Y, T, R); the column loop streams row blocks of the resident tile
    through the vector units with no HBM traffic, which is what makes the
    panel latency- rather than bandwidth-bound on TPU;
  * the masked-pivot formulation (pivot row = row_start + j, rows above
    row_start frozen) is expressed with iota masks only: the traced column
    index selects through a lane mask, the traced pivot row through a row
    mask, and the tile is read and written in static-size row blocks
    (``pl.ds``) — Mosaic lowers no dynamic slice of a value;
  * m, b should be multiples of (8, 128) for full lane utilization; the
    ``ops`` wrapper pads when they are not.

Working set: A (double-buffered input), Y (double-buffered output) and the
working copy of A, 5 * m * b words, plus (b, b) T and R. At m = 16384,
b = 128 that is 40 MiB — above the default scoped-VMEM limit, so the kernel
sets its own (``vmem_limit``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.householder import MATMUL_PRECISION, mm

# Largest scoped-VMEM request a kernel here makes (v5e has 128 MiB of VMEM
# per core; leave room for Mosaic's internal scratch).
_VMEM_CAP = 100 * 2**20


def vmem_limit(nbytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose buffers need ``nbytes``: the
    buffers plus 25% and 4 MiB of headroom, at least the 32 MiB default of
    recent chips, at most ``_VMEM_CAP`` (a larger working set fails to
    compile, loudly)."""
    return int(min(max(nbytes * 5 // 4 + 4 * 2**20, 32 * 2**20), _VMEM_CAP))


def row_block(m: int, cap: int = 512) -> int:
    """Largest power-of-two row block <= ``cap`` that divides ``m`` (the
    tile's rows are a sublane multiple on the pallas routes); ``m`` itself
    when nothing does (interpret-mode calls at odd shapes)."""
    c = cap
    while c >= 8:
        if m % c == 0:
            return c
        c //= 2
    return m


def unrolled_loop(num_steps: int, body, init, unroll: int = 1):
    """``fori_loop(0, num_steps, body, init)`` with an ``unroll`` factor.

    ``unroll=1`` is the plain fori_loop; larger factors replicate the body
    inside a scan step — same operations in the same order, so results are
    unchanged, but the backend's per-iteration loop overhead is amortized.
    On CPU that overhead dominates these small-body column loops, which is
    what makes ``unroll`` the autotune knob for the ``xla`` engine
    (autotune.py).
    """
    if unroll == 1:
        return jax.lax.fori_loop(0, num_steps, body, init)
    return jax.lax.scan(
        lambda carry, j: (body(j, carry), None),
        init, jnp.arange(num_steps), unroll=unroll,
    )[0]


def panel_qr_math(A: jax.Array, row_start: jax.Array, *, num_cols: int,
                  unroll: int = 1):
    """The ``xla`` compiled engine's program on plain arrays: (Y, T, R) of
    the masked panel QR (``unroll`` only changes loop scheduling, not the
    operation sequence). The pallas kernel runs the same algorithm over
    VMEM refs (``householder_in_vmem``); both are gated against the
    oracle in ``repro.kernels.ref``."""
    m, b = A.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)[:, 0]
    dtype = A.dtype

    def col_step(j, carry):
        A_, Y_, taus_ = carry
        pivot = row_start + j
        mask = rows >= pivot
        x = jnp.where(mask, A_[:, j], 0.0)
        x0 = x[pivot]
        sigma = jnp.sum(x * x) - x0 * x0
        norm_x = jnp.sqrt(x0 * x0 + sigma)
        sign = jnp.where(x0 >= 0, 1.0, -1.0).astype(dtype)
        beta = -sign * norm_x
        degenerate = norm_x <= jnp.asarray(1e-30, dtype)
        denom = jnp.where(degenerate, 1.0, x0 - beta)
        v = jnp.where(mask, x / denom, 0.0)
        v = v.at[pivot].set(1.0)
        tau = jnp.where(degenerate, 0.0, (beta - x0) / beta).astype(dtype)
        w = mm(v, A_)
        A_ = A_ - tau * v[:, None] * w[None, :]
        Y_ = Y_.at[:, j].set(v)
        taus_ = taus_.at[j].set(tau)
        return A_, Y_, taus_

    A_out, Y, taus = unrolled_loop(
        num_cols, col_step, (A, A * 0.0, A[0] * 0.0), unroll
    )

    # T forward recurrence over the Gram matrix.
    G = mm(Y.T, Y)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)[:, 0]

    def t_step(j, T):
        g = jnp.where(cols < j, G[:, j], 0.0)
        col = -taus[j] * mm(T, g)
        col = jnp.where(cols < j, col, 0.0)
        col = col.at[j].set(taus[j])
        return T.at[:, j].set(col)

    T = unrolled_loop(num_cols, t_step, G * 0.0, unroll)

    # R = rows [row_start, row_start + b) of the transformed tile.
    R_rows = jax.lax.dynamic_slice(A_out, (row_start, 0), (b, b))
    tri = cols[:, None] <= cols[None, :]
    return Y, T, jnp.where(tri, R_rows, 0.0)


# -- the in-VMEM program (pallas kernel bodies) ------------------------------


def householder_in_vmem(w_ref, y_ref, row_start, *, chunk: int):
    """Masked Householder QR of the (m, b) tile held in ``w_ref``, in place.

    On return ``w_ref`` holds the transformed tile and ``y_ref`` the
    Householder vectors (zero above each pivot). Returns ``(taus, R)``:
    the (1, b) reflector scales and the (b, b) upper-triangular R — rows
    ``[row_start, row_start + b)`` of the transformed tile (the start
    clamped into the tile like ``lax.dynamic_slice``), read through the ref.

    Three passes over ``chunk``-row blocks per column: the masked column
    norm, the reflector (written to Y) with its row vector ``w = v^T A``,
    and the rank-1 update. The traced column ``j`` selects through a lane
    mask and the pivot row through a row mask, so nothing slices a value at
    a traced offset."""
    m, b = w_ref.shape
    dt = w_ref.dtype
    n_chunks = m // chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    bsub = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    zero11 = jnp.zeros((1, 1), dt)

    def block(c):
        r0 = pl.multiple_of(c * chunk, chunk)
        return pl.ds(r0, chunk), r0 + sub

    def clear_y(c, carry):
        rs, _ = block(c)
        y_ref[rs, :] = jnp.zeros((chunk, b), dt)
        return carry

    jax.lax.fori_loop(0, n_chunks, clear_y, 0)

    def col_step(j, taus):
        pivot = row_start + j
        colmask = lane == j

        def column(blk, rows):
            x = jnp.sum(jnp.where(colmask, blk, 0.0), axis=1, keepdims=True)
            return jnp.where(rows >= pivot, x, 0.0)

        def norm_pass(c, acc):
            ss, x0 = acc
            rs, rows = block(c)
            x = column(w_ref[rs, :], rows)
            return (ss + jnp.sum(x * x, keepdims=True),
                    x0 + jnp.sum(jnp.where(rows == pivot, x, 0.0),
                                 keepdims=True))

        ss, x0 = jax.lax.fori_loop(0, n_chunks, norm_pass, (zero11, zero11))
        sigma = ss - x0 * x0
        norm_x = jnp.sqrt(x0 * x0 + sigma)
        sign = jnp.where(x0 >= 0, 1.0, -1.0).astype(dt)
        beta = -sign * norm_x
        degenerate = norm_x <= jnp.asarray(1e-30, dt)
        denom = jnp.where(degenerate, 1.0, x0 - beta)
        tau = jnp.where(degenerate, 0.0, (beta - x0) / beta).astype(dt)

        def reflect_pass(c, wrow):
            rs, rows = block(c)
            blk = w_ref[rs, :]
            v = jnp.where(rows >= pivot, column(blk, rows) / denom, 0.0)
            v = jnp.where(rows == pivot, 1.0, v).astype(dt)
            y_ref[rs, :] = jnp.where(colmask, v, y_ref[rs, :])
            return wrow + jnp.sum(v * blk, axis=0, keepdims=True)

        wrow = jax.lax.fori_loop(0, n_chunks, reflect_pass,
                                 jnp.zeros((1, b), dt))

        def update_pass(c, carry):
            rs, _ = block(c)
            v = jnp.sum(jnp.where(colmask, y_ref[rs, :], 0.0), axis=1,
                        keepdims=True)
            w_ref[rs, :] = w_ref[rs, :] - tau * v * wrow
            return carry

        jax.lax.fori_loop(0, n_chunks, update_pass, 0)
        return jnp.where(colmask, tau, taus)

    taus = jax.lax.fori_loop(0, b, col_step, jnp.zeros((1, b), dt))
    R = w_ref[pl.ds(jnp.clip(row_start, 0, m - b), b), :]
    return taus, jnp.where(bsub <= lane, R, 0.0)


def t_factor_in_vmem(y_ref, taus, *, chunk: int):
    """Upper-triangular T of ``Q = I - Y T Y^T`` from the Y held in
    ``y_ref`` and the (1, b) ``taus``: the forward recurrence over the
    Gram matrix ``G = Y^T Y`` (accumulated over row blocks), with the
    traced column selected through masks."""
    m, b = y_ref.shape
    dt = y_ref.dtype

    def gram(c, G):
        yb = y_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
        return G + jax.lax.dot_general(
            yb, yb, (((0,), (0,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32)

    G = jax.lax.fori_loop(0, m // chunk, gram,
                          jnp.zeros((b, b), jnp.float32)).astype(dt)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    bsub = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)

    def t_step(j, T):
        # row j of the symmetric G is its column j, laid out along lanes
        g = jnp.sum(jnp.where(bsub == j, G, 0.0), axis=0, keepdims=True)
        g = jnp.where(lane < j, g, 0.0)
        tau = jnp.sum(jnp.where(lane == j, taus, 0.0), axis=1, keepdims=True)
        col = -tau * jnp.sum(T * g, axis=1, keepdims=True)
        col = jnp.where(bsub < j, col, 0.0)
        col = jnp.where(bsub == j, tau, col)
        return jnp.where(lane == j, col, T)

    return jax.lax.fori_loop(0, b, t_step, jnp.zeros((b, b), dt))


def _panel_qr_kernel(rs_ref, a_ref, y_ref, t_ref, r_ref, w_ref, *,
                     chunk: int):
    m = a_ref.shape[0]

    def load(c, carry):
        rs = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        w_ref[rs, :] = a_ref[rs, :]
        return carry

    jax.lax.fori_loop(0, m // chunk, load, 0)
    taus, R = householder_in_vmem(w_ref, y_ref, rs_ref[0, 0], chunk=chunk)
    t_ref[...] = t_factor_in_vmem(y_ref, taus, chunk=chunk)
    r_ref[...] = R


@functools.partial(jax.jit, static_argnames=("unroll",))
def panel_qr_xla(A: jax.Array, row_start: jax.Array, *, unroll: int = 2):
    """The ``xla`` compiled engine: the panel program as plain compiled XLA
    — the engine off TPU (``backend.compiled_engine``). No alignment
    contract: runs at natural shapes. ``unroll`` is the engine's autotune
    knob (column-loop unroll factor)."""
    rs = jnp.asarray(row_start, jnp.int32)
    return panel_qr_math(A, rs, num_cols=A.shape[1], unroll=unroll)


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_qr(A: jax.Array, row_start: jax.Array, *, interpret: bool | None = None):
    """Pallas panel QR. Returns (Y, T, R) like ``ref.panel_qr``.

    A: (m, b) f32, m % 8 == 0 and b % 128 == 0 for full TPU tiling (the
    kernel itself is shape-generic; alignment is a performance contract —
    ``ops.panel_qr`` pads up to it).
    row_start: scalar int32 — rows above it are frozen (CAQR sweep).
    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend
    interpret = backend.resolve_interpret(interpret)
    m, b = A.shape
    chunk = row_block(m)
    # (1, 1): under vmap the lane axis blocks in front of two whole dims
    rs = jnp.asarray(row_start, jnp.int32).reshape((1, 1))
    kernel = functools.partial(_panel_qr_kernel, chunk=chunk)
    grid_spec = pl.GridSpec(
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, b), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m, b), lambda: (0, 0)),
            pl.BlockSpec((b, b), lambda: (0, 0)),
            pl.BlockSpec((b, b), lambda: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((m, b), A.dtype)],
    )
    Y, T, R = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, b), A.dtype),
            jax.ShapeDtypeStruct((b, b), A.dtype),
            jax.ShapeDtypeStruct((b, b), A.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(5 * m * b * A.dtype.itemsize)),
        interpret=interpret,
        name="panel_qr",
    )(rs, A)
    return Y, T, R
