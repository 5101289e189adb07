"""Pallas TPU kernel: Householder panel QR with compact-WY output.

The CAQR leaf hot-spot (LAPACK ``geqrt`` equivalent): factorize an (m, b)
panel tile entirely in VMEM, producing Y (unit-lower-trapezoidal Householder
vectors), T (upper triangular) and R.

TPU adaptation notes (vs. the CPU/GPU panel kernels the paper's MPI code
would call):
  * the whole tile is VMEM-resident — one HBM read of A, one write of
    (Y, T, R); the column loop streams the resident tile through the
    vector units with no HBM traffic, which is what makes the panel
    latency- rather than bandwidth-bound on TPU;
  * the column loop runs on the tile transposed once into VMEM scratch,
    rows along lanes: a column is one row of the scratch, its norm and
    reflector are lane-dense elementwise work, and ``w = A^T v`` reduces
    across lanes once per row of the scratch rather than once per block
    and column;
  * the masked-pivot formulation (pivot row = row_start + j, rows above
    row_start frozen) is expressed with iota masks and ref slices only:
    the traced pivot row selects through a lane mask, the traced column
    through a row offset of the scratch ref (``pl.ds``) — Mosaic lowers no
    dynamic slice of a value;
  * m should be a multiple of 128 (rows are lanes of the scratch) and b of
    128 for full tiling; the ``ops`` wrapper pads when they are not.

Working set: A (double-buffered input), Y (double-buffered output) and the
transposed working tile and Y, 6 * m * b words, plus (b, b) T and R. At
m = 16384, b = 128 that is 48 MiB — above the default scoped-VMEM limit,
so the kernel sets its own (``vmem_limit``).
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


# Vregs of the transposed tile that one step of the column update may
# cover (``update_rows``).
_UPDATE_VREGS = 512


def update_rows(m: int, b: int, sub: int = 8) -> int:
    """Rows of the transposed (b, m) tile that one step of the column
    update covers, ``b`` padded to a ``sub`` multiple: the most that divide
    it and hold at most ``_UPDATE_VREGS`` vregs of ``sub`` x 128 words,
    ``sub`` at the least. A step costs a fixed latency (its lane reduction)
    besides its work, and updates the finished rows it holds for nothing:
    on a v5e the 15744-row leaf tile is fastest in steps of 32 rows, the
    1024- and 256-row tiles (the service's buckets, the tree combine) in
    one step of the whole tile. The step changes no output bit."""
    bs = -(-b // sub) * sub
    for rows in range(bs, sub - 1, -sub):
        if bs % rows == 0 and rows * m <= _UPDATE_VREGS * sub * 128:
            return rows
    return sub


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


def householder_in_vmem(a_ref, y_ref, row_start):
    """Masked Householder QR of the (m, b) tile in ``a_ref``.

    Writes the Householder vectors (zero above each pivot) to ``y_ref`` and
    returns ``(taus, R)``: the (1, b) reflector scales and the (b, b)
    upper-triangular R — rows ``[row_start, row_start + b)`` of the
    transformed tile (the start clamped into the tile like
    ``lax.dynamic_slice``), read through ``y_ref`` before Y lands there.

    The column loop runs on the tile held transposed in VMEM scratch, rows
    along lanes: column ``j`` of A is row ``j`` of the scratch, so the
    masked norm, the pivot and the reflector are lane-dense elementwise ops
    with one final reduction each. Each column then makes one pass over the
    scratch in steps of ``update_rows`` rows, from the step holding row
    ``j`` down: ``w`` of the step's rows (multiply, accumulate across
    lanes, one lane reduction per row) and their rank-1 update. Steps above
    hold finished columns and are not touched; finished rows inside the
    first step change only at lanes past their own pivot, which nothing
    reads. The traced column is read, and its reflector written, as one
    row of the scratch refs at a traced offset (``pl.ds``); nothing slices
    a value at a traced offset."""
    m, b = a_ref.shape
    dt = a_ref.dtype
    sub = 32 // dt.itemsize          # rows of one (sub, 128) vreg tile
    bs = -(-b // sub) * sub
    grp = update_rows(m, b, sub)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    blane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    bsub = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)

    def factor(at_ref, yt_ref, v_ref):
        # rows b..bs-1 pad the scratch to whole (sub, 128) tiles; each row
        # of the update reads only itself, and no result reads these
        at_ref[:b, :] = a_ref[...].T

        def col_step(j, taus):
            pivot = row_start + j
            x = jnp.where(lane >= pivot, at_ref[pl.ds(j, 1), :], 0.0)
            x0 = jnp.sum(jnp.where(lane == pivot, x, 0.0), keepdims=True)
            sigma = jnp.sum(x * x, keepdims=True) - x0 * x0
            norm_x = jnp.sqrt(x0 * x0 + sigma)
            sign = jnp.where(x0 >= 0, 1.0, -1.0).astype(dt)
            beta = -sign * norm_x
            degenerate = norm_x <= jnp.asarray(1e-30, dt)
            denom = jnp.where(degenerate, 1.0, x0 - beta)
            tau = jnp.where(degenerate, 0.0, (beta - x0) / beta).astype(dt)
            v = jnp.where(lane >= pivot, x / denom, 0.0)
            v = jnp.where(lane == pivot, 1.0, v).astype(dt)
            yt_ref[pl.ds(j, 1), :] = v
            v_ref[...] = jnp.broadcast_to(v, (grp, m))

            def update(h, carry):
                rs = pl.ds(pl.multiple_of(h * grp, grp), grp)
                vv = v_ref[...]
                w = jnp.sum(at_ref[rs, :] * vv, axis=1, keepdims=True)
                at_ref[rs, :] = at_ref[rs, :] - (tau * w) * vv
                return carry

            jax.lax.fori_loop(j // grp, bs // grp, update, 0)
            return jnp.where(blane == j, tau, taus)

        taus = jax.lax.fori_loop(0, b, col_step, jnp.zeros((1, b), dt))
        # the transformed tile in (m, b) layout, for R; then Y over it
        y_ref[...] = at_ref[:b, :].T
        R = y_ref[pl.ds(jnp.clip(row_start, 0, m - b), b), :]
        y_ref[...] = yt_ref[:b, :].T
        return taus, jnp.where(bsub <= blane, R, 0.0)

    return pl.run_scoped(factor, pltpu.VMEM((bs, m), dt),
                         pltpu.VMEM((bs, m), dt), pltpu.VMEM((grp, m), dt))


def t_factor_in_vmem(y_ref, taus):
    """Upper-triangular T of ``Q = I - Y T Y^T`` from the Y held in
    ``y_ref`` and the (1, b) ``taus``: the forward recurrence over the
    Gram matrix ``G = Y^T Y`` (accumulated over row blocks), with the
    traced column selected through masks."""
    m, b = y_ref.shape
    dt = y_ref.dtype
    chunk = row_block(m)

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


def _panel_qr_kernel(rs_ref, a_ref, y_ref, t_ref, r_ref):
    taus, R = householder_in_vmem(a_ref, y_ref, rs_ref[0, 0])
    t_ref[...] = t_factor_in_vmem(y_ref, taus)
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

    A: (m, b) f32, m % 128 == 0 and b % 128 == 0 for full TPU tiling (the
    kernel itself is shape-generic; alignment is a performance contract —
    ``ops.panel_qr`` pads up to it).
    row_start: scalar int32 — rows above it are frozen (CAQR sweep).
    interpret: None resolves via ``backend.interpret_default()``.
    """
    from repro.kernels import backend
    interpret = backend.resolve_interpret(interpret)
    m, b = A.shape
    # (1, 1): under vmap the lane axis blocks in front of two whole dims
    rs = jnp.asarray(row_start, jnp.int32).reshape((1, 1))
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
    )
    Y, T, R = pl.pallas_call(
        _panel_qr_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, b), A.dtype),
            jax.ShapeDtypeStruct((b, b), A.dtype),
            jax.ShapeDtypeStruct((b, b), A.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(6 * m * b * A.dtype.itemsize)),
        interpret=interpret,
        name="panel_qr",
    )(rs, A)
    return Y, T, R
