"""Householder QR with compact-WY representation.

This is the numerical substrate of the paper: every node of the TSQR tree and
every trailing-matrix update is expressed through (Y, T, R) factors with
``Q = I - Y T Y^T`` (LAPACK ``geqrt`` convention: Y unit-lower-trapezoidal,
T upper-triangular, ``tau = diag(T)``).

Everything here is pure JAX, jit-able, and uses *masked* column loops instead
of dynamic slicing so the same code path serves as the oracle for the Pallas
kernels (``repro.kernels.ref`` re-exports these) and runs unmodified inside
``shard_map``.

Dispatch seam: the public entry points (``householder_qr_masked``,
``stacked_qr``, ``apply_qt``, ``stacked_apply_qt``) route through the fused
Pallas kernels in ``repro.kernels.ops`` when the backend policy says so (TPU
by default; see ``repro.kernels.backend``) and the call is a 2-D f32 one
the kernels cover. Note the 2-D test sees *per-call* rank: under ``vmap``
(SimComm's ``map_local``) per-lane tracers are 2-D, so vmapped call sites
dispatch too and batch through ``pallas_call``'s batching rule (exercised
by the forced-kernel SimComm sweep test). Explicitly batched arrays with a
leading lane axis, other dtypes, and explicit ``num_cols`` take the
pure-jnp implementations below, which are also the oracles the kernels are
validated against (``ref.py`` binds the ``_``-prefixed pure forms
directly, never the dispatchers); the SimComm trailing ``_combine``
vmaps its lane-stacked arrays into this seam where the dispatch is on.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# f32 means f32: every matmul of the sweep (here, in the rest of
# ``repro.core`` and in the kernels' tile math) runs at HIGHEST precision.
# A TPU otherwise lowers an f32 dot to a single bf16 pass, good to ~1e-3
# relative; CPU backends ignore the setting. DESIGN.md §10.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at the sweep's matmul precision."""
    return jnp.matmul(a, b, precision=MATMUL_PRECISION)


def _kernel_dispatch(*arrays) -> bool:
    """Route to repro.kernels.ops? (trace-time decision; lazy import keeps
    core importable without the kernels package and avoids the ops->ref->
    householder import cycle). The rank test is per call: vmapped per-lane
    tracers are 2-D and dispatch; only explicitly lane-stacked arrays are
    filtered out (see module docstring)."""
    if not all(a.ndim == 2 and a.dtype == jnp.float32 for a in arrays):
        return False
    from repro.kernels import backend

    return backend.dispatch_enabled()


class WY(NamedTuple):
    """Compact-WY factorization of an m x n panel: Q = I - Y T Y^T."""

    Y: jax.Array  # (m, n) unit lower trapezoidal (implicit unit diagonal NOT stored: Y[j,j] == 1 stored explicitly)
    T: jax.Array  # (n, n) upper triangular
    R: jax.Array  # (n, n) upper triangular


def _house(x: jax.Array, pivot: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Householder reflector for the masked vector ``x``.

    Returns ``(v, tau)`` with ``v[pivot] == 1``, ``v`` zero outside ``mask``,
    such that ``(I - tau v v^T) x = beta * e_pivot`` and beta = -sign(x0)*||x||.

    ``mask`` selects the active rows (pivot row included). Rows outside the
    mask are ignored entirely, which lets callers express "QR of the rows
    below the current panel" without any dynamic slicing.
    """
    x = jnp.where(mask, x, 0.0)
    x0 = x[pivot]
    sigma = jnp.sum(x * x) - x0 * x0
    norm_x = jnp.sqrt(x0 * x0 + sigma)
    sign = jnp.where(x0 >= 0, 1.0, -1.0).astype(x.dtype)
    beta = -sign * norm_x
    denom = x0 - beta
    # Degenerate column (all zeros below+at pivot): tau = 0, v = e_pivot.
    degenerate = norm_x <= jnp.asarray(1e-30, x.dtype)
    safe_denom = jnp.where(degenerate, 1.0, denom)
    v = x / safe_denom
    v = jnp.where(mask, v, 0.0)
    v = v.at[pivot].set(1.0)
    tau = jnp.where(degenerate, 0.0, (beta - x0) / beta)
    return v.astype(x.dtype), tau.astype(x.dtype)


def householder_qr_masked(
    A: jax.Array, row_start: jax.Array, num_cols: int | None = None
) -> WY:
    """Blocked Householder QR of the active rows of ``A`` (kernel-dispatched;
    see module docstring). ``num_cols`` forces the pure path."""
    if num_cols is None and _kernel_dispatch(A):
        from repro.kernels import ops

        Y, T, R = ops.panel_qr(A, row_start)
        return WY(Y=Y, T=T, R=R)
    return _householder_qr_masked(A, row_start, num_cols)


@functools.partial(jax.jit, static_argnames=("num_cols",))
def _householder_qr_masked(
    A: jax.Array, row_start: jax.Array, num_cols: int | None = None
) -> WY:
    """Blocked Householder QR of the active rows of ``A``.

    A: (m, n). Active rows are ``row_start <= i < m``; rows above ``row_start``
    are treated as frozen (they belong to already-computed R rows in CAQR) and
    are neither read nor written. Column ``j``'s pivot sits at row
    ``row_start + j``.

    Returns WY factors of the active submatrix embedded at their global row
    positions: Y is (m, n) with zeros in frozen rows, R is (n, n) and equals
    rows ``row_start .. row_start+n`` of the transformed matrix.
    """
    m, n = A.shape
    if num_cols is None:
        num_cols = n
    rows = jnp.arange(m)
    dtype = A.dtype

    def body(j, carry):
        A_, Y_, taus_ = carry
        pivot = row_start + j
        mask = rows >= pivot
        v, tau = _house(A_[:, j], pivot, mask)
        # Apply (I - tau v v^T) to every column; finished columns (k < j) have
        # zeros at and below the pivot in the masked region only where v acts,
        # and v^T A on them is ~0, so the full-width update is exact and keeps
        # the loop free of dynamic slices.
        w = mm(v, A_)  # (n,)
        A_ = A_ - tau * jnp.outer(v, w)
        Y_ = Y_.at[:, j].set(v)
        taus_ = taus_.at[j].set(tau)
        return A_, Y_, taus_

    # Carries derive from A (not fresh constants) so their varying-manual-axes
    # match under shard_map (see jax shard_map VMA rules).
    A_out, Y, taus = jax.lax.fori_loop(
        0,
        num_cols,
        body,
        (A, A * jnp.zeros((), dtype), A[0] * jnp.zeros((), dtype)),
    )
    R_rows = jax.lax.dynamic_slice_in_dim(A_out, row_start, n, axis=0)
    R = jnp.triu(R_rows[:n, :n])
    T = build_t(Y, taus)
    return WY(Y=Y, T=T, R=R)


def panel_qr_apply(W: jax.Array, row_start: jax.Array, b: int):
    """Fused leaf step: panel QR of ``W[:, :b]`` + Q^T applied to the whole
    window + C' row extraction. Returns ``(wy, C, C_prime)``.

    This is the sweep's per-lane leaf work as ONE kernel launch
    (kernel-dispatched through the ``fused_sweep`` policy slot); the pure
    path is the unfused composition of the primitives above.
    """
    if _kernel_dispatch(W):
        from repro.kernels import ops

        Y, T, R, C, Cp = ops.panel_qr_apply(W, row_start, b)
        return WY(Y=Y, T=T, R=R), C, Cp
    wy = _householder_qr_masked(W[:, :b], row_start)
    C = _apply_qt(wy.Y, wy.T, W)
    Cp = jax.lax.dynamic_slice_in_dim(C, row_start, b, axis=0)
    return wy, C, Cp


def householder_qr(A: jax.Array) -> WY:
    """QR of the full matrix (row_start = 0)."""
    return householder_qr_masked(A, jnp.asarray(0, jnp.int32))


def _householder_qr(A: jax.Array) -> WY:
    """Pure-jnp QR (no kernel dispatch) — the oracle form."""
    return _householder_qr_masked(A, jnp.asarray(0, jnp.int32))


@jax.jit
def build_t(Y: jax.Array, taus: jax.Array) -> jax.Array:
    """Forward T recurrence: T[:j,j] = -tau_j T[:j,:j] (Y[:,:j]^T y_j).

    Masked formulation over the Gram matrix G = Y^T Y so the loop body is
    static-shaped.
    """
    n = Y.shape[1]
    G = mm(Y.T, Y)  # (n, n)
    idx = jnp.arange(n)

    def body(j, T):
        g = jnp.where(idx < j, G[:, j], 0.0)  # (n,)
        col = -taus[j] * mm(T, g)
        col = jnp.where(idx < j, col, 0.0)
        col = col.at[j].set(taus[j])
        return T.at[:, j].set(col)

    T0 = G * jnp.zeros((), Y.dtype)  # derives from Y: VMA-consistent carry
    return jax.lax.fori_loop(0, n, body, T0)


def apply_qt(Y: jax.Array, T: jax.Array, C: jax.Array) -> jax.Array:
    """Q^T C = C - Y (T^T (Y^T C))  for Q = I - Y T Y^T (kernel-dispatched)."""
    if _kernel_dispatch(Y, T, C):
        from repro.kernels import ops

        return ops.wy_apply(Y, T, C)
    return _apply_qt(Y, T, C)


@jax.jit
def _apply_qt(Y: jax.Array, T: jax.Array, C: jax.Array) -> jax.Array:
    W = mm(T.T, mm(Y.T, C))
    return C - mm(Y, W)


@jax.jit
def apply_q(Y: jax.Array, T: jax.Array, C: jax.Array) -> jax.Array:
    """Q C = C - Y (T (Y^T C))."""
    W = mm(T, mm(Y.T, C))
    return C - mm(Y, W)


@jax.jit
def q_dense(Y: jax.Array, T: jax.Array) -> jax.Array:
    """Materialize Q = I - Y T Y^T (testing / small sizes only)."""
    m = Y.shape[0]
    return jnp.eye(m, dtype=Y.dtype) - mm(Y, mm(T, Y.T))


class StackedQR(NamedTuple):
    """Structured QR of two stacked b x b upper triangles [R_top; R_bot].

    The Householder vectors have the form Y = [I_b; Y2] with Y2 upper
    triangular (LAPACK ``tpqrt`` structure), so only Y2 and T are stored.
    Q = I - [I; Y2] T [I; Y2]^T and R is the new upper triangle.
    """

    Y2: jax.Array  # (b, b) upper triangular
    T: jax.Array  # (b, b) upper triangular
    R: jax.Array  # (b, b) upper triangular


def stacked_qr(R_top: jax.Array, R_bot: jax.Array) -> StackedQR:
    """QR of [R_top; R_bot] exploiting the triangular structure.

    This is the TSQR tree-combine operation. Both inputs are b x b upper
    triangular. Kernel-dispatched (LAPACK ``tpqrt`` analogue kernel); the
    pure path runs the generic masked Householder loop on the stacked
    2b x b matrix — it preserves the structure (Y's top block is exactly I,
    bottom block upper triangular) — and slices the structured parts out.
    """
    if _kernel_dispatch(R_top, R_bot):
        from repro.kernels import ops

        Y2, T, R = ops.stacked_qr(R_top, R_bot)
        return StackedQR(Y2=Y2, T=T, R=R)
    return _stacked_qr(R_top, R_bot)


@jax.jit
def _stacked_qr(R_top: jax.Array, R_bot: jax.Array) -> StackedQR:
    b = R_top.shape[0]
    S = jnp.concatenate([jnp.triu(R_top), jnp.triu(R_bot)], axis=0)  # (2b, b)
    wy = _householder_qr_masked(S, jnp.asarray(0, jnp.int32))
    Y2 = jnp.triu(wy.Y[b:, :])
    return StackedQR(Y2=Y2, T=wy.T, R=wy.R)


def stacked_apply_qt(
    sq: StackedQR, C_top: jax.Array, C_bot: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the stacked Q^T to [C_top; C_bot] using the paper's W form.

    W = T^T (C_top + Y2^T C_bot)
    C_top_hat = C_top - W          (paper: \\hat C'_0 = C'_0 - Y_0 W, Y_0 = I)
    C_bot_hat = C_bot - Y2 W       (paper: \\hat C'_1 = C'_1 - Y_1 W)

    Returns (C_top_hat, C_bot_hat, W); W is part of the recovery bundle.
    Kernel-dispatched to the fused trailing-combine kernel.
    """
    if _kernel_dispatch(sq.Y2, sq.T, C_top, C_bot):
        from repro.kernels import ops

        return ops.stacked_apply(sq.Y2, sq.T, C_top, C_bot)
    return _stacked_apply_qt(sq, C_top, C_bot)


@jax.jit
def _stacked_apply_qt(
    sq: StackedQR, C_top: jax.Array, C_bot: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    W = mm(sq.T.T, C_top + mm(sq.Y2.T, C_bot))
    return C_top - W, C_bot - mm(sq.Y2, W), W


@jax.jit
def stacked_apply_q(
    sq: StackedQR, C_top: jax.Array, C_bot: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Apply the stacked Q (not transposed) to [C_top; C_bot]."""
    W = mm(sq.T, C_top + mm(sq.Y2.T, C_bot))
    return C_top - W, C_bot - mm(sq.Y2, W)
