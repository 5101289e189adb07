"""Public wrappers for the Pallas kernels — the dispatch seam.

``repro.core`` routes its hot operations here (see ``backend.dispatch_enabled``
for when). Each call resolves the per-op policy (``backend.kernel_mode`` —
compiled / interpret / oracle) at trace time and routes accordingly:

* **compiled / pallas** — native non-interpret ``pallas_call`` (Mosaic),
  the engine on TPU.
* **compiled / xla** — the op's tile program as plain compiled XLA
  (``*_xla`` in the kernel modules): the engine off TPU
  (``backend.compiled_engine`` is the written policy). No alignment
  contract: runs at natural shapes, no padding copies.
* **interpret** — the Pallas interpreter; the validation vehicle, never
  chosen automatically.
* **oracle** — the pure-jnp reference in ``ref.py``; also the automatic
  route for dtypes outside the kernels' envelope (f32 and bf16 are in).

The *pallas* routes enforce the alignment contract (rows in
``backend.sublane(dtype)`` multiples — the panel QR's in lane multiples,
as it holds its tile transposed — panel widths in lane-pad multiples)
by zero-padding up to it and slicing back — padding with zeros is exact in
exact arithmetic for every op in this family (extra zero rows/columns
produce degenerate reflectors with tau = 0 and contribute nothing to any
inner product); in floats the padded result differs from the unpadded
kernel only by the backend regrouping reductions at the larger size
(roundoff-level).

Block shapes (``block_n`` column tiles, ``lane_pad`` width padding, the
``xla`` engines' column-loop ``unroll``) default to the autotuner's winner
for the call's (op, geometry, dtype, variant) cell when one was tuned
(``repro.kernels.autotune``), else to the static defaults. Explicit
arguments always win — that is how the tuner itself times candidates.

``use_kernels(False)`` (or REPRO_NO_KERNELS=1) routes every call to the
oracle — the escape hatch for anything outside the kernels' envelope. The
policy state lives in ``backend`` (shared with the core dispatch, read at
trace time), so the two layers cannot disagree.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, backend, ref
from repro.kernels import panel_qr as _panel
from repro.kernels import stacked_qr as _stacked
from repro.kernels import wy_apply as _wy

# shared override: use_kernels(None) restores the automatic policy
use_kernels = backend.use_kernels

DEFAULT_WY_BLOCK_N = 256
DEFAULT_STACKED_BLOCK_N = 512
DEFAULT_QR_UNROLL = 2

_SUPPORTED_DTYPES = ("float32", "bfloat16")

# per-call routes (the resolved leg of the policy)
_R_ORACLE = "oracle"
_R_INTERPRET = "interpret"
_R_PALLAS = backend.ENGINE_PALLAS
_R_XLA = backend.ENGINE_XLA


def _interpret() -> bool:
    return backend.interpret_default()


def _route(op: str, *arrays) -> str:
    """Resolve policy + dtype envelope to one of oracle/interpret/pallas/xla."""
    if any(a.dtype.name not in _SUPPORTED_DTYPES for a in arrays):
        return _R_ORACLE
    mode = backend.kernel_mode(op)
    if mode == backend.MODE_ORACLE:
        return _R_ORACLE
    if mode == backend.MODE_INTERPRET:
        return _R_INTERPRET
    return backend.compiled_engine(op)


def _lane_pad(op: str, geometry, dtype, route: str, explicit) -> int:
    if explicit is not None:
        return explicit
    tuned = autotune.lookup(op, geometry, dtype, route).get("lane_pad")
    if tuned is not None and not (route == _R_PALLAS and tuned != backend.LANE):
        return tuned
    return backend.LANE


def _block_n(op: str, geometry, dtype, route: str, explicit, default) -> int:
    if explicit is not None:
        return explicit
    return autotune.lookup(op, geometry, dtype, route).get("block_n", default)


def _unroll(op: str, geometry, dtype, route: str, explicit) -> int:
    if explicit is not None:
        return explicit
    return autotune.lookup(op, geometry, dtype, route).get(
        "unroll", DEFAULT_QR_UNROLL)


def panel_qr(A: jax.Array, row_start=0, *,
             lane_pad: Optional[int] = None,
             unroll: Optional[int] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(Y, T, R) of the masked Householder panel QR of A (m, b).

    ``row_start`` may be traced; a negative one counts as 0 (every row
    live). Padding uses only static shape info: the kernel holds the tile
    transposed, rows along lanes, so rows pad to a lane multiple (by
    ``b_pad - b`` extra, so the kernel's R extraction at any legal
    row_start <= m - b stays in bounds). ``unroll`` is the ``xla``
    engine's column-loop unroll factor (autotuned when not given).
    """
    rs = jnp.maximum(jnp.asarray(row_start, jnp.int32), 0)
    route = _route("panel_qr", A)
    if route == _R_ORACLE:
        return ref.panel_qr(A, rs)
    if route == _R_XLA:
        u = _unroll("panel_qr", A.shape, A.dtype, route, unroll)
        return _panel.panel_qr_xla(A, rs, unroll=u)
    m, b = A.shape
    lane = _lane_pad("panel_qr", (m, b), A.dtype, route, lane_pad)
    b_pad = backend.pad_to(b, lane)
    m_pad = backend.pad_to(m + (b_pad - b), backend.LANE)
    interp = route == _R_INTERPRET
    if (m_pad, b_pad) == (m, b):
        return _panel.panel_qr(A, rs, interpret=interp)
    A_p = jnp.pad(A, ((0, m_pad - m), (0, b_pad - b)))
    Y, T, R = _panel.panel_qr(A_p, rs, interpret=interp)
    return Y[:m, :b], T[:b, :b], R[:b, :b]


def stacked_qr(R_top: jax.Array, R_bot: jax.Array, *,
               lane_pad: Optional[int] = None,
               unroll: Optional[int] = None):
    """(Y2, T, R) of the TSQR tree combine."""
    route = _route("stacked_qr", R_top, R_bot)
    if route == _R_ORACLE:
        return ref.stacked_qr(R_top, R_bot)
    if route == _R_XLA:
        u = _unroll("stacked_qr", (R_top.shape[0],), R_top.dtype, route,
                    unroll)
        return _stacked.stacked_qr_xla(R_top, R_bot, unroll=u)
    b = R_top.shape[0]
    lane = _lane_pad("stacked_qr", (b,), R_top.dtype, route, lane_pad)
    b_pad = backend.pad_to(b, lane)
    interp = route == _R_INTERPRET
    if b_pad == b:
        return _stacked.stacked_qr(R_top, R_bot, interpret=interp)
    pad = ((0, b_pad - b), (0, b_pad - b))
    Y2, T, R = _stacked.stacked_qr(
        jnp.pad(R_top, pad), jnp.pad(R_bot, pad), interpret=interp
    )
    return Y2[:b, :b], T[:b, :b], R[:b, :b]


def wy_apply(Y: jax.Array, T: jax.Array, C: jax.Array,
             block_n: Optional[int] = None) -> jax.Array:
    """Fused Q^T C. The trailing dim of C is tiled/padded by the kernel."""
    route = _route("wy_apply", Y, T, C)
    if route == _R_ORACLE:
        return ref.wy_apply(Y, T, C)
    if route == _R_XLA:
        return _wy.wy_apply_xla(Y, T, C)
    m, b = Y.shape
    n = C.shape[1]
    bn = _block_n("wy_apply", (m, b, n), C.dtype, route, block_n,
                  DEFAULT_WY_BLOCK_N)
    sub = backend.sublane(Y.dtype)
    b_pad = backend.pad_to(b, backend.LANE)
    m_pad = backend.pad_to(m, sub)
    interp = route == _R_INTERPRET
    if (m_pad, b_pad) == (m, b):
        return _wy.wy_apply(Y, T, C, block_n=bn, interpret=interp)
    Y_p = jnp.pad(Y, ((0, m_pad - m), (0, b_pad - b)))
    T_p = jnp.pad(T, ((0, b_pad - b), (0, b_pad - b)))
    C_p = jnp.pad(C, ((0, m_pad - m), (0, 0)))
    out = _wy.wy_apply(Y_p, T_p, C_p, block_n=bn, interpret=interp)
    return out[:m]


def stacked_apply(Y2, T, C_top, C_bot, block_n: Optional[int] = None):
    """Fused trailing combine; returns (Ct_hat, Cb_hat, W)."""
    route = _route("stacked_apply", Y2, T, C_top, C_bot)
    if route == _R_ORACLE:
        return ref.stacked_apply(Y2, T, C_top, C_bot)
    if route == _R_XLA:
        return _stacked.stacked_apply_xla(Y2, T, C_top, C_bot)
    b = Y2.shape[0]
    n = C_top.shape[1]
    bn = _block_n("stacked_apply", (b, n), C_top.dtype, route, block_n,
                  DEFAULT_STACKED_BLOCK_N)
    b_pad = backend.pad_to(b, backend.LANE)
    interp = route == _R_INTERPRET
    if b_pad == b:
        return _stacked.stacked_apply(
            Y2, T, C_top, C_bot, block_n=bn, interpret=interp
        )
    bb = ((0, b_pad - b), (0, b_pad - b))
    rows = ((0, b_pad - b), (0, 0))
    ot, ob, W = _stacked.stacked_apply(
        jnp.pad(Y2, bb), jnp.pad(T, bb),
        jnp.pad(C_top, rows), jnp.pad(C_bot, rows),
        block_n=bn, interpret=interp,
    )
    return ot[:b], ob[:b], W[:b]
