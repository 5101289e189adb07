"""The FT-CAQR sweep as a state machine: ``SweepState`` + ``sweep_step``.

This is the one panel program of the repo. ``repro.core.caqr_factorize``
runs it to completion; the fault-tolerant paths (the scheduled driver
``repro.ft.driver``, the online orchestrator ``repro.ft.online``, the
shard_map segments of ``repro.launch.spmd_qr``, the QR service) step it and
interleave failure detection and REBUILD at its boundaries.

``SweepState``
    Everything the sweep holds between two interruptible points: the
    working matrix and re-readable source, the in-flight panel artifacts
    (leaf WY factors, the TSQR butterfly ladder, C' and the per-level
    trailing bundles), the per-panel stored outputs, and the **cursor** —
    the next ``sweep_point(panel, phase, level)`` to execute. The cursor
    (with the static ``SweepGeometry``) is pytree *aux data*: two states at
    different points are different treedefs, so ``jax.jit(sweep_step)``
    specializes per point with no retrace hazards.

``sweep_step(comm, state) -> state``
    Executes exactly one sweep point — the work between the previous
    recoverable boundary and ``state.cursor`` — and advances the cursor.
    Panel ``k``'s trailing update is restricted to the live window
    ``A[:, k*b:]``: the ``k*b`` already-factored columns to the left are
    dead (their R rows were extracted at their own panel) and are not
    touched; per-panel R rows and bundles are zero-padded back to full
    width so they stack.

Cursor semantics (DESIGN.md §9): the boundary state after executing point
``p`` is the recoverable state "after ``p``, before its successor". Work
that falls between two points is assigned to the segment that ENDS at the
next point: panel ``k``'s writeback/deposit (which follows its last
trailing combine) runs at the start of the ``(k+1, leaf)`` segment, and the
final panel's deposit plus R assembly run in ``finalize``. A death injected
at a boundary therefore corresponds one-to-one to a ``FailureSchedule``
death at the just-completed point (the cursor vocabulary is
``repro.ft.failures``).

Serialization: ``sweep_state_to_host`` / ``sweep_state_from_host`` flatten
a state to named numpy arrays plus a JSON-able meta record (geometry,
cursor, tuple arities) — the wire format behind ``repro.ckpt``'s
``save_sweep_state`` and the diskless mid-sweep snapshots.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.caqr import (
    PanelFactors,
    SweepGeometry,
    assemble_R,
    pad_to_geometry,
    panel_geometry,
    sweep_geometry,
)
from repro.core.householder import householder_qr_masked
from repro.core.trailing import (
    RecoveryBundle,
    _leaf_apply,
    _writeback,
    trailing_combine_level,
)
from repro.core.tsqr import DistTSQRFactors, _levels, ft_tsqr_level
from repro.ft.failures import (
    PHASE_LEAF,
    PHASE_TSQR,
    next_sweep_point,
    sweep_point,
)

Cursor = Optional[Tuple[int, str, int]]

# Dynamic (pytree-children) fields of SweepState, in flattening order.
_ARRAY_FIELDS = (
    "A0", "A",
    "window", "leaf_Y", "leaf_T", "R_leaf", "R_carry",
    "Y2s", "Ts", "level_Y2", "level_T",
    "C_local", "C_prime", "Ws", "Cs_self", "Cs_buddy", "tops",
    "factors", "R_rows", "bundles",
    "code",
)


@dataclasses.dataclass(frozen=True)
class SweepState:
    """Explicit loop state of the windowed FT-CAQR sweep (a jax pytree).

    Static aux data: ``geom`` (the padded ``SweepGeometry``) and ``cursor``
    (the next sweep point; ``None`` when every point has executed and only
    ``finalize`` remains). Everything else is per-lane array state in the
    SimComm layout (lane axis per leaf where ``state_lane_axes`` says —
    position 0 for block/leaf arrays, position 1 for level-stacked stacks).

    In-flight fields are ``None`` (empty tuples for the growing ladders)
    outside the phase that defines them.
    """

    geom: SweepGeometry
    cursor: Cursor
    # the re-readable data source (padded; never poisoned) + working matrix
    A0: Any
    A: Any
    # in-flight panel state (what a mid-panel death obliterates)
    window: Any = None
    leaf_Y: Any = None
    leaf_T: Any = None
    R_leaf: Any = None
    R_carry: Any = None
    Y2s: Tuple = ()          # TSQR butterfly ladder, one entry per level
    Ts: Tuple = ()
    level_Y2: Any = None     # stacked ladder (L, [P,] b, b) — trailing phase
    level_T: Any = None
    C_local: Any = None      # leaf-applied live window
    C_prime: Any = None      # running C' between trailing levels
    Ws: Tuple = ()           # per-level trailing bundle slices
    Cs_self: Tuple = ()
    Cs_buddy: Tuple = ()
    tops: Tuple = ()
    # stored outputs, one entry per completed (deposited) panel
    factors: Tuple = ()      # PanelFactors
    R_rows: Tuple = ()
    bundles: Tuple = ()      # RecoveryBundle
    # coded checksum slots (repro.ft.coding): one (f, *byte_shape) uint8
    # parity per protected leaf, re-encoded at every boundary by the
    # scheme's refresh; None under the plain XOR scheme. No lane axis —
    # the parity slots model dedicated checksum lanes outside the compute
    # failure domain (skip-axis -1 in state_lane_axes; never poisoned;
    # serialized since wire format v2 so a resumed MDS run keeps its
    # redundancy across the restart).
    code: Any = None

    @property
    def levels(self) -> int:
        return self.geom.levels

    @property
    def done(self) -> bool:
        return self.cursor is None

    def replace(self, **kw) -> "SweepState":
        return dataclasses.replace(self, **kw)


def _state_flatten(s: SweepState):
    return tuple(getattr(s, f) for f in _ARRAY_FIELDS), (s.geom, s.cursor)


def _state_unflatten(aux, children) -> SweepState:
    geom, cursor = aux
    return SweepState(geom=geom, cursor=cursor,
                      **dict(zip(_ARRAY_FIELDS, children)))


jax.tree_util.register_pytree_node(SweepState, _state_flatten, _state_unflatten)


def initial_sweep_state(comm, A0, panel_width: int) -> SweepState:
    """Entry state: padded source matrix, cursor at the first sweep point.

    Accepts any general shape (tall / ragged / wide); the state machine
    runs at the padded ``sweep_geometry``.
    """
    P = comm.axis_size()
    assert _levels(P) >= 1, "need at least 2 lanes to tolerate failures"
    m_loc, n = comm.local_shape(A0)
    geom = sweep_geometry(P, m_loc, n, panel_width)
    A_pad = pad_to_geometry(comm, A0, geom)
    return SweepState(geom=geom, cursor=sweep_point(0, PHASE_LEAF),
                      A0=A_pad, A=A_pad)


# -- the transition ----------------------------------------------------------


def _begin_panel_leaf(comm, s: SweepState, k: int) -> SweepState:
    """Window slice + local masked panel QR of panel ``k``."""
    geom = s.geom
    col0, _t_lane, row_start, active = panel_geometry(
        comm, k, geom.b, geom.m_loc_pad)
    window = comm.map_local(lambda A: A[:, col0:])(s.A)
    panel = comm.map_local(lambda W: W[:, : geom.b])(window)
    wy = comm.map_local(householder_qr_masked)(panel, row_start)
    return s.replace(
        window=window,
        leaf_Y=comm.where(active, wy.Y, jnp.zeros_like(wy.Y)),
        leaf_T=comm.where(active, wy.T, jnp.zeros_like(wy.T)),
        R_leaf=comm.where(active, wy.R, jnp.zeros_like(wy.R)),
    )


def _deposit_panel(comm, s: SweepState, k: int) -> SweepState:
    """Writeback + per-panel output deposit of the just-finished panel
    ``k`` (the work after the panel's last trailing combine), then clear
    the in-flight fields."""
    geom = s.geom
    col0, t_lane, row_start, active = panel_geometry(
        comm, k, geom.b, geom.m_loc_pad)
    C_out = _writeback(comm, s.C_local, s.C_prime, row_start, active)
    # reattach the updated live window to the untouched dead columns
    A = comm.map_local(
        lambda A, W: jnp.concatenate([A[:, :col0], W], axis=1))(s.A, C_out)
    # the new R rows (global rows [col0, col0+b)) live at lane t_lane's
    # final C' block: replicate them (one b x n all-reduce — the FT
    # broadcast) and left-zero-pad back to full-width column indices
    idx = comm.axis_index()
    r_rows = comm.psum(
        comm.where(idx == t_lane, s.C_prime, jnp.zeros_like(s.C_prime)))
    r_rows = comm.map_local(lambda r: jnp.pad(r, ((0, 0), (col0, 0))))(r_rows)
    bundle = RecoveryBundle(
        W=jnp.stack(s.Ws),
        C_self=jnp.stack(s.Cs_self),
        C_buddy=jnp.stack(s.Cs_buddy),
        Y2=s.level_Y2,
        T=s.level_T,
        self_was_top=jnp.stack(s.tops),
    )

    def pad_cols(x):
        # left-zero-pad a window-width bundle slice to full width so the
        # per-panel bundles stack (dead columns need no recovery)
        if col0 == 0:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(col0, 0)])

    bundle = bundle._replace(
        W=pad_cols(bundle.W),
        C_self=pad_cols(bundle.C_self),
        C_buddy=pad_cols(bundle.C_buddy),
    )
    pf = PanelFactors(
        leaf_Y=s.leaf_Y,
        leaf_T=s.leaf_T,
        level_Y2=s.level_Y2,
        level_T=s.level_T,
        row_start=row_start,
        active=active,
        target=jnp.broadcast_to(t_lane, jnp.shape(comm.axis_index())),
    )
    return s.replace(
        A=A,
        R_rows=s.R_rows + (r_rows,),
        bundles=s.bundles + (bundle,),
        factors=s.factors + (pf,),
        window=None, leaf_Y=None, leaf_T=None, R_leaf=None, R_carry=None,
        Y2s=(), Ts=(), level_Y2=None, level_T=None,
        C_local=None, C_prime=None, Ws=(), Cs_self=(), Cs_buddy=(), tops=(),
    )


def sweep_step(comm, state: SweepState) -> SweepState:
    """Execute exactly one sweep point (the segment ending at
    ``state.cursor``) and advance the cursor.

    Pure and Comm-generic: under ``SimComm`` it runs eagerly or under
    ``jax.jit`` (the orchestrator compiles it per cursor); under
    ``AxisComm``/``MeshComm`` it is the body a ``shard_map`` segment traces
    (``repro.launch.spmd_qr.make_spmd_sweep_step``).
    """
    point = state.cursor
    assert point is not None, "sweep already complete; call finalize"
    geom = state.geom
    k, phase, lvl = point
    L = state.levels
    col0 = k * geom.b
    t_lane = col0 // geom.m_loc_pad

    if phase == PHASE_LEAF:
        if k > 0:
            state = _deposit_panel(comm, state, k - 1)
        state = _begin_panel_leaf(comm, state, k)
    elif phase == PHASE_TSQR:
        # the carry is R_leaf at the first butterfly level
        carry = state.R_leaf if lvl == 0 else state.R_carry
        R_next, Y2, T = ft_tsqr_level(comm, carry, lvl, t_lane, t_lane)
        state = state.replace(
            R_carry=R_next, Y2s=state.Y2s + (Y2,), Ts=state.Ts + (T,))
    else:  # PHASE_TRAILING
        if lvl == 0:
            # stack the ladder + leaf-apply the live window (the work
            # between the last TSQR level and the first trailing combine)
            _c0, _t, row_start, active = panel_geometry(
                comm, k, geom.b, geom.m_loc_pad)
            level_Y2 = jnp.stack(state.Y2s)
            level_T = jnp.stack(state.Ts)
            dist = DistTSQRFactors(state.leaf_Y, state.leaf_T, level_Y2,
                                   level_T, state.R_leaf)
            C_local, C_prime = _leaf_apply(
                comm, dist, state.window, row_start,
                active=active, skip_consumed=True)
            state = state.replace(
                level_Y2=level_Y2, level_T=level_T, C_local=C_local,
                C_prime=comm.where(active, C_prime, jnp.zeros_like(C_prime)),
            )
        out = trailing_combine_level(
            comm, state.C_prime, state.level_Y2[lvl], state.level_T[lvl],
            lvl, t_lane, t_lane,
        )
        state = state.replace(
            C_prime=out.C_prime,
            Ws=state.Ws + (out.W,),
            Cs_self=state.Cs_self + (out.C_self,),
            Cs_buddy=state.Cs_buddy + (out.C_buddy,),
            tops=state.tops + (out.is_top,),
        )

    return state.replace(
        cursor=next_sweep_point(point, geom.n_panels, L))


def finalize(comm, state: SweepState):
    """Deposit the last panel and assemble the sweep outputs.

    Returns ``(R, factors, bundles)``: R replicated ``(min(m, n), n)``,
    factors and bundles stacked over panels — the layout of ``CAQRResult``
    and ``FTSweepResult``. Pure: the caller's state is not consumed
    (calling twice double-runs the deposit arithmetic but on the same
    inputs)."""
    assert state.cursor is None, f"sweep not complete: at {state.cursor}"
    state = _deposit_panel(comm, state, state.geom.n_panels - 1)
    factors = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *state.factors)
    bundles = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *state.bundles)
    R = assemble_R(comm, jnp.stack(state.R_rows), state.geom)
    return R, factors, bundles


def deposit_boundary(comm, state: SweepState):
    """Flush the pending deposit at a panel boundary and return
    ``(state, r)`` where ``r`` is the number of fully deposited panels —
    the consumed-column frontier sits at ``r * geom.b``.

    Legal only at a boundary: cursor at a leaf point ``(k, leaf, 0)``
    (deposits the deferred panel ``k-1``) or past-the-end ``None``
    (deposits the last panel; do NOT also call :func:`finalize`, which
    would re-run the same deposit). The elastic transitions
    (``repro.ft.elastic``) harvest the trailing submatrix at exactly
    this frontier."""
    if state.cursor is None:
        state = _deposit_panel(comm, state, state.geom.n_panels - 1)
        return state, state.geom.n_panels
    k, phase, _ = state.cursor
    assert phase == PHASE_LEAF, f"not at a panel boundary: {state.cursor}"
    if k > 0:
        state = _deposit_panel(comm, state, k - 1)
    return state, k


def run_steps(comm, state: SweepState, max_points: Optional[int] = None
              ) -> SweepState:
    """Iterate ``sweep_step`` up to ``max_points`` times (or to completion).
    The orchestrator jits this whole call as one compiled segment, so
    ``max_points`` is the segment size."""
    n = 0
    while state.cursor is not None and (max_points is None or n < max_points):
        state = sweep_step(comm, state)
        n += 1
    return state


def panel_points(geom: SweepGeometry) -> int:
    """Sweep points per panel: leaf + L butterfly + L trailing levels."""
    return 1 + 2 * geom.levels


# -- lane-axis bookkeeping ---------------------------------------------------

_FACTORS_AXES = PanelFactors(
    leaf_Y=0, leaf_T=0, level_Y2=1, level_T=1,
    row_start=0, active=0, target=0,
)
_BUNDLE_AXES = RecoveryBundle(W=1, C_self=1, C_buddy=1, Y2=1, T=1,
                              self_was_top=1)


def state_lane_axes(state: SweepState) -> SweepState:
    """A ``SweepState``-shaped pytree of ints: the lane-axis position of
    every array leaf (SimComm layout). Drives generic death-masking
    (``repro.ft.driver.obliterate_state``), the NaN-sentinel probes, and the
    per-leaf ``shard_map`` specs of the SPMD segment runner. Structure-only:
    works on ``jax.eval_shape`` structs too."""

    def like(field, ax):
        # mirror the field's structure (None stays None; tuples map per-entry)
        return jax.tree_util.tree_map(lambda _: ax, getattr(state, field))

    axes = {f: like(f, 0) for f in _ARRAY_FIELDS}
    for f in ("level_Y2", "level_T"):
        axes[f] = like(f, 1)
    axes["factors"] = tuple(_FACTORS_AXES for _ in state.factors)
    axes["bundles"] = tuple(_BUNDLE_AXES for _ in state.bundles)
    # parity slots have NO lane axis (checksum lanes live outside the
    # compute failure domain): the -1 sentinel skips them in death
    # masking, NaN scans, and the SPMD specs (replicated)
    axes["code"] = like("code", -1)
    return SweepState(geom=state.geom, cursor=state.cursor, **axes)


# The sweep-state wire format version written by default. v1 excluded the
# coded parity slots as derivable state (a resumed sweep re-encodes at its
# first boundary) — but that re-encode is a window of vulnerability: a
# multi-death present AT the resume boundary can only be joint-decoded from
# the parity as persisted (`SweepOrchestrator._resume_boundary_pass`), which
# v1 threw away. v2 serializes `SweepState.code`; v1 stays loadable (the
# regression test writes and reloads both).
WIRE_VERSION = 2

# Fields excluded from wire format v1 (the v2 writer keeps them).
_V1_EXCLUDED_FIELDS = ("code",)


# -- host serialization (the SweepState wire format, DESIGN.md §9) -----------


def _wire_excluded(version: int) -> Tuple[str, ...]:
    assert version in (1, 2), f"unknown sweep-state wire version {version}"
    return _V1_EXCLUDED_FIELDS if version == 1 else ()


def _flat_arrays(state: SweepState, version: int) -> Dict[str, Any]:
    flat: Dict[str, Any] = {}
    for f in _ARRAY_FIELDS:
        if f in _wire_excluded(version):
            continue
        v = getattr(state, f)
        if v is None:
            continue
        if isinstance(v, tuple):
            for i, entry in enumerate(v):
                if isinstance(entry, (PanelFactors, RecoveryBundle)):
                    for fld, x in zip(entry._fields, entry):
                        flat[f"{f}/{i}/{fld}"] = x
                else:
                    flat[f"{f}/{i}"] = entry
        else:
            flat[f] = v
    return flat


def sweep_state_to_host(state: SweepState,
                        version: int = WIRE_VERSION) -> Dict[str, np.ndarray]:
    """Flatten a state to named host (numpy) arrays plus a ``__meta__``
    JSON record (geometry, cursor, per-field structure) — the persistable
    wire format. Inverse: ``sweep_state_from_host``.

    ``version=2`` (default) includes the ``code`` parity slots, so a
    suspended ``MDSScheme`` run resumes with its coded redundancy intact;
    ``version=1`` writes the PR-9 format (no parity — a resumed state
    re-encodes at its first boundary and cannot joint-decode deaths present
    at the resume boundary itself)."""
    excluded = _wire_excluded(version)
    arrays = {k: np.asarray(v) for k, v in _flat_arrays(state, version).items()}
    meta = {
        "version": version,
        "geom": list(state.geom),
        "cursor": list(state.cursor) if state.cursor is not None else None,
        "none_fields": [
            f for f in _ARRAY_FIELDS
            if f not in excluded
            and not isinstance(getattr(state, f), tuple)
            and getattr(state, f) is None
        ],
        "tuple_lens": {
            f: len(getattr(state, f)) for f in _ARRAY_FIELDS
            if f not in excluded
            and isinstance(getattr(state, f), tuple)
        },
    }
    arrays["__meta__"] = np.asarray(json.dumps(meta))
    return arrays


def sweep_state_from_host(arrays: Dict[str, np.ndarray],
                          to_device: bool = True) -> SweepState:
    """Rebuild a ``SweepState`` from ``sweep_state_to_host`` output (e.g. a
    loaded ``.npz``). ``to_device=False`` keeps numpy leaves — structural
    inspection with no live jax backend needed."""
    meta = json.loads(str(arrays["__meta__"]))
    version = meta["version"]
    assert version in (1, 2), meta
    geom = SweepGeometry(*meta["geom"])
    cursor = tuple(meta["cursor"]) if meta["cursor"] is not None else None
    conv = jnp.asarray if to_device else np.asarray

    def leaf(key):
        return conv(arrays[key])

    fields: Dict[str, Any] = {}
    for f in _ARRAY_FIELDS:
        if f in _wire_excluded(version):
            fields[f] = None  # v1: parity re-encodes at the first boundary
        elif f in meta["none_fields"]:
            fields[f] = None
        elif f in meta["tuple_lens"]:
            n = meta["tuple_lens"][f]
            if f == "factors":
                fields[f] = tuple(
                    PanelFactors(**{fld: leaf(f"factors/{i}/{fld}")
                                    for fld in PanelFactors._fields})
                    for i in range(n))
            elif f == "bundles":
                fields[f] = tuple(
                    RecoveryBundle(**{fld: leaf(f"bundles/{i}/{fld}")
                                      for fld in RecoveryBundle._fields})
                    for i in range(n))
            else:
                fields[f] = tuple(leaf(f"{f}/{i}") for i in range(n))
        else:
            fields[f] = leaf(f)
    return SweepState(geom=geom, cursor=cursor, **fields)
