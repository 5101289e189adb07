"""Fault-tolerant execution driver for the windowed CAQR sweep (paper §II-III).

This is the end-to-end form of the paper's claim: run the *entire* windowed
right-looking FT-CAQR sweep while lanes die at scheduled points — at any
panel, after any TSQR butterfly level or trailing-combine level — and finish
with ``R``, the per-panel implicit-Q factors, and the recovery bundles
**bit-identical** to the failure-free run (the recovery regression oracle).

Execution model (DESIGN.md §8-9)
--------------------------------
The sweep itself is the reified state machine of ``repro.ft.online.state``:
an explicit ``SweepState`` pytree advanced one interruptible point at a time
by the pure transition ``sweep_step``. This driver is a thin loop over that
transition that injects *scheduled* (trace-time) failures at each boundary —
the simulation-convenience path, kept as the differential oracle for the
*online* path (``repro.ft.online.orchestrator``, where deaths are discovered
at runtime instead of scripted). Both are ONE Comm-generic program
(``repro.core.comm``) that runs two ways:

* ``SimComm``  — the P-lane single-device simulator: eager, level-stepped,
  with wall-clock REBUILD latency per event. This is the test/debug path.
* ``AxisComm`` — inside ``jax.shard_map`` over a device mesh: the production
  SPMD path the paper describes, one real process per lane. The entrypoint
  is ``repro.launch.spmd_qr.ft_caqr_sweep_spmd``.

Death and recovery are expressed through the Comm death-mask primitives
(``comm.poison`` / ``comm.fetch_lane`` / ``comm.where_lane``) as the two
``SweepState`` transitions ``obliterate_state`` and ``rebuild_state``
defined here, shared verbatim by the scheduled and online paths: "kill lane
2 after panel 1's level-0 trailing combine" compiles to a masked NaN-write
on both paths, and every REBUILD fetch is a point-to-point collective keyed
by static lane indices. ``sweep_step`` calls the *same* single-level
primitives the production sweep is built from: ``ft_tsqr_level``
(core/tsqr), ``trailing_combine_level`` and ``_leaf_apply``/``_writeback``
(core/trailing), and the geometry/assembly helpers of ``core/caqr``.
Failure-free, the paths are the same floating-point program, so bit-identity
holds by construction; under failures it is regression-gated by
``tests/test_spmd_ft_driver.py`` and ``tests/test_online_recovery.py``.

Failure model (paper §II, ULFM REBUILD semantics)
-------------------------------------------------
A ``FailureSchedule`` keyed by ``sweep_point(panel, phase, level)`` kills
lanes at interruptible points; death is *simulated faithfully*: every float
the lane holds — its block-row, leaf/ladder factors, C', stored per-panel
factors and bundles — is overwritten with NaN, so any read of dead state
poisons the result and the bit-identity oracle catches it.

Recovery (paper §III-B/III-C REBUILD)
-------------------------------------
The respawned lane is rebuilt from (a) its own slice of the *initial*
matrix, re-read from the data source, and (b) per lost artifact, the state
of exactly ONE surviving lane — its XOR-buddy at the relevant tree level:

* previous panels — leaf factors are *recomputed* from the re-read rows
  (never fetched; they are lane-private), the final C' of each panel comes
  from the last-level buddy's bundle ``{W, T, C', Y2, role}``, and the
  lane's own bundle rows are mirrors of each level-buddy's
  (``W`` is pair-shared, ``C_self``/``C_buddy`` swap);
* current panel, mid-TSQR — the butterfly ladder ``(Y2, T)`` and the running
  R are identical at the level-0 buddy (lanes ``i`` and ``i^1`` agree at
  every level: same pair at level 0, same ``i >> (s+1)`` group above), so
  one copy restores them;
* current panel, mid-trailing — C' after the last completed level ``s`` is
  rebuilt from the level-``s`` buddy's bundle by replaying the pair combine
  through ``_combine`` (the same kernel-dispatch seam as the failure-free
  path) and keeping the failed side.

Each rebuilt artifact therefore reads ONE survivor (recorded in the event's
ledger — the single-source property is enforced by construction); a full
mid-sweep rebuild touches at most ``log2 P`` distinct survivors across
artifact classes. If a needed buddy is itself dead (e.g. both members of a
pair killed at the same point), ``UnrecoverableFailure`` is raised — that is
the honest limit of one-level redundancy doubling. Under shard_map the
schedule is validated at trace time, so an unrecoverable schedule fails
before any device computes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import AbstractSet, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import recovery as rec
from repro.core.caqr import PanelFactors, lane_geometry
from repro.core.comm import SimComm
from repro.core.householder import apply_qt
from repro.core.trailing import RecoveryBundle
from repro.core.tsqr import _levels
from repro.ft.coding import CodingScheme, XORPairScheme
from repro.ft.semantics import Semantics
from repro.ft.failures import (
    Detector,
    FailureSchedule,
    PHASE_TSQR,
    PHASE_TRAILING,
    UnrecoverableFailure,
)
from repro.ft.online.state import (
    SweepState,
    finalize,
    initial_sweep_state,
    state_lane_axes,
    sweep_step,
)


@dataclasses.dataclass
class RecoveryEvent:
    """One REBUILD: which lane died where, and the single-source read ledger
    (artifact name -> the one surviving lane it was fetched from).

    ``elapsed_s`` is wall-clock REBUILD latency, synced on both sides, under
    the online orchestrator (one heal program on either comm, its time
    split evenly among the lanes it heals) and the eager SimComm driver;
    under the scheduled shard_map path the whole sweep is one traced
    program, so there it records trace time only.

    ``xchip_bytes`` is the bytes this heal moved from one chip to another,
    counted where the heal issues each transfer (the tally of the heal
    program's comm, ``MeshComm.xchip_bytes``); a heal on one chip moves
    none. A heal of several
    lanes counts its bytes once, on its first event.
    """

    point: Tuple[int, str, int]
    lane: int
    reads: Dict[str, int]
    elapsed_s: float
    xchip_bytes: int = 0

    @property
    def sources(self) -> List[int]:
        return sorted(set(self.reads.values()))


class FTSweepResult(NamedTuple):
    """Same layout as ``CAQRResult(collect_bundles=True)`` plus the recovery
    event log."""

    R: jax.Array
    factors: PanelFactors
    bundles: RecoveryBundle
    events: List[RecoveryEvent]


# -- death + REBUILD as SweepState transitions -------------------------------
#
# Shared by the scheduled driver below and the online orchestrator
# (repro.ft.online.orchestrator): process death and single-source recovery
# are functions of (comm, state), not of the execution mode.


def obliterate_state(comm, state: SweepState, lane: int) -> SweepState:
    """Process death, mask-form: NaN every float the lane holds — current
    block-row, in-flight panel state, and its slices of all stored sweep
    outputs (``comm.poison`` — an at-set under SimComm, a masked select on
    the lane's own device under shard_map). The initial matrix ``A0`` is the
    re-readable data source of the paper's model and survives."""
    # A0 survives: mark its axis with the skip sentinel (keeps the axes
    # pytree structurally identical to the state) so the biggest leaf is
    # not pointlessly poisoned and re-replaced
    axes = state_lane_axes(state).replace(A0=-1)
    return jax.tree_util.tree_map(
        lambda x, ax: x if ax < 0 else comm.poison(x, lane, lane_axis=ax),
        state, axes)


_XOR_SCHEME = XORPairScheme()


def recover_lanes(
    comm,
    state: SweepState,
    newly: List[int],
    point: Tuple[int, str, int],
    dead: AbstractSet[int],
    sync=None,
    on_recovered=None,
    scheme: Optional[CodingScheme] = None,
) -> Tuple[SweepState, List[RecoveryEvent]]:
    """The shared REBUILD protocol: all detected deaths strike first
    (normalize whatever was observed to the full mask-death), then recovery
    runs. Both execution modes — the scheduled driver's checkpoint and the
    online orchestrator's detection handler — call exactly this, so the
    scheduled-vs-online bitwise equivalence cannot drift apart in one copy.

    ``scheme`` (``repro.ft.coding``, default the paper's ``XORPairScheme``)
    selects the redundancy: a SINGLE newly-dead lane always takes the
    paper's single-source XOR REBUILD below (so ``MDSScheme(f=1)`` is
    ledger-identical to XOR); ``2 <= t <= scheme.f`` simultaneous deaths
    take the joint GF decode (``scheme.decode_lanes``, multi-source
    ledger); ``t > scheme.f`` falls back to the per-lane XOR loop, whose
    exhaustion is the honest ``UnrecoverableFailure`` boundary.

    ``sync(state)`` (optional) drains async dispatch before/after each
    rebuild so ``elapsed_s`` covers only the REBUILD itself;
    ``on_recovered(lane)`` (optional) runs after a lane is rebuilt, before
    its event is logged — the callers revive their detectors here (which
    also removes the lane from a live ``dead`` set, keeping later rebuilds'
    single-source checks honest)."""
    scheme = _XOR_SCHEME if scheme is None else scheme
    events: List[RecoveryEvent] = []
    newly = sorted(newly)
    for lane in newly:
        state = obliterate_state(comm, state, lane)
    if (scheme.joint and 2 <= len(newly) <= scheme.f
            and not (set(dead) - set(newly))):
        if sync is not None:
            sync(state)
        t0 = time.perf_counter()
        state, reads = scheme.decode_lanes(comm, state, newly, dead)
        if sync is not None:
            sync(state)
        elapsed = time.perf_counter() - t0
        for lane in newly:
            if on_recovered is not None:
                on_recovered(lane)
            events.append(RecoveryEvent(
                point=point, lane=lane, reads=dict(reads),
                elapsed_s=elapsed,
            ))
        return state, events
    try:
        for lane in newly:
            if sync is not None:
                sync(state)
            t0 = time.perf_counter()
            state, reads = rebuild_state(comm, state, lane, point, dead)
            if sync is not None:
                sync(state)
            if on_recovered is not None:
                on_recovered(lane)
            events.append(RecoveryEvent(
                point=point, lane=lane, reads=reads,
                elapsed_s=time.perf_counter() - t0,
            ))
    except UnrecoverableFailure as e:
        if scheme.joint and len(newly) > scheme.f:
            raise UnrecoverableFailure(
                f"{len(newly)} simultaneous deaths exceed the coding "
                f"scheme's tolerance f={scheme.f}, and the XOR fallback "
                f"found no live source: {e}") from None
        raise
    return state, events


class HealProgram:
    """A REBUILD compiled as one program, called like ``recover_lanes``
    (``(state, newly, point, dead, sync=, on_recovered=, scheme=)``): the
    online orchestrator's heal on either comm (``SimHeal`` in
    ``repro.ft.online.orchestrator``, ``MeshHeal`` in
    ``repro.launch.spmd_qr``).

    A subclass traces ``recover_lanes`` of ``newly`` at one point into its
    program; ``_run(state)`` runs it and returns the healed state with the
    read ledger (lane -> reads) recorded while tracing. Here the events are
    built on the host: the program's time, synced on both sides, split
    evenly among the lanes it heals, and its cross-chip bytes on the first
    event, so a heal's events sum to the program's own numbers.
    ``on_recovered`` runs for each lane after the program.
    ``program_status(state)`` says whether a call on ``state`` builds the
    program (``"built"``) or reuses it (``"cached"``)."""

    chips = "0"        # the dead lanes' chips, as ``ftqr.heal`` names them
    xchip_reads = 0    # artifacts read from a lane on another chip
    xchip_bytes = 0    # bytes the program moves between chips

    def __init__(self, newly):
        self.newly = sorted(newly)
        self._ran = set()

    def _run(self, state: SweepState):
        raise NotImplementedError

    def program_status(self, state: SweepState) -> str:
        ran = jax.tree_util.tree_structure(state) in self._ran
        return "cached" if ran else "built"

    def __call__(self, state, newly, point, dead, sync=None,
                 on_recovered=None, scheme=None):
        del dead, scheme  # traced into the program
        assert sorted(newly) == self.newly
        if sync is not None:
            sync(state)
        t0 = time.perf_counter()
        treedef = jax.tree_util.tree_structure(state)
        state, reads = self._run(state)
        self._ran.add(treedef)
        if sync is not None:
            sync(state)
        elapsed = (time.perf_counter() - t0) / len(self.newly)
        events = []
        for i, lane in enumerate(self.newly):
            if on_recovered is not None:
                on_recovered(lane)
            events.append(RecoveryEvent(
                point=point, lane=lane, reads=dict(reads[lane]),
                elapsed_s=elapsed,
                xchip_bytes=self.xchip_bytes if i == 0 else 0))
        return state, events


def rebuild_state(
    comm,
    state: SweepState,
    lane: int,
    point: Tuple[int, str, int],
    dead: AbstractSet[int] = frozenset(),
) -> Tuple[SweepState, Dict[str, int]]:
    """The paper's REBUILD as a state transition: respawn ``lane`` at the
    recoverable boundary ``point``, re-read its initial slice, replay
    completed panels, restore the in-flight panel state — each lost artifact
    from exactly one surviving buddy. Returns the repaired state and the
    single-source read ledger. ``dead`` is the set of currently-dead lanes
    (a needed source in it raises ``UnrecoverableFailure``).

    Comm-generic expression: replay arithmetic runs per lane through
    ``comm.map_local`` at the dead lane's *static* geometry (under SPMD
    every lane runs the same program; survivors' replay results are
    discarded by the final ``where_lane`` masks — under SimComm the vmap
    computes the same discarded slots), and every buddy read is a
    ``fetch_lane``/``ppermute`` keyed by static lane indices, so exactly
    one survivor sends per artifact on the production path too."""
    geom = state.geom
    b, m_loc = geom.b, geom.m_loc_pad
    reads: Dict[str, int] = {}

    def fetch(artifact: str, source: int) -> int:
        if source == lane or source in dead:
            raise UnrecoverableFailure(
                f"rebuilding lane {lane} at {point} needs {artifact} "
                f"from lane {source}, which is not a live survivor"
            )
        reads[artifact] = source
        return source

    k = point[0]
    # respawn: every lane re-reads its own slice of the data source; only
    # the dead lane's replay survives the rebuild's masked writes
    rows = state.A0
    for j in range(k):
        state, rows = _replay_panel(comm, state, j, lane, rows, fetch)

    # current panel: recompute the masked leaf from the rebuilt rows
    col0, t_lane, rs, act = lane_geometry(k, b, m_loc, lane)
    lY, lT, lR = comm.map_local(
        lambda r: rec.recompute_leaf(r, col0, b, rs, act)
    )(rows)
    state = state.replace(
        leaf_Y=comm.where_lane(lane, lY, state.leaf_Y),
        leaf_T=comm.where_lane(lane, lT, state.leaf_T),
        R_leaf=comm.where_lane(lane, lR, state.R_leaf),
        A=comm.where_lane(lane, rows, state.A),
        window=comm.where_lane(
            lane, comm.map_local(lambda r: r[:, col0:])(rows), state.window),
    )

    _, phase, lvl = point
    if phase == PHASE_TSQR:
        # ladder + running R: identical at the level-0 buddy (see module
        # docstring) — one copy restores all completed levels
        src = fetch("tsqr.ladder+R", lane ^ 1)
        Y2s, Ts = list(state.Y2s), list(state.Ts)
        for i in range(lvl + 1):
            Y2s[i] = comm.fetch_lane(Y2s[i], lane, src)
            Ts[i] = comm.fetch_lane(Ts[i], lane, src)
        state = state.replace(
            Y2s=tuple(Y2s), Ts=tuple(Ts),
            R_carry=comm.fetch_lane(state.R_carry, lane, src),
        )
    elif phase == PHASE_TRAILING:
        src = fetch("tsqr.ladder", lane ^ 1)
        level_Y2 = comm.fetch_lane(state.level_Y2, lane, src, lane_axis=1)
        level_T = comm.fetch_lane(state.level_T, lane, src, lane_axis=1)
        # the per-level ladder tuple and the running tsqr R ride along from
        # the same survivor: no sweep output reads them after the stacking,
        # but a respawned lane must hold NO stale NaN — the online
        # detectors (sentinel probe, deep scan) rely on a rebuilt lane
        # being indistinguishable from one that never died
        Y2s, Ts = list(state.Y2s), list(state.Ts)
        for i in range(len(Y2s)):
            Y2s[i] = comm.fetch_lane(Y2s[i], lane, src)
            Ts[i] = comm.fetch_lane(Ts[i], lane, src)
        state = state.replace(Y2s=tuple(Y2s), Ts=tuple(Ts))
        if state.R_carry is not None:
            state = state.replace(
                R_carry=comm.fetch_lane(state.R_carry, lane, src))
        # leaf-applied window: local recompute through the same seam
        C_local = comm.where_lane(
            lane,
            comm.map_local(
                lambda Y, T, r: apply_qt(Y, T, r[:, col0:])
            )(lY, lT, rows),
            state.C_local,
        )
        # C' after the last completed level: ONE fetch from that level's
        # buddy, replayed through the seam-routed pair combine
        src_c = fetch(f"trailing.cprime@level{lvl}", lane ^ (1 << lvl))
        failed_was_top = ((lane >> lvl) & 1) == ((t_lane >> lvl) & 1)
        pair_live = lane >= t_lane and src_c >= t_lane
        recv = lambda x: comm.ppermute(x, [(src_c, lane)])
        cp = comm.map_local(
            lambda cb, cs, y2, t: rec.rebuild_cprime_after_level(
                cb, cs, y2, t, failed_was_top, pair_live)
        )(recv(state.Cs_buddy[lvl]), recv(state.Cs_self[lvl]),
          level_Y2[lvl], level_T[lvl])
        C_prime = comm.where_lane(lane, cp, state.C_prime)
        # the lane's own bundle rows: mirror of each level-buddy's entry
        # (W is pair-shared; C_self/C_buddy swap sides)
        Ws = list(state.Ws)
        Cs_self, Cs_buddy = list(state.Cs_self), list(state.Cs_buddy)
        for s in range(lvl + 1):
            src_s = fetch(f"trailing.bundle@level{s}", lane ^ (1 << s))
            new_w = comm.fetch_lane(Ws[s], lane, src_s)
            new_cs = comm.fetch_lane(
                Cs_buddy[s], lane, src_s, into=Cs_self[s])
            new_cb = comm.fetch_lane(
                Cs_self[s], lane, src_s, into=Cs_buddy[s])
            Ws[s], Cs_self[s], Cs_buddy[s] = new_w, new_cs, new_cb
        state = state.replace(
            level_Y2=level_Y2, level_T=level_T, C_local=C_local,
            C_prime=C_prime, Ws=tuple(Ws),
            Cs_self=tuple(Cs_self), Cs_buddy=tuple(Cs_buddy),
        )
    return state, reads


def _replay_panel(
    comm, state: SweepState, j: int, lane: int, rows, fetch
) -> Tuple[SweepState, jax.Array]:
    """Advance the respawned lane's block-row through completed panel ``j``
    and restore its slices of that panel's stored outputs."""
    geom = state.geom
    b, m_loc, L = geom.b, geom.m_loc_pad, geom.levels
    col0, t_lane, rs, act = lane_geometry(j, b, m_loc, lane)
    lY, lT, _lR = comm.map_local(
        lambda r: rec.recompute_leaf(r, col0, b, rs, act)
    )(rows)

    src_l = fetch(f"panel{j}.tsqr_ladder", lane ^ 1)
    factors = list(state.factors)
    fj = factors[j]
    factors[j] = PanelFactors(
        leaf_Y=comm.where_lane(lane, lY, fj.leaf_Y),
        leaf_T=comm.where_lane(lane, lT, fj.leaf_T),
        level_Y2=comm.fetch_lane(fj.level_Y2, lane, src_l, lane_axis=1),
        level_T=comm.fetch_lane(fj.level_T, lane, src_l, lane_axis=1),
        row_start=fj.row_start, active=fj.active, target=fj.target,
    )
    src_r = fetch(f"panel{j}.r_rows", lane ^ 1)
    R_rows = list(state.R_rows)
    R_rows[j] = comm.fetch_lane(R_rows[j], lane, src_r)

    # final C' of panel j: one fetch from the last-level buddy's bundle.
    # Indexing the leading LEVEL axis first leaves per-lane layout on
    # both comms (SimComm keeps the lane axis in front, AxisComm is
    # already local), so the replayed combine is one expression.
    bj = state.bundles[j]
    if act:
        src_c = fetch(f"panel{j}.cprime_final", lane ^ (1 << (L - 1)))
        failed_was_top = ((lane >> (L - 1)) & 1) == ((t_lane >> (L - 1)) & 1)
        pair_live = lane >= t_lane and (lane ^ (1 << (L - 1))) >= t_lane
        recv = lambda x: comm.ppermute(x, [(src_c, lane)])
        # stored bundles are zero-padded to full width; slice back to the
        # live window so the replayed combine runs at the original width
        cp = comm.map_local(
            lambda cb, cs, y2, t: rec.rebuild_cprime_after_level(
                cb, cs, y2, t, failed_was_top, pair_live)
        )(recv(bj.C_buddy[L - 1][..., col0:]),
          recv(bj.C_self[L - 1][..., col0:]),
          recv(bj.Y2[L - 1]), recv(bj.T[L - 1]))
        rows = comm.map_local(
            lambda r, y, t, c: rec.rebuild_block_row_through_panel(
                r, y, t, c, col0, rs, act)
        )(rows, lY, lT, cp)
    else:
        rows = comm.map_local(
            lambda r, y, t: rec.rebuild_block_row_through_panel(
                r, y, t, None, col0, rs, act)
        )(rows, lY, lT)

    # the lane's own bundle rows for panel j: per-level mirrors, written
    # level-sliced (leading axis) and re-stacked so the same code drives
    # both comm layouts
    W_lv = [bj.W[s] for s in range(L)]
    Cs_lv = [bj.C_self[s] for s in range(L)]
    Cb_lv = [bj.C_buddy[s] for s in range(L)]
    for s in range(L):
        src_s = fetch(f"panel{j}.bundle@level{s}", lane ^ (1 << s))
        W_lv[s] = comm.fetch_lane(bj.W[s], lane, src_s)
        Cs_lv[s] = comm.fetch_lane(bj.C_buddy[s], lane, src_s, into=Cs_lv[s])
        Cb_lv[s] = comm.fetch_lane(bj.C_self[s], lane, src_s, into=Cb_lv[s])
    bundles = list(state.bundles)
    bundles[j] = RecoveryBundle(
        W=jnp.stack(W_lv), C_self=jnp.stack(Cs_lv), C_buddy=jnp.stack(Cb_lv),
        Y2=comm.fetch_lane(bj.Y2, lane, src_l, lane_axis=1),
        T=comm.fetch_lane(bj.T, lane, src_l, lane_axis=1),
        self_was_top=bj.self_was_top,
    )
    state = state.replace(
        factors=tuple(factors), R_rows=tuple(R_rows), bundles=tuple(bundles))
    return state, rows


# -- the scheduled (trace-time) driver ---------------------------------------


class FTSweepDriver:
    """Level-stepped windowed CAQR sweep with failure injection + REBUILD.

    A thin loop over the reified state machine: each iteration runs
    ``repro.ft.online.state.sweep_step`` (one sweep point), then fires the
    scheduled deaths of the just-completed point and repairs them with
    ``obliterate_state`` / ``rebuild_state``. Comm-generic (paper §II
    execution model; DESIGN.md §8): under ``SimComm`` lanes are simulator
    slices of single-device arrays; under ``AxisComm`` (inside
    ``shard_map``) each lane is a real device and every kill/fetch is a
    masked collective. The two paths run the same floating-point program
    and produce bit-identical results.

    ``A0`` is the initial matrix — SimComm layout ``(P, m_loc, n)``, per-lane
    ``(m_loc, n)`` under AxisComm — and doubles as the re-readable data
    source of the paper's recovery model. Any shape ``caqr_factorize``
    accepts is accepted here: the driver runs at the same padded
    ``sweep_geometry``, and a respawned lane re-reads its *padded* initial
    slice (re-reading the raw slice and re-padding is the same thing — the
    pad is static zeros, not lost state), so every REBUILD stays
    single-source and the outputs stay bit-identical to the failure-free
    general-shape sweep.
    """

    def __init__(
        self,
        A0: jax.Array,
        comm,
        panel_width: int,
        schedule: Optional[FailureSchedule] = None,
        detector: Optional[Detector] = None,
        scheme: Optional[CodingScheme] = None,
    ):
        self.comm = comm
        self.scheme = _XOR_SCHEME if scheme is None else scheme
        self.P = comm.axis_size()
        # SimComm runs eagerly (lane kills between real dispatches, timed
        # REBUILDs); AxisComm traces the whole sweep into one program, so
        # device syncs / wall clocks are meaningless there.
        self._eager = isinstance(comm, SimComm)
        self.levels = _levels(self.P)
        assert self.levels >= 1, "need at least 2 lanes to tolerate failures"
        self.b = panel_width
        self.state = initial_sweep_state(comm, A0, panel_width)
        self.geom = self.state.geom
        self.detector = detector or Detector(self.P, schedule)
        self.events: List[RecoveryEvent] = []

    # -- sweep -------------------------------------------------------------

    def run(self) -> FTSweepResult:
        while self.state.cursor is not None:
            point = self.state.cursor
            self.state = sweep_step(self.comm, self.state)
            # re-encode the parity slots from live state BEFORE the just-
            # completed point's deaths fire: a boundary decode must see
            # survivors exactly as encoded (identity under XOR pairing)
            self.state = self.scheme.refresh(self.comm, self.state)
            self._checkpoint(point)
        R, factors, bundles = finalize(self.comm, self.state)
        return FTSweepResult(R=R, factors=factors, bundles=bundles,
                             events=self.events)

    # -- failure injection + REBUILD ---------------------------------------

    def _checkpoint(self, point: Tuple[int, str, int]) -> None:
        newly = self.detector.begin_step(point)
        if not newly:
            return
        # the sync drains the async-dispatched sweep prefix so the latency
        # clock covers only each REBUILD itself; no-op under tracing
        sync = _block_on_state if self._eager else None
        self.state, events = recover_lanes(
            self.comm, self.state, newly, point, self.detector.dead,
            sync=sync, on_recovered=self.detector.revive,
            scheme=self.scheme,
        )
        self.events.extend(events)


def _block_on_state(state: SweepState) -> None:
    jax.block_until_ready(jax.tree_util.tree_leaves(state))


def ft_caqr_sweep(
    A0: jax.Array,
    comm,
    panel_width: int,
    schedule: Optional[FailureSchedule] = None,
    semantics: Optional["Semantics"] = None,
    scheme: Optional[CodingScheme] = None,
) -> FTSweepResult:
    """Run the full windowed FT-CAQR sweep under a failure schedule
    (paper §II-III end to end).

    Returns ``(R, factors, bundles, events)`` — bit-identical to the
    failure-free run regardless of the schedule (the paper's recovery
    guarantee), with one ``RecoveryEvent`` per REBUILD. The failure-free
    run steps the same state machine ``caqr_factorize`` compiles whole,
    so the two agree to float32 rounding.

    ``semantics`` selects the FT-MPI continuation policy: REBUILD
    (default) runs this driver; SHRINK/BLANK delegate to the scheduled
    elastic driver (``repro.ft.elastic.ft_caqr_sweep_elastic``), which
    returns an ``ElasticSweepResult`` with a host-spliced R instead.

    ``scheme`` selects the redundancy coding (``repro.ft.coding``):
    ``XORPairScheme`` (default — the paper's pairwise XOR, one death per
    pair) or ``MDSScheme(f=...)``, whose coded checksum slots recover ANY
    ``f`` simultaneous deaths — including a whole former XOR buddy pair —
    still bitwise-identical to the failure-free sweep.

    ``comm`` selects the execution: ``SimComm(P)`` for the single-device
    simulator, ``AxisComm(axis)`` inside ``shard_map`` for the production
    SPMD path (use ``repro.launch.spmd_qr.ft_caqr_sweep_spmd`` which wires
    the mesh and output layouts). For *runtime-detected* (unscripted)
    failures, use the online orchestrator
    (``repro.ft.online.orchestrator.SweepOrchestrator``), which drives the
    same state machine.

    Example (simulator; kill lane 1 after panel 0's level-0 trailing
    combine, recover, and match the failure-free sweep bit for bit):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import SimComm
    >>> from repro.ft import FailureSchedule, ft_caqr_sweep, sweep_point
    >>> A = jnp.asarray(np.random.default_rng(0).standard_normal((2, 4, 4)),
    ...                 jnp.float32)
    >>> sched = FailureSchedule(events={sweep_point(0, "trailing", 0): [1]})
    >>> out = ft_caqr_sweep(A, SimComm(2), 4, schedule=sched)
    >>> ref = ft_caqr_sweep(A, SimComm(2), 4)
    >>> bool(jnp.array_equal(out.R, ref.R))
    True
    >>> [(e.point, e.lane) for e in out.events]
    [((0, 'trailing', 0), 1)]
    """
    if semantics is not None and semantics is not Semantics.REBUILD:
        from repro.ft.elastic import ft_caqr_sweep_elastic

        return ft_caqr_sweep_elastic(
            A0, comm, panel_width, schedule=schedule, semantics=semantics,
            scheme=scheme)
    return FTSweepDriver(A0, comm, panel_width, schedule,
                         scheme=scheme).run()
