"""Host-side orchestrator: compiled sweep segments + runtime recovery.

This inverts the control flow of the scheduled FT path (DESIGN.md §9): the
sweep no longer runs as one traced program with a baked-in
``FailureSchedule`` — the host loops over *compiled segments* of the
reified state machine (``repro.ft.online.state.sweep_step``), and between
segments it

1. runs the registered **fault hooks** (test/demo injectors — in production
   the faults are real and this list is empty),
2. **polls the detector** (``repro.ft.online.detect``) — deaths are
   discovered, never scripted,
3. synthesizes the **REBUILD** for whatever was found, with the same
   ``obliterate_state`` / ``rebuild_state`` transitions the scheduled
   driver uses (one ``RecoveryEvent`` per death, single-source ledger and
   all), attributed to the just-completed sweep point,
4. optionally **persists** the state (diskless snapshot store or any
   ``push(state)`` callable) so an orchestrator killed mid-sweep can be
   resumed from the last boundary (``SweepOrchestrator.from_state``).

Because a boundary state is bit-identical to the monolithic driver's
checkpoint state, a death detected at the boundary after point ``p``
recovers into exactly the state a trace-time ``FailureSchedule({p: [lane]})``
run has after its REBUILD — the scheduled path stays the differential
oracle for the online path (``tests/test_online_recovery.py``).

Detection latency: the NaN-sentinel probe catches a death at the first
boundary after it happens — at most one segment late. A missed poll (a
detector false-negative) is still recoverable as long as the dead lane's
state has not crossed into a survivor through a collective: the intervening
segment must be lane-local for the dead lane (a ``leaf`` segment, or any
segment where the dead lane is not the panel's deposit root). The
one-segment-late case is regression-tested; longer blindness can
contaminate survivors and then honestly fails the NaN oracle.

Execution backends: under ``SimComm`` segments are jitted directly; for the
production SPMD path pass ``step_fn=`` a shard_map segment runner
(``repro.launch.spmd_qr.make_spmd_sweep_step``) — the state then lives as
global lane-sharded arrays between segments, host-side death masking runs
through the SimComm primitives on the identical global layout, and the
REBUILD runs as the runner's own shard_map heal program (``step_fn.heal``).
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.comm import SimComm
from repro.ft.coding import CodingScheme, XORPairScheme
from repro.ft.driver import (
    FTSweepResult,
    RecoveryEvent,
    obliterate_state,
    recover_lanes,
    rebuild_state,
)
from repro.ft.elastic import ElasticController, ElasticSweepResult
from repro.ft.failures import PHASE_LEAF, LaneFailure, prev_sweep_point
from repro.ft.online.detect import NaNSentinelDetector, OnlineDetector
from repro.ft.online.state import (
    SweepState,
    finalize,
    initial_sweep_state,
    run_panel_fused,
    run_steps,
    state_lane_axes,
)
from repro.ft.semantics import Semantics
from repro.ft.stragglers import (
    SpeculationEvent,
    StragglerMonitor,
    StragglerPolicy,
)

# One jitted segment runner per (comm, segment size); jax's own cache then
# specializes per state treedef (= per cursor), so every orchestrator over
# the same geometry shares compiled segments.
_SEGMENT_CACHE: Dict[Tuple, Callable] = {}

FaultHook = Callable[[object, SweepState], SweepState]
BoundaryHook = Callable[["SweepOrchestrator"], None]

# Host spans of the sweep loop, recorded into the profiler's trace (no-ops
# when no profiler runs): ``ftqr.sweep`` around one factorization, and
# inside it ``ftqr.dispatch`` per segment enqueue, ``ftqr.poll`` per
# detector poll and ``ftqr.heal`` per recovery. ``bench/program_spans.py``
# splits the device's idle time among them (``idle_share.dispatch``,
# ``.poll``, ``.loop``, ``.heal``, ``heal_ops``). ``sweep`` ids tie the
# spans to their factorization; ``ftqr.sweep`` names the layout (``chips``,
# ``lanes_per_chip``), ``ftqr.heal`` the dead lanes' ``chip`` and its
# ``xchip_reads``, the artifacts it read from another chip.
_SWEEP_IDS = itertools.count()


def forwarding_jit(f: Callable):
    """``jax.jit(f)`` for a function of one pytree whose unchanged leaves
    come back as the caller's own arrays.

    A jitted program materializes every output, so a sweep segment would
    copy every leaf it passes through — the source matrix, every stored
    panel's factors and bundles — and hold input and output state at once
    (twice the state in device memory). Here the traced function returns
    only the leaves it computed; the leaves it returns untouched (the same
    tracer object as an input leaf) are re-inserted on the host. The map
    from output to input is recorded while tracing, once per input
    structure. ``_cache_size()`` counts the compiled specializations."""
    plans: Dict = {}

    @functools.partial(jax.jit, static_argnums=1)
    def run(leaves, treedef):
        out, out_tree = jax.tree_util.tree_flatten(
            f(jax.tree_util.tree_unflatten(treedef, leaves)))
        index = {id(x): i for i, x in enumerate(leaves)}
        src = tuple(index.get(id(x)) for x in out)
        plans[treedef] = (out_tree, src)
        return [x for x, i in zip(out, src) if i is None]

    def call(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        fresh = iter(run(leaves, treedef))
        out_tree, src = plans[treedef]
        return jax.tree_util.tree_unflatten(
            out_tree, [next(fresh) if i is None else leaves[i] for i in src])

    call._cache_size = run._cache_size
    return call


def compiled_segment(comm, n_points: int) -> Callable[[SweepState], SweepState]:
    """The RESIDENT compiled segment runner: a process-wide jitted
    ``run_steps(comm, state, n_points)`` shared by every caller over the
    same ``(comm kind, P, segment size)`` — the orchestrator's segments and
    the multi-tenant ``repro.serve.qr_service`` slots all dispatch through
    the same callable. jax's jit cache then specializes per state treedef
    (= per geometry + cursor), so two tenants at the same bucket and sweep
    point share one compiled program; after one warm sweep per bucket no
    new compilation happens no matter how many requests flow through
    (``fn._cache_size()`` counts the resident specializations)."""
    key = (type(comm).__name__, comm.axis_size(), n_points)
    fn = _SEGMENT_CACHE.get(key)
    if fn is None:
        fn = forwarding_jit(lambda s: run_steps(comm, s, n_points))
        _SEGMENT_CACHE[key] = fn
    return fn


def compiled_finalize(comm) -> Callable[[SweepState], Tuple]:
    """``finalize`` as one compiled program per ``(comm kind, P)`` (jax
    specializes it per geometry): the last deposit, the stacking of every
    panel's factors and bundles and the R assembly run without eager
    intermediates — at the production shape the stacked outputs alone are
    several GiB."""
    key = (type(comm).__name__, comm.axis_size(), "finalize")
    fn = _SEGMENT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(lambda s: finalize(comm, s))
        _SEGMENT_CACHE[key] = fn
    return fn


class SweepOrchestrator:
    """Run the FT-CAQR sweep as host-controlled segments with runtime
    failure detection and REBUILD (the paper's online execution model).

    Parameters
    ----------
    A0, comm, panel_width:
        As ``ft_caqr_sweep`` — any general shape, SimComm layout
        ``(P, m_loc, n)``. (Omit and use :meth:`from_state` to resume a
        persisted mid-sweep state instead.)
    detector:
        ``OnlineDetector`` polled at every boundary (default: the
        NaN-sentinel probe).
    segment_points:
        Sweep points per compiled segment (>= 1). Larger segments amortize
        host/dispatch overhead but widen the detection-latency window —
        ``benchmarks/bench_online.py`` measures the tradeoff.
    fused:
        Run whole-panel fused segments (``run_panel_fused`` — the
        ``kernels.fused_sweep`` megakernel path): O(1) dispatches per
        panel instead of O(points * ops), with boundaries (detector polls,
        hooks, persistence) at panel ends — the only legal fused
        boundaries. Bitwise-identical results. ``segment_points`` is
        ignored except to re-align a state resumed mid-panel. Mutually
        exclusive with ``step_fn``.
    jit_segments:
        Compile segments with ``jax.jit`` (default). ``False`` runs them
        eagerly — slower, handy for debugging.
    step_fn:
        Optional external segment backend, called as ``step_fn(state) ->
        state`` once per sweep point: the SPMD path passes the shard_map
        runner from ``repro.launch.spmd_qr.make_spmd_sweep_step``, whose
        ``heal`` attribute gives the REBUILD program.
    fault_hooks:
        Callables ``hook(comm, state) -> state`` run at every boundary
        *before* the detector poll — test/demo fault injectors
        (``ScriptedKiller``, ``WallClockKiller``).
    boundary_hooks:
        Callables ``hook(orchestrator)`` run at every boundary *after*
        detection + recovery, when the state is healed and consistent —
        the admission surface: a serving layer can inspect
        ``orch.state.cursor``, swap work in at a panel boundary, or
        harvest per-boundary telemetry. Mutating ``orch.state`` here is
        legal exactly when the cursor sits at a panel boundary
        (``deposit_boundary`` semantics) — ``repro.serve.qr_service``
        builds its continuous-batching admission on this contract.
    store, persist_every:
        If a store is given, ``store.push(state)`` every ``persist_every``
        boundaries (default 1 = every boundary) and at the final one —
        e.g. ``repro.ckpt.diskless.SweepStateStore``.
    semantics:
        FT-MPI continuation policy on detection (``repro.ft.semantics``).
        REBUILD (default) is the paper's recovery; ABORT re-raises the
        death as ``LaneFailure``; SHRINK/BLANK continue elastically
        (``repro.ft.elastic``): the death is healed from its XOR buddies
        like a REBUILD, then at the next panel boundary the world
        re-meshes (survivor adopts the rows / hole stays masked) and the
        sweep resumes as a new epoch. Elastic runs return
        ``ElasticSweepResult`` (host-spliced R) instead of
        ``FTSweepResult``.
    elastic_policy:
        Slot policy of a shrunken world: ``"pad"`` (default — ceil-pow2
        slots with zero-row ghosts) or ``"fold"`` (floor-pow2, rows
        re-split; the SPMD re-mesh uses this so the new mesh fits on
        surviving devices).
    step_factory:
        Required with ``step_fn`` + elastic semantics: called as
        ``step_factory(n_slots)`` after a transition to build the new
        world's segment runner
        (``repro.launch.spmd_qr.make_spmd_step_factory``).
    grow_at:
        Optional sweep point; when it completes, a returning lane re-joins
        at the next panel boundary (``ElasticController.request_grow``).
    straggler_monitor, lane_clock:
        Wire a ``repro.ft.stragglers.StragglerMonitor`` into the segment
        loop: ``lane_clock(comm, state)`` returns per-lane times for the
        just-run segment (tests simulate; a pod reports real step times).
        Policy SPECULATE races a buddy recompute of a flagged lane's
        sweep point against the straggler (first result wins,
        bitwise-checked, logged as ``SpeculationEvent`` in
        ``self.speculations``); EVICT (or ``escalate_after`` exhausted)
        poisons the lane and escalates to a SHRINK transition.
    async_segments:
        Double-buffered segment execution: dispatch segment N+1 before
        polling the detector on segment N's boundary. Results are
        bitwise-identical to the sync loop — a fault-hook mutation or a
        detected death discards the in-flight speculation and re-dispatches
        from the recovered state
        (``tests/test_online_recovery.py::test_async_matches_sync``).
        REBUILD/ABORT semantics only (no elastic/straggler/fused
        composition). ``benchmarks/bench_train.py`` gates async strictly
        cheaper per boundary than sync.
    """

    def __init__(
        self,
        A0=None,
        comm=None,
        panel_width: Optional[int] = None,
        detector: Optional[OnlineDetector] = None,
        *,
        segment_points: int = 1,
        fused: bool = False,
        jit_segments: bool = True,
        step_fn: Optional[Callable[[SweepState], SweepState]] = None,
        fault_hooks: Sequence[FaultHook] = (),
        boundary_hooks: Sequence[BoundaryHook] = (),
        store=None,
        persist_every: Optional[int] = None,
        semantics: Semantics = Semantics.REBUILD,
        state: Optional[SweepState] = None,
        elastic_policy: str = "pad",
        step_factory: Optional[Callable[[int], Callable]] = None,
        grow_at=None,
        straggler_monitor: Optional[StragglerMonitor] = None,
        lane_clock: Optional[Callable] = None,
        scheme: Optional[CodingScheme] = None,
        async_segments: bool = False,
    ):
        assert comm is not None, "comm is required"
        self.comm = comm
        if state is None:
            assert A0 is not None and panel_width is not None, \
                "need (A0, panel_width) or a resume state"
            state = initial_sweep_state(comm, A0, panel_width)
        self.state = state
        self.detector = detector if detector is not None else NaNSentinelDetector()
        assert segment_points >= 1
        self.segment_points = segment_points
        assert not (fused and step_fn is not None), (
            "fused segments replace the per-point runner; pass one or the "
            "other")
        self.fused = fused
        self.jit_segments = jit_segments
        self.step_fn = step_fn
        if step_fn is None and jit_segments:
            assert isinstance(comm, SimComm), (
                "jitted host segments need SimComm; pass step_fn= for the "
                "shard_map backend (repro.launch.spmd_qr.make_spmd_sweep_step)"
            )
        self.fault_hooks = list(fault_hooks)
        self.boundary_hooks = list(boundary_hooks)
        self.store = store
        if store is not None and persist_every is None:
            persist_every = 1  # a store with no cadence means every boundary
        self.persist_every = persist_every
        self.semantics = semantics
        self.elastic_policy = elastic_policy
        self.step_factory = step_factory
        self.grow_at = grow_at
        self.elastic: Optional[ElasticController] = None
        if semantics in (Semantics.SHRINK, Semantics.BLANK):
            self.elastic = ElasticController(
                semantics, self.state.geom, policy=elastic_policy)
        self.straggler_monitor = straggler_monitor
        self.lane_clock = lane_clock
        self.scheme = XORPairScheme() if scheme is None else scheme
        self.async_segments = async_segments
        if async_segments:
            assert semantics in (Semantics.REBUILD, Semantics.ABORT), (
                "async double-buffered segments compose with REBUILD/ABORT "
                "only; elastic transitions re-mesh the world mid-run and "
                "would invalidate every in-flight speculation")
            assert straggler_monitor is None and grow_at is None and not fused
        # set by from_state: a resumed orchestrator owes the resume boundary
        # a hook/poll pass BEFORE running any segment (deaths that struck
        # while the sweep was suspended are recoverable only from the
        # persisted state — under MDSScheme that needs the persisted parity
        # slots, wire-format v2)
        self._resumed = False
        self.speculations: List[SpeculationEvent] = []
        self._spec_counts: Dict[int, int] = {}
        self.events: List[RecoveryEvent] = []
        # run statistics (benchmarks read these)
        self.segments_run = 0
        self.boundaries = 0
        # host seconds inside the detector polls, including the wait for
        # the segment the poll reads; what the poll costs the device is the
        # device idle under the ``ftqr.poll`` spans (``idle_share.poll`` of
        # ``bench/program_spans.py``)
        self.poll_s = 0.0

    @classmethod
    def from_state(cls, state: SweepState, comm, **kw) -> "SweepOrchestrator":
        """Resume from a persisted mid-sweep ``SweepState`` (e.g.
        ``repro.ckpt.load_sweep_state`` or a diskless snapshot). The
        recovery-event log of the previous incarnation is not carried
        over."""
        orch = cls(comm=comm, state=state, **kw)
        orch._resumed = True
        return orch

    # -- segments ----------------------------------------------------------

    def _stepped(self, state: SweepState, n_points: int) -> SweepState:
        if not self.jit_segments:
            return run_steps(self.comm, state, n_points)
        return compiled_segment(self.comm, n_points)(state)

    def _fused_segment(self, state: SweepState) -> SweepState:
        # a state resumed mid-panel first steps to the next leaf boundary
        # (fused segments only start there), then runs whole panels
        while state.cursor is not None and state.cursor[1] != PHASE_LEAF:
            state = self._stepped(state, 1)
        if state.cursor is None:
            return state
        if not self.jit_segments:
            return run_panel_fused(self.comm, state)
        key = (type(self.comm).__name__, self.comm.axis_size(), "fused")
        fn = _SEGMENT_CACHE.get(key)
        if fn is None:
            comm = self.comm
            fn = forwarding_jit(lambda s: run_panel_fused(comm, s))
            _SEGMENT_CACHE[key] = fn
        return fn(state)

    def _segment(self, state: SweepState) -> SweepState:
        panel, phase, level = state.cursor
        with TraceAnnotation("ftqr.dispatch", panel=panel, phase=phase,
                             level=level):
            if self.step_fn is not None:
                for _ in range(self.segment_points):
                    if state.cursor is None:
                        break
                    state = self.step_fn(state)
                return state
            if self.fused:
                return self._fused_segment(state)
            return self._stepped(state, self.segment_points)

    def _finalize(self):
        if not self.jit_segments:
            return finalize(self.comm, self.state)
        return compiled_finalize(self.comm)(self.state)

    # -- the host loop -----------------------------------------------------

    def run(self) -> FTSweepResult:
        """Drive the sweep to completion; returns the same ``FTSweepResult``
        as ``ft_caqr_sweep`` (bit-identical to the failure-free sweep no
        matter what the detector found, or ``UnrecoverableFailure``).
        Under SHRINK/BLANK semantics returns ``ElasticSweepResult``
        instead — epochs at different world sizes have no common lane
        layout for factors, so R is host-spliced."""
        geom = self.state.geom
        lanes = self.comm.axis_size()
        with TraceAnnotation(
                "ftqr.sweep", sweep=next(_SWEEP_IDS), lanes=lanes,
                m_loc=geom.m_loc, n=geom.n,
                chips=getattr(self.step_fn, "chips", 1),
                lanes_per_chip=getattr(self.step_fn, "lanes_per_chip",
                                       lanes)):
            if self._resumed:
                self._resumed = False
                self._resume_boundary_pass()
            if self.async_segments:
                return self._run_async()
            return self._run_sync()

    def _run_sync(self):
        """The sync loop: per boundary [segment, refresh, hooks, poll,
        recover, boundary hooks]."""
        boundary = 0
        while True:
            # re-read per iteration: an elastic transition swaps in a new
            # epoch's geometry (and comm) mid-run
            geom = self.state.geom
            levels = geom.levels
            if self.state.cursor is not None:
                self.state = self._segment(self.state)
                self.segments_run += 1
            boundary += 1
            self.boundaries += 1
            # re-encode the parity slots from the (all-live) boundary state
            # BEFORE the fault hooks / detector can observe deaths for this
            # boundary: the decode must see survivors exactly as encoded
            self.state = self.scheme.refresh(self.comm, self.state)
            # the just-completed point = the recoverable boundary any death
            # discovered now is attributed to
            point = prev_sweep_point(self.state.cursor, geom.n_panels, levels)
            for hook in self.fault_hooks:
                self.state = hook(self.comm, self.state)
            newly = self._poll(self.state, boundary)
            if newly:
                self._recover(newly, point)
            if (self.straggler_monitor is not None
                    and self.lane_clock is not None
                    and self.state.cursor is not None):
                self._check_stragglers(point)
            if self.elastic is not None and point == self.grow_at:
                self.elastic.request_grow()
            self._maybe_transition()
            for hook in self.boundary_hooks:
                hook(self)
            if self.store is not None and self.persist_every and (
                    boundary % self.persist_every == 0
                    or self.state.cursor is None):
                self.store.push(self.state)
            if self.state.cursor is None and (
                    self.elastic is None or not self.elastic.pending):
                break
        if self.elastic is not None:
            return self.elastic.finish(self.comm, self.state, self.events)
        R, factors, bundles = self._finalize()
        return FTSweepResult(R=R, factors=factors, bundles=bundles,
                             events=self.events)

    def _resume_boundary_pass(self) -> None:
        """Hook/poll pass at the RESUME boundary, before any segment runs.

        A death that struck while the sweep was suspended (or is injected
        at the resume point) must be recovered from the state exactly as
        persisted: the parity slots are NOT re-encoded first — under
        ``MDSScheme`` the joint decode uses the persisted ``state.code``
        (sweep-state wire format v2, ``repro.ft.online.state``). A v1 state
        resumes with ``code=None``, so a multi-death at this boundary that
        exceeds the XOR pairing is honestly ``UnrecoverableFailure`` — the
        re-encode window of vulnerability that v2 closes."""
        if self.state.cursor is None:
            return
        geom = self.state.geom
        point = prev_sweep_point(self.state.cursor, geom.n_panels, geom.levels)
        if point is None:
            return  # resumed at the very first point: nothing completed yet
        for hook in self.fault_hooks:
            self.state = hook(self.comm, self.state)
        newly = self._poll(self.state, 0)
        if newly:
            self._recover(newly, point)

    def _poll(self, state: SweepState, boundary: int) -> List[int]:
        """One detector poll, under its ``ftqr.poll`` span."""
        with TraceAnnotation("ftqr.poll", boundary=boundary):
            t0 = time.perf_counter()
            newly = list(self.detector.poll(self.comm, state))
            self.poll_s += time.perf_counter() - t0
        return newly

    def _run_async(self) -> FTSweepResult:
        """The double-buffered segment loop (async mode).

        Per boundary the sync loop serializes [segment, refresh, hooks,
        poll, recover]; under jax's async dispatch the poll is the only
        step that *must* materialize device values, so this loop dispatches
        the NEXT segment speculatively before collecting the detector probe
        — the device computes segment N+1 while the host blocks on segment
        N's sentinels. The speculation is kept only when the boundary was
        quiet; a fault-hook mutation (object identity — hooks return the
        same state when they do nothing) or a detected death discards it
        and re-dispatches from the authoritative recovered state, which is
        exactly what the sync loop would have run — results stay bitwise
        identical to sync execution
        (``tests/test_online_recovery.py::test_async_matches_sync``)."""
        boundary = 0
        cur = self.state
        if cur.cursor is not None:
            cur = self._segment(cur)
            self.segments_run += 1
        while True:
            geom = cur.geom
            # re-encode parity from the boundary state BEFORE anything can
            # observe this boundary's deaths (same contract as sync)
            cur = self.scheme.refresh(self.comm, cur)
            point = prev_sweep_point(cur.cursor, geom.n_panels, geom.levels)
            pre_hooks = cur
            for hook in self.fault_hooks:
                cur = hook(self.comm, cur)
            spec = None
            if cur is pre_hooks and cur.cursor is not None:
                # quiet so far: dispatch the next segment ahead of the
                # (blocking) detector collect — the double buffer
                spec = self._segment(cur)
            boundary += 1
            self.boundaries += 1
            newly = self._poll(cur, boundary)
            self.state = cur
            if newly:
                spec = None  # speculated from a state recovery rewrites
                self._recover(newly, point)
            for hook in self.boundary_hooks:
                hook(self)
            if self.store is not None and self.persist_every and (
                    boundary % self.persist_every == 0
                    or self.state.cursor is None):
                self.store.push(self.state)
            if self.state.cursor is None:
                break
            if spec is not None and self.state is cur:
                cur = spec
                self.segments_run += 1
            else:
                # a hook/recovery rewrote the state: the speculative
                # dispatch is stale — re-dispatch from the real boundary
                cur = self._segment(self.state)
                self.segments_run += 1
        R, factors, bundles = self._finalize()
        return FTSweepResult(R=R, factors=factors, bundles=bundles,
                             events=self.events)

    # -- elastic transitions -----------------------------------------------

    def _maybe_transition(self) -> None:
        """Apply a pending SHRINK/BLANK/grow at a panel boundary: the
        controller deposits + harvests + re-owns, and the orchestrator
        swaps in the new world's comm, segment runner, and detector
        arming."""
        while self.elastic is not None and \
                self.elastic.ready(self.state.cursor):
            new_comm, new_state = self.elastic.transition(
                self.comm, self.state)
            self.state = new_state
            if new_comm is None:
                # the closing epoch already finished the factorization;
                # keep draining — leftover requests are bookkeeping only
                continue
            break
        else:
            return
        self.comm = new_comm
        if self.step_fn is not None:
            assert self.step_factory is not None, (
                "an elastic transition under step_fn= needs step_factory= "
                "to re-mesh the segment runner over the shrunken lane axis "
                "(repro.launch.spmd_qr.make_spmd_step_factory)")
            self.step_fn = self.step_factory(new_comm.axis_size())
        reset = getattr(self.detector, "reset", None)
        if reset is not None:
            reset()  # re-arm sentinels for the new world's lane numbering
        if self.straggler_monitor is not None:
            # lane ids re-number across a transition: stale EWMAs would
            # mis-attribute slowness in the new world
            self.straggler_monitor.ewma.clear()
            for k in self.straggler_monitor.flags:
                self.straggler_monitor.flags[k] = 0

    # -- stragglers --------------------------------------------------------

    def _check_stragglers(self, point) -> None:
        times = self.lane_clock(self.comm, self.state)
        flagged = self.straggler_monitor.report(times)
        cfg = self.straggler_monitor.cfg
        # clocks may keep reporting lanes of a pre-transition world (or
        # ghost slots): only live current-world lanes can be acted on
        flagged = [
            l for l in flagged
            if l < self.comm.axis_size() and (
                self.elastic is None or self.elastic.world.live[l])]
        for lane in flagged:
            if cfg.policy is StragglerPolicy.SPECULATE:
                self._speculate(lane, point)
                self.straggler_monitor.flags[lane] = 0
                n = self._spec_counts.get(lane, 0) + 1
                self._spec_counts[lane] = n
                if cfg.escalate_after is not None and n >= cfg.escalate_after:
                    self._evict(lane, point)
            elif cfg.policy is StragglerPolicy.EVICT:
                self._evict(lane, point)
            # REBALANCE/IGNORE have no mid-sweep action: row ownership is
            # fixed by the factorization, only the batch pipeline rebalances

    def _speculate(self, lane: int, point) -> None:
        """Speculative buddy recompute of a straggler's sweep point: run
        the proven REBUILD arithmetic for ``lane`` on a copy (sourcing
        from its XOR buddies), bitwise-compare the lane's slice, and let
        the first finished result win — the sweep never blocks on the
        slow lane. A mismatch means the lane was corrupt, not slow; the
        rebuilt copy is authoritative either way."""
        struck = obliterate_state(self.comm, self.state, lane)
        spec, reads = rebuild_state(self.comm, struck, lane, point, {lane})
        axes = state_lane_axes(self.state)
        flat_own = jax.tree_util.tree_leaves(self.state)
        flat_spec = jax.tree_util.tree_leaves(spec)
        flat_ax = jax.tree_util.tree_leaves(axes)
        matched = all(
            np.array_equal(
                np.asarray(self.comm.lane_slice(a, lane, ax)),
                np.asarray(self.comm.lane_slice(b, lane, ax)))
            for a, b, ax in zip(flat_own, flat_spec, flat_ax)
            if ax >= 0)  # ax < 0: no lane axis (checksum-lane parity slots)
        self.state = spec  # first result wins (bitwise-equal when matched)
        self.speculations.append(SpeculationEvent(
            point=tuple(point), lane=lane, matched=matched, reads=reads))

    def _evict(self, lane: int, point) -> None:
        """Persistent straggler: treat it as failed. Poison it, heal from
        its buddies, and hand it to the elastic controller as a SHRINK
        death — the world re-meshes without it at the next boundary."""
        if self.elastic is None:
            self.elastic = ElasticController(
                Semantics.SHRINK, self.state.geom, policy=self.elastic_policy)
        self.state = obliterate_state(self.comm, self.state, lane)
        self._heal([lane], point)
        self.elastic.note_deaths([lane])
        self.straggler_monitor.ewma.pop(lane, None)
        self.straggler_monitor.flags[lane] = 0
        self._spec_counts.pop(lane, None)

    # -- recovery ----------------------------------------------------------

    def _recover(self, newly: List[int], point) -> None:
        assert point is not None, "death detected before any sweep point ran"
        if self.semantics is Semantics.ABORT:
            raise LaneFailure(newly[0], point)
        # SHRINK/BLANK heal exactly like REBUILD (the adopter "hosts" the
        # dead slot until the panel boundary), then note the death for the
        # boundary transition
        self._heal(newly, point)
        if self.elastic is not None and self.semantics in (
                Semantics.SHRINK, Semantics.BLANK):
            self.elastic.note_deaths(newly)

    def _heal(self, newly: List[int], point) -> None:
        panel, phase, level = point
        dead = set(newly)
        if self.step_fn is None:
            heal = functools.partial(recover_lanes, self.comm)
            chips, xchip_reads = "0", 0
        else:
            # On the shard_map path the state lives as lane-sharded global
            # arrays. The heal is one program on the lanes' own devices
            # (``MeshHeal``): ``recover_lanes`` under the segments' own
            # comm, so the replay runs the sweep's floating-point program,
            # on the dead lane's chip block as the segments ran it, and
            # each buddy artifact moves alone, point to point. That keeps
            # the healed R bit-identical to the failure-free one. Eager
            # replay math on the global arrays would compile auto-sharded
            # executables whose reduction order drifts by ~1 ulp, and
            # gathering the state onto one device for the heal needs the
            # whole state in one chip's memory.
            heal = self.step_fn.heal(self.state, newly, point, self.scheme)
            chips, xchip_reads = heal.chips, heal.xchip_reads

        def on_recovered(lane: int) -> None:
            dead.discard(lane)
            # announce the respawn so the detector re-arms for this lane
            # immediately (back-to-back deaths at consecutive boundaries
            # must still be seen)
            revive = getattr(self.detector, "revive", None)
            if revive is not None:
                revive(lane)

        with TraceAnnotation("ftqr.heal",
                             lanes=" ".join(str(l) for l in sorted(newly)),
                             panel=panel, phase=phase, level=level,
                             chip=chips, xchip_reads=xchip_reads):
            # the SAME strike-then-rebuild protocol as the scheduled driver's
            # checkpoint — shared code, so the scheduled-vs-online bitwise
            # equivalence cannot drift apart in one copy
            self.state, events = heal(
                self.state, newly, point, dead,
                sync=lambda s: jax.block_until_ready(
                    jax.tree_util.tree_leaves(s)),
                on_recovered=on_recovered,
                scheme=self.scheme,
            )
            self.events.extend(events)


def ft_caqr_sweep_online(
    A0,
    comm,
    panel_width: int,
    detector: Optional[OnlineDetector] = None,
    **kw,
) -> FTSweepResult:
    """One-call form of the online path: ``SweepOrchestrator(...).run()``.

    The online counterpart of ``ft_caqr_sweep`` — same result layout, but
    failures are discovered by ``detector`` at runtime instead of scripted
    by a ``FailureSchedule``."""
    return SweepOrchestrator(A0, comm, panel_width, detector, **kw).run()
