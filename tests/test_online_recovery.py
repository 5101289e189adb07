"""Online recovery: the reified sweep state machine under runtime detection.

The scheduled (trace-time ``FailureSchedule``) driver is the differential
oracle throughout: iterating ``sweep_step`` to completion must be
bit-identical to the monolithic sweep, and a *runtime-detected* kill —
poison injected at a segment boundary, discovered by the NaN-sentinel
probe, rebuilt by the orchestrator — must produce output bit-identical to
the same kill expressed as a trace-time schedule (and hence to the
failure-free sweep). Also covered: two failures in different panels, a
detector false-negative surfacing one segment late, suspend/persist/resume
through ``repro.ckpt`` (numpy round-trip), and the diskless snapshot store.
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SimComm, caqr_factorize, sweep_geometry
from repro.ckpt import load_sweep_state, save_sweep_state
from repro.ckpt.diskless import SweepStateStore
from repro.ft import (
    FailureSchedule,
    SweepOrchestrator,
    UnrecoverableFailure,
    ft_caqr_sweep,
    ft_caqr_sweep_online,
    iter_sweep_points,
    sweep_point,
)
from repro.ft.failures import LaneFailure, next_sweep_point, prev_sweep_point
from repro.ft.online.detect import (
    DelayedDetector,
    FailStopDetector,
    NaNSentinelDetector,
    ScriptedKiller,
    WallClockKiller,
)
from repro.ft.online.state import (
    finalize,
    initial_sweep_state,
    run_steps,
    sweep_state_from_host,
    sweep_state_to_host,
    sweep_step,
)
from repro.ft.semantics import Semantics

# the PR-3 ragged geometry: unaligned lane heights AND a ragged last panel
RP, RM_LOC, RN, RB = 4, 6, 10, 4
RGEOM = sweep_geometry(RP, RM_LOC, RN, RB)
LEVELS = 2
R_POINTS = list(iter_sweep_points(RGEOM.n_panels, LEVELS))


def _matrix(P=RP, m_loc=RM_LOC, n=RN, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)


def _leaves(*trees):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(trees)]


def _assert_bit_identical(got, ref):
    for g, r in zip(_leaves(got.R, got.factors, got.bundles),
                    _leaves(ref.R, ref.factors, ref.bundles)):
        assert np.array_equal(g, r), "online output differs from oracle"


def _assert_same_events(got, sched):
    assert [(e.point, e.lane, e.reads) for e in got.events] == \
        [(e.point, e.lane, e.reads) for e in sched.events]


@pytest.fixture(scope="module")
def ragged_reference():
    A = _matrix()
    ref = caqr_factorize(A, SimComm(RP), RB, collect_bundles=True,
                         use_scan=False)
    return A, ref


# -- the state machine itself ------------------------------------------------


@pytest.mark.parametrize("shape", [
    ("aligned", 8, 16, 4), ("ragged", RM_LOC, RN, RB), ("wide", 4, 24, 4),
], ids=lambda s: s[0])
def test_stepped_iteration_matches_monolithic(shape):
    """Iterating jitted sweep_step to completion + finalize == the
    monolithic windowed sweep, bit for bit, on every geometry class."""
    _, m_loc, n, b = shape
    comm = SimComm(4)
    A = _matrix(4, m_loc, n, seed=5)
    ref = caqr_factorize(A, comm, b, collect_bundles=True, use_scan=False)
    step = jax.jit(functools.partial(sweep_step, comm))
    s = initial_sweep_state(comm, A, b)
    points = []
    while s.cursor is not None:
        points.append(s.cursor)
        s = step(s)
    assert points == list(iter_sweep_points(s.geom.n_panels, LEVELS))
    R, factors, bundles = finalize(comm, s)
    for g, r in zip(_leaves(R, factors, bundles),
                    _leaves(ref.R, ref.factors, ref.bundles)):
        assert np.array_equal(g, r)


def test_segment_forwards_unchanged_leaves():
    """A compiled segment returns the leaves it does not compute as the
    caller's own arrays (no device copy of the source matrix or of the
    stored panels), and its computed leaves equal a plain jit's."""
    from repro.ft.online.orchestrator import compiled_segment

    comm = SimComm(4)
    s = initial_sweep_state(comm, _matrix(4, 8, 16, seed=3), 4)
    seg = compiled_segment(comm, 1)
    while s.cursor != (1, "tsqr", 0):
        s = seg(s)
    out = seg(s)
    assert out.A0 is s.A0 and out.A is s.A and out.window is s.window
    assert all(o is i for o, i in zip(jax.tree_util.tree_leaves(out.bundles),
                                      jax.tree_util.tree_leaves(s.bundles)))
    want = jax.jit(functools.partial(run_steps, comm, max_points=1))(s)
    assert out.cursor == want.cursor
    for g, w in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_cursor_arithmetic_round_trip():
    """next/prev sweep-point are inverse over the whole enumeration."""
    pts = R_POINTS
    for a, b_ in zip(pts, pts[1:] + [None]):
        assert next_sweep_point(a, RGEOM.n_panels, LEVELS) == b_
        assert prev_sweep_point(b_, RGEOM.n_panels, LEVELS) == a
    assert prev_sweep_point(pts[0], RGEOM.n_panels, LEVELS) is None


# -- orchestrator: failure-free + the online kill matrix ---------------------


def test_orchestrator_failure_free(ragged_reference):
    A, ref = ragged_reference
    got = SweepOrchestrator(A, SimComm(RP), RB).run()
    _assert_bit_identical(got, ref)
    assert got.events == []


@pytest.mark.parametrize("lane", [0, 1, 3])
@pytest.mark.parametrize("point", R_POINTS,
                         ids=lambda p: f"p{p[0]}-{p[1]}{p[2]}")
def test_online_kill_matrix_ragged(ragged_reference, point, lane):
    """Every phase/level/panel of the ragged sweep: a runtime kill at the
    boundary, discovered by the NaN sentinel, is bit-identical to the same
    kill as a trace-time FailureSchedule (and to failure-free)."""
    A, ref = ragged_reference
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller({point: [lane]})])
    _assert_bit_identical(got, ref)
    sched = ft_caqr_sweep(A, SimComm(RP), RB,
                          schedule=FailureSchedule(events={point: [lane]}))
    _assert_same_events(got, sched)
    (event,) = got.events
    assert event.point == point and event.lane == lane
    assert all(src != lane for src in event.reads.values())


@pytest.mark.parametrize("geom", [
    ("aligned", 8, 16, 4, sweep_point(2, "trailing", 1), 2),
    ("wide", 4, 24, 4, sweep_point(2, "tsqr", 0), 1),
], ids=lambda g: g[0])
def test_online_kill_other_geometries(geom):
    _, m_loc, n, b, point, lane = geom
    comm = SimComm(4)
    A = _matrix(4, m_loc, n, seed=7)
    ref = caqr_factorize(A, comm, b, collect_bundles=True, use_scan=False)
    got = ft_caqr_sweep_online(
        A, comm, b, fault_hooks=[ScriptedKiller({point: [lane]})])
    _assert_bit_identical(got, ref)
    sched = ft_caqr_sweep(A, comm, b,
                          schedule=FailureSchedule(events={point: [lane]}))
    _assert_same_events(got, sched)


def test_online_two_failures_in_different_panels(ragged_reference):
    A, ref = ragged_reference
    kills = {sweep_point(0, "trailing", 1): [2], sweep_point(1, "tsqr", 0): [1]}
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller(kills)])
    _assert_bit_identical(got, ref)
    sched = ft_caqr_sweep(A, SimComm(RP), RB,
                          schedule=FailureSchedule(events=kills))
    _assert_same_events(got, sched)
    assert len(got.events) == 2


def test_online_same_lane_dies_twice_same_panel(ragged_reference):
    """The lane dies mid-trailing, is rebuilt, and dies AGAIN one level
    later in the same panel. The rebuild must fully heal the lane — a
    stale NaN (e.g. the running tsqr R) would keep its sentinel dark and
    the second death would go undetected until survivors were
    contaminated (regression for exactly that bug)."""
    A, ref = ragged_reference
    kills = {sweep_point(1, "trailing", 0): [2],
             sweep_point(1, "trailing", 1): [2]}
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller(kills)])
    _assert_bit_identical(got, ref)
    sched = ft_caqr_sweep(A, SimComm(RP), RB,
                          schedule=FailureSchedule(events=kills))
    _assert_same_events(got, sched)
    assert len(got.events) == 2


def test_rebuilt_state_carries_no_nan(ragged_reference):
    """After any REBUILD the state is NaN-free — the invariant the
    sentinel detector's re-arming relies on (checked via the deep scan at
    every boundary of a multi-death run)."""
    from repro.ft.online.detect import _deep_nan_lanes

    A, _ = ragged_reference
    comm = SimComm(RP)
    killer = ScriptedKiller({sweep_point(1, "trailing", 0): [2],
                             sweep_point(2, "tsqr", 1): [0]})
    seen_clean = []

    def audit(comm_, state):
        state = killer(comm_, state)
        seen_clean.append(True)
        return state

    orch = SweepOrchestrator(A, comm, RB, fault_hooks=[audit])
    orch.run()
    assert not _deep_nan_lanes(comm, orch.state)
    assert seen_clean


def test_online_simultaneous_non_buddy_deaths(ragged_reference):
    A, ref = ragged_reference
    point = sweep_point(1, "trailing", 0)
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller({point: [0, 3]})])
    _assert_bit_identical(got, ref)
    assert len(got.events) == 2


def test_online_buddy_pair_death_is_unrecoverable():
    """Both members of a level-0 pair die at once: discovered at the same
    boundary, and the REBUILD honestly refuses (the single source is dead)."""
    A = _matrix()
    point = sweep_point(1, "trailing", 0)
    with pytest.raises(UnrecoverableFailure):
        ft_caqr_sweep_online(
            A, SimComm(RP), RB, fault_hooks=[ScriptedKiller({point: [2, 3]})])


def test_detector_false_negative_one_segment_late(ragged_reference):
    """The detector misses the death once; it surfaces one segment later
    (after the lane-local leaf segment) and recovery at the *later*
    boundary is bit-identical to a schedule that kills there."""
    A, ref = ragged_reference
    killer = ScriptedKiller({sweep_point(0, "trailing", 1): [2]})
    det = DelayedDetector(NaNSentinelDetector(), miss=1)
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, detector=det, fault_hooks=[killer])
    _assert_bit_identical(got, ref)
    # attributed to the boundary where it was *found*, one point later
    late_point = sweep_point(1, "leaf")
    sched = ft_caqr_sweep(
        A, SimComm(RP), RB,
        schedule=FailureSchedule(events={late_point: [2]}))
    _assert_same_events(got, sched)
    assert got.events[0].point == late_point


def test_fail_stop_detector_report_delay(ragged_reference):
    """The injectable fail-stop detector: declared deaths surface after
    report_delay polls — delay 0 equals the sentinel path bitwise."""
    A, ref = ragged_reference
    point = sweep_point(1, "trailing", 1)
    det = FailStopDetector(report_delay=0)
    killer = ScriptedKiller({point: [3]})

    def kill_and_declare(comm, state):
        before = len(killer._fired)
        state = killer(comm, state)
        if len(killer._fired) > before:
            det.declare(3)
        return state

    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, detector=det, fault_hooks=[kill_and_declare])
    _assert_bit_identical(got, ref)
    assert [(e.point, e.lane) for e in got.events] == [(point, 3)]


def test_nan_sentinel_deep_scan(ragged_reference):
    """The deep (every-leaf) scan finds the same death the cheap sentinel
    probe does, end to end."""
    A, ref = ragged_reference
    point = sweep_point(2, "tsqr", 1)
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, detector=NaNSentinelDetector(deep=True),
        fault_hooks=[ScriptedKiller({point: [1]})])
    _assert_bit_identical(got, ref)
    assert [(e.point, e.lane) for e in got.events] == [(point, 1)]


# -- the compiled sentinel probe ---------------------------------------------

# SimComm(16) (four butterfly levels) at a tiny size: 3 panels
PROBE_P, PROBE_M_LOC, PROBE_N, PROBE_B = 16, 8, 12, 4
PROBE_POINTS = [sweep_point(1, "leaf"), sweep_point(1, "tsqr", 0),
                sweep_point(1, "tsqr", 3), sweep_point(1, "trailing", 0),
                sweep_point(1, "trailing", 3)]


@pytest.fixture(scope="module")
def probe_states():
    """The healthy SimComm(16) state at the boundary after each of
    ``PROBE_POINTS``."""
    from repro.ft.online.orchestrator import compiled_segment

    comm = SimComm(PROBE_P)
    s = initial_sweep_state(
        comm, _matrix(PROBE_P, PROBE_M_LOC, PROBE_N, seed=11), PROBE_B)
    seg = compiled_segment(comm, 1)
    states = {}
    while s.cursor is not None:
        s = seg(s)
        point = prev_sweep_point(s.cursor, s.geom.n_panels, s.geom.levels)
        if point in PROBE_POINTS:
            states[point] = s
    assert set(states) == set(PROBE_POINTS)
    return comm, states


@pytest.mark.parametrize("lane", [0, 6, 15])
@pytest.mark.parametrize("point", PROBE_POINTS,
                         ids=lambda p: f"p{p[0]}-{p[1]}{p[2]}")
def test_sentinel_probe_matches_deep_scan(probe_states, point, lane):
    """At a leaf, TSQR and trailing boundary, the compiled sentinel probe
    reports exactly the lanes the full scan reports: none on the healthy
    state, the dead lane once after ``obliterate_state``, and the same lane
    again after ``revive`` re-arms it."""
    from repro.ft.driver import obliterate_state

    comm, states = probe_states
    healthy = states[point]
    dead = obliterate_state(comm, healthy, lane)
    deep = NaNSentinelDetector(deep=True)
    assert deep.poll(comm, healthy) == []
    assert deep.poll(comm, dead) == [lane]
    det = NaNSentinelDetector()
    assert det.poll(comm, healthy) == []
    assert det.poll(comm, dead) == [lane]
    assert det.poll(comm, dead) == []  # reported once per death
    det.revive(lane)
    assert det.poll(comm, dead) == [lane]
    assert det.poll(comm, healthy) == []
    assert det.poll(comm, dead) == [lane]  # healed lanes re-arm by themselves


def test_sentinel_probe_programs():
    """Over one sweep the probe compiles once per distinct signature of the
    probed fields — fewer programs than boundaries, each built while the
    first sweep runs — and no program reshapes or materializes a field:
    every value it computes holds at most one float per lane."""
    from repro.ft.online.detect import _probed, _sentinel_probe

    seen = {}

    def record(orch):
        fields = _probed(orch.state)
        seen.setdefault(tuple((x.shape, x.dtype) for x in fields), fields)

    A = _matrix(PROBE_P, PROBE_M_LOC, PROBE_N, seed=12)
    _sentinel_probe.clear_cache()
    orch = SweepOrchestrator(A, SimComm(PROBE_P), PROBE_B,
                             boundary_hooks=[record])
    orch.run()
    assert 0 < _sentinel_probe._cache_size() <= len(seen) < orch.boundaries

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for param in eqn.params.values():
                sub = getattr(param, "jaxpr", param)  # a closed jaxpr's body
                if hasattr(sub, "eqns"):
                    yield from eqns(sub)

    for fields in seen.values():
        closed = jax.make_jaxpr(_sentinel_probe)(fields)
        for eqn in eqns(closed.jaxpr):
            assert eqn.primitive.name != "reshape", eqn
            for v in eqn.outvars:
                assert np.prod(v.aval.shape) <= PROBE_P, eqn
    # a later sweep over the same geometry builds nothing
    SweepOrchestrator(A, SimComm(PROBE_P), PROBE_B).run()
    assert _sentinel_probe._cache_size() <= len(seen)


def test_async_matches_sync():
    """The double-buffered loop against the sync loop on SimComm(8) with a
    kill after a trailing level: bit-identical R, factors and bundles, and
    the same recovery events (the kill discards the speculative segment)."""
    A = _matrix(8, 8, 16, seed=13)
    point = sweep_point(1, "trailing", 1)
    got = {}
    for async_segments in (False, True):
        got[async_segments] = ft_caqr_sweep_online(
            A, SimComm(8), 4, async_segments=async_segments,
            fault_hooks=[ScriptedKiller({point: [5]})])
    _assert_bit_identical(got[True], got[False])
    _assert_same_events(got[True], got[False])
    assert [(e.point, e.lane) for e in got[True].events] == [(point, 5)]


def test_segmented_execution_and_boundary_kill(ragged_reference):
    """segment_points > 1: fewer boundaries, same bits; a kill at a segment
    boundary recovers exactly like the scheduled oracle."""
    A, ref = ragged_reference
    orch = SweepOrchestrator(A, SimComm(RP), RB, segment_points=3)
    got = orch.run()
    _assert_bit_identical(got, ref)
    assert orch.segments_run == -(-len(R_POINTS) // 3)
    point = R_POINTS[2]  # just-completed at the first 3-point boundary
    got = ft_caqr_sweep_online(
        A, SimComm(RP), RB, segment_points=3,
        fault_hooks=[ScriptedKiller({point: [1]})])
    _assert_bit_identical(got, ref)
    sched = ft_caqr_sweep(A, SimComm(RP), RB,
                          schedule=FailureSchedule(events={point: [1]}))
    _assert_same_events(got, sched)


def test_abort_semantics_raises(ragged_reference):
    A, _ = ragged_reference
    point = sweep_point(0, "tsqr", 0)
    with pytest.raises(LaneFailure):
        ft_caqr_sweep_online(
            A, SimComm(RP), RB, semantics=Semantics.ABORT,
            fault_hooks=[ScriptedKiller({point: [1]})])


def test_wall_clock_killer(ragged_reference, fake_clock):
    """The unscripted demo path: the kill position is chosen by the clock;
    wherever it lands, the finished factorization is bit-identical. The
    injected fake clock (1s per boundary) makes the strike position
    deterministic — no dependence on host load."""
    A, ref = ragged_reference
    killer = WallClockKiller(after_s=3.0, lane=2, clock=fake_clock)
    got = ft_caqr_sweep_online(A, SimComm(RP), RB, fault_hooks=[killer])
    _assert_bit_identical(got, ref)
    # clock reads 0,1,2,3,... at consecutive boundaries: strike lands
    # exactly when 3.0s have "elapsed" — the 4th boundary, point index 3
    assert killer.struck_at == R_POINTS[3]
    assert [(e.point, e.lane) for e in got.events] == [(killer.struck_at, 2)]


# -- suspend / persist / resume ----------------------------------------------


def test_suspend_resume_npz_round_trip(tmp_path, ragged_reference):
    """Suspend mid-sweep to an .npz, reload (numpy-only round trip), resume
    in a fresh state machine: bit-identical finish. Exercises the
    repro.ckpt wire format the way a new process would."""
    A, ref = ragged_reference
    comm = SimComm(RP)
    s = initial_sweep_state(comm, A, RB)
    for _ in range(7):
        s = sweep_step(comm, s)
    cursor_at_save = s.cursor
    path = save_sweep_state(os.path.join(str(tmp_path), "mid_sweep"), s)

    # host-side inspection needs no device arrays at all
    host = load_sweep_state(path, to_device=False)
    assert host.cursor == cursor_at_save
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(host))
    assert host.geom == s.geom

    # resume in a fresh orchestrator ("new process": only the file crosses)
    resumed = SweepOrchestrator.from_state(
        load_sweep_state(path), SimComm(RP)).run()
    _assert_bit_identical(resumed, ref)


def test_suspend_resume_with_failure_after_resume(tmp_path, ragged_reference):
    """A lane dies *after* the resume: the restored state carries every
    recovery bundle, so REBUILD still works and still matches the oracle."""
    A, ref = ragged_reference
    comm = SimComm(RP)
    s = initial_sweep_state(comm, A, RB)
    for _ in range(4):
        s = sweep_step(comm, s)
    path = save_sweep_state(os.path.join(str(tmp_path), "mid"), s)
    point = sweep_point(2, "trailing", 0)
    got = SweepOrchestrator.from_state(
        load_sweep_state(path), SimComm(RP),
        fault_hooks=[ScriptedKiller({point: [0]})]).run()
    _assert_bit_identical(got, ref)
    assert [(e.point, e.lane) for e in got.events] == [(point, 0)]


def test_host_wire_format_identity(ragged_reference):
    """to_host/from_host is the identity on arrays, cursor, and geometry."""
    A, _ = ragged_reference
    comm = SimComm(RP)
    s = initial_sweep_state(comm, A, RB)
    for _ in range(9):
        s = sweep_step(comm, s)
    s2 = sweep_state_from_host(sweep_state_to_host(s))
    assert s2.cursor == s.cursor and s2.geom == s.geom
    for a, b_ in zip(_leaves(s), _leaves(s2)):
        assert np.array_equal(a, b_)


def test_diskless_store_snapshot_and_restore(ragged_reference):
    """The orchestrator's persist hook: diskless snapshots every N
    boundaries; a successor restores the latest and finishes bitwise."""
    A, ref = ragged_reference
    store = SweepStateStore(keep=2)
    SweepOrchestrator(A, SimComm(RP), RB, store=store, persist_every=4).run()
    assert len(store) == 2
    assert store.restore().cursor is None  # final boundary also pushed
    mid = store.restore(back=1)
    assert mid.cursor is not None
    got = SweepOrchestrator.from_state(mid, SimComm(RP)).run()
    _assert_bit_identical(got, ref)


# -- slow tier: exhaustive online matrix on the aligned square sweep ---------


@pytest.mark.slow
@pytest.mark.parametrize("lane", range(4))
def test_online_kill_matrix_aligned_exhaustive(lane):
    P, m_loc, n, b = 4, 8, 16, 4
    A = _matrix(P, m_loc, n, seed=0)
    comm = SimComm(P)
    ref = caqr_factorize(A, comm, b, collect_bundles=True, use_scan=False)
    for point in iter_sweep_points(n // b, LEVELS):
        got = ft_caqr_sweep_online(
            A, comm, b, fault_hooks=[ScriptedKiller({point: [lane]})])
        for g, r in zip(_leaves(got.R, got.factors, got.bundles),
                        _leaves(ref.R, ref.factors, ref.bundles)):
            assert np.array_equal(g, r)
