"""Elastic mid-sweep execution (``repro.ft.elastic``): SHRINK/BLANK
continuation, re-grow, speculative straggler recompute.

Oracle structure (DESIGN.md §11):

* vs the failure-free run — row re-hosting changes the reduction shapes,
  so elastic R matches within ``kernels.ref.tolerances`` after sign
  fixing (each epoch's TSQR may flip R-row signs);
* scheduled elastic vs online-detected elastic — shared
  ``ElasticController`` code, so **bitwise**;
* the acceptance matrix: mid-sweep SHRINK at *every* sweep point on the
  ragged P=4, m_loc=6, n=10, b=4 geometry finishes on 3 live lanes.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import SimComm
from repro.core.caqr import sweep_geometry
from repro.core.tsqr import _levels, _xor_perm
from repro.ft.coding import pairing_table, xor_buddy
from repro.ft import (
    FailureSchedule,
    Semantics,
    StragglerConfig,
    StragglerMonitor,
    StragglerPolicy,
    SweepOrchestrator,
    ft_caqr_sweep,
    ft_caqr_sweep_elastic,
    iter_sweep_points,
)
from repro.ft.elastic import (
    LaneWorld,
    ceil_pow2,
    floor_pow2,
    harvest_trailing,
    plan_transition,
)
from repro.ft.failures import UnrecoverableFailure
from repro.ft.online.detect import ScriptedKiller
from sweep_reference import eager_sweep


def signfix(R):
    s = np.sign(np.diag(R))
    s = np.where(s == 0, 1.0, s)
    return R * s[:, None]


# the acceptance geometry: ragged rows (m_loc=6 pads to 8) and ragged
# columns (n=10 pads to 12), 3 panels, 2 butterfly levels
RP, RM_LOC, RN, RB = 4, 6, 10, 4
RGEOM = sweep_geometry(RP, RM_LOC, RN, RB)
R_POINTS = list(iter_sweep_points(RGEOM.n_panels, RGEOM.levels))


def _matrix(P, m_loc, n, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)


@pytest.fixture(scope="module")
def ragged_reference():
    A = _matrix(RP, RM_LOC, RN)
    ref = eager_sweep(A, SimComm(RP), RB)
    return A, np.asarray(ref.R[0])


def _assert_close(R_elastic, R_ref):
    from repro.kernels import ref as kref

    rtol, atol = kref.tolerances(jnp.float32)
    np.testing.assert_allclose(
        signfix(np.asarray(R_elastic)), signfix(np.asarray(R_ref)),
        rtol=rtol, atol=atol)


# -- the acceptance matrix: SHRINK at every sweep point ----------------------


@pytest.mark.parametrize("point", R_POINTS, ids=[str(p) for p in R_POINTS])
@pytest.mark.parametrize("lane", [0, 1, 3])
def test_shrink_every_point_ragged(ragged_reference, point, lane):
    A, R_ref = ragged_reference
    sched = FailureSchedule(events={point: [lane]})
    res = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                semantics=Semantics.SHRINK)
    _assert_close(res.R, R_ref)
    # the world finished without the dead lane: 3 live lanes
    assert res.world.n_live == RP - 1
    assert [e.lane for e in res.events] == [lane]
    assert [t.kind for t in res.transitions] == ["shrink"]
    assert res.transitions[0].lanes == (lane,)


@pytest.mark.parametrize("point", R_POINTS[1::4], ids=str)
def test_blank_keeps_hole(ragged_reference, point):
    A, R_ref = ragged_reference
    sched = FailureSchedule(events={point: [2]})
    res = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                semantics=Semantics.BLANK)
    _assert_close(res.R, R_ref)
    (t,) = res.transitions
    assert t.kind == "blank"
    # BLANK keeps the world size; the hole is a masked no-op lane
    assert res.world.n_slots == RP
    assert res.world.live == (True, True, False, True)
    # the designated adopter is the XOR level-0 buddy
    assert t.adopter == xor_buddy(2, 0) == 3


# -- online path: bitwise vs the scheduled oracle ----------------------------


@pytest.mark.parametrize("point", R_POINTS, ids=[str(p) for p in R_POINTS])
@pytest.mark.parametrize("semantics", [Semantics.SHRINK, Semantics.BLANK],
                         ids=["shrink", "blank"])
def test_online_bitwise_vs_scheduled_oracle(ragged_reference, point,
                                            semantics):
    A, _ = ragged_reference
    sched = FailureSchedule(events={point: [1]})
    oracle = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                   semantics=semantics)
    online = SweepOrchestrator(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller({point: [1]})],
        semantics=semantics,
    ).run()
    assert np.array_equal(np.asarray(oracle.R), np.asarray(online.R))
    assert len(online.events) == len(oracle.events) == 1
    assert online.events[0].point == oracle.events[0].point == tuple(point)
    assert [t.kind for t in online.transitions] == \
        [t.kind for t in oracle.transitions]
    assert online.world == oracle.world


def test_driver_semantics_delegation(ragged_reference):
    """``ft_caqr_sweep(semantics=SHRINK)`` routes to the elastic driver."""
    A, R_ref = ragged_reference
    sched = FailureSchedule(events={R_POINTS[4]: [3]})
    res = ft_caqr_sweep(A, SimComm(RP), RB, schedule=sched,
                        semantics=Semantics.SHRINK)
    _assert_close(res.R, R_ref)
    assert res.world.n_live == RP - 1


def test_failure_free_elastic_is_exact(ragged_reference):
    """No deaths -> one epoch, R exactly equal to the failure-free run."""
    A, R_ref = ragged_reference
    res = ft_caqr_sweep_elastic(A, SimComm(RP), RB,
                                semantics=Semantics.SHRINK)
    assert np.array_equal(np.asarray(res.R), R_ref)
    assert res.transitions == [] and res.events == []
    assert res.world.n_live == RP


# -- grow --------------------------------------------------------------------


def test_grow_rejoins_after_shrink(ragged_reference):
    A, R_ref = ragged_reference
    sched = FailureSchedule(events={R_POINTS[3]: [1]})
    res = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                semantics=Semantics.SHRINK,
                                grow_at=(1, "trailing", 1))
    _assert_close(res.R, R_ref)
    assert [t.kind for t in res.transitions] == ["shrink", "grow"]
    # the returning lane restores the live count
    assert res.world.n_live == RP
    # grow re-enters the pairing of the restored world size implicitly
    assert res.world.n_slots == ceil_pow2(res.world.n_live)


def test_grow_online_matches_scheduled(ragged_reference):
    A, _ = ragged_reference
    point, grow_pt = R_POINTS[2], (1, "trailing", 0)
    sched = FailureSchedule(events={point: [2]})
    oracle = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                   semantics=Semantics.SHRINK,
                                   grow_at=grow_pt)
    online = SweepOrchestrator(
        A, SimComm(RP), RB, fault_hooks=[ScriptedKiller({point: [2]})],
        semantics=Semantics.SHRINK, grow_at=grow_pt,
    ).run()
    assert np.array_equal(np.asarray(oracle.R), np.asarray(online.R))
    assert [t.kind for t in online.transitions] == ["shrink", "grow"]


# -- multiple deaths / edge worlds -------------------------------------------


def test_two_deaths_different_panels(ragged_reference):
    A, R_ref = ragged_reference
    sched = FailureSchedule(events={R_POINTS[1]: [3], R_POINTS[7]: [0]})
    res = ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                                semantics=Semantics.SHRINK)
    _assert_close(res.R, R_ref)
    # second kill addresses the *new* world's numbering (epoch restart)
    assert len(res.transitions) == 2
    assert res.world.n_live == 2


def test_buddy_pair_death_still_unrecoverable(ragged_reference):
    """Both members of an XOR pair dying at one point loses the bundle
    sources — elastic semantics cannot save that either."""
    A, _ = ragged_reference
    sched = FailureSchedule(events={R_POINTS[2]: [2, 3]})
    with pytest.raises(UnrecoverableFailure):
        ft_caqr_sweep_elastic(A, SimComm(RP), RB, schedule=sched,
                              semantics=Semantics.SHRINK)


def test_shrink_aligned_and_wide_shapes():
    for P, m_loc, n, b in [(4, 8, 32, 4), (2, 8, 8, 4), (4, 4, 24, 4)]:
        A = _matrix(P, m_loc, n, seed=11)
        ref = eager_sweep(A, SimComm(P), b)
        geom = sweep_geometry(P, m_loc, n, b)
        pts = list(iter_sweep_points(geom.n_panels, geom.levels))
        sched = FailureSchedule(events={pts[len(pts) // 2]: [1]})
        res = ft_caqr_sweep_elastic(A, SimComm(P), b, schedule=sched,
                                    semantics=Semantics.SHRINK)
        _assert_close(res.R, np.asarray(ref.R[0]))


# -- stragglers --------------------------------------------------------------


def _slow_lane_clock(slow):
    def clock(comm, state):
        P = comm.axis_size()
        return {i: (8.0 if i == slow and i < P else 1.0) for i in range(P)}

    return clock


def test_speculative_recompute_bitwise():
    """A persistently slow lane triggers speculative buddy recompute;
    the race winner is bitwise-identical to a blocking run — R and the
    full event ledger stay exactly the failure-free result."""
    P, m_loc, n, b = 4, 8, 32, 4
    A = _matrix(P, m_loc, n, seed=0)
    ref = eager_sweep(A, SimComm(P), b)
    mon = StragglerMonitor(P, StragglerConfig(
        threshold=1.4, patience=2, policy=StragglerPolicy.SPECULATE))
    orch = SweepOrchestrator(A, SimComm(P), b, straggler_monitor=mon,
                             lane_clock=_slow_lane_clock(2))
    res = orch.run()
    assert np.array_equal(np.asarray(res.R), np.asarray(ref.R))
    assert orch.speculations, "slow lane never triggered speculation"
    assert all(s.matched for s in orch.speculations)
    assert all(s.lane == 2 for s in orch.speculations)
    assert all(s.reads for s in orch.speculations)
    assert res.events == []  # speculation is not a death


def test_evict_escalates_to_shrink():
    P, m_loc, n, b = 4, 8, 32, 4
    A = _matrix(P, m_loc, n, seed=0)
    ref = eager_sweep(A, SimComm(P), b)
    mon = StragglerMonitor(P, StragglerConfig(
        threshold=1.4, patience=2, policy=StragglerPolicy.EVICT))

    def clock(comm, state):
        # only the first epoch's lane 2 is slow (evicted once); the
        # post-transition epoch has a wider m_loc_pad (adopted rows)
        P_now = comm.axis_size()
        slow = 2 if state.geom.m_loc == m_loc else -1
        return {i: (8.0 if i == slow else 1.0) for i in range(P_now)}

    orch = SweepOrchestrator(A, SimComm(P), b, straggler_monitor=mon,
                             lane_clock=clock)
    res = orch.run()
    _assert_close(res.R, np.asarray(ref.R[0]))
    assert [t.kind for t in res.transitions] == ["shrink"]
    assert res.world.n_live == P - 1


def test_speculate_escalate_after():
    P, m_loc, n, b = 4, 8, 32, 4
    A = _matrix(P, m_loc, n, seed=0)
    ref = eager_sweep(A, SimComm(P), b)
    mon = StragglerMonitor(P, StragglerConfig(
        threshold=1.4, patience=2, policy=StragglerPolicy.SPECULATE,
        escalate_after=2))

    def clock(comm, state):
        P_now = comm.axis_size()
        slow = 1 if state.geom.m_loc == m_loc else -1
        return {i: (8.0 if i == slow else 1.0) for i in range(P_now)}

    orch = SweepOrchestrator(A, SimComm(P), b, straggler_monitor=mon,
                             lane_clock=clock)
    res = orch.run()
    _assert_close(res.R, np.asarray(ref.R[0]))
    assert len(orch.speculations) >= 2
    assert [t.kind for t in res.transitions] == ["shrink"]


# -- plan / pairing unit coverage --------------------------------------------


def test_pairing_table_matches_butterfly():
    for P in (2, 4, 8, 16):
        table = pairing_table(P)
        assert len(table) == _levels(P)
        for s, perm in enumerate(table):
            assert perm == _xor_perm(P, s)
            assert all(dst == xor_buddy(src, s) for src, dst in perm)


def test_plan_shrink_pad_appends_to_buddy():
    world = LaneWorld(n_slots=4, live=(True,) * 4)
    sources, after, adopter = plan_transition(world, "shrink", (2,),
                                              policy="pad")
    assert adopter == 3  # xor level-0 buddy of 2
    # survivors [0,1,3] renumber compactly; the dead lane's rows are
    # appended to its adopter's slice; slot 3 is a zero-row ghost
    assert sources == [[0], [1], [3, 2], []]
    assert after.n_slots == 4 and after.live == (True, True, True, False)


def test_plan_shrink_fold_resplits():
    world = LaneWorld(n_slots=4, live=(True,) * 4)
    sources, after, _ = plan_transition(world, "shrink", (0,), policy="fold")
    assert after.n_slots == floor_pow2(3) == 2
    assert after.live == (True, True)
    assert sorted(x for src in sources for x in src) == [0, 1, 2, 3]


def test_plan_blank_keeps_hole():
    world = LaneWorld(n_slots=4, live=(True,) * 4)
    sources, after, adopter = plan_transition(world, "blank", (1,))
    assert adopter == 0
    assert sources == [[0, 1], [], [2], [3]]
    assert after.live == (True, False, True, True)


def test_harvest_covers_all_padded_rows(ragged_reference):
    """Every unconsumed padded row rides the harvest (pad rows can carry
    trailing-matrix content when m_loc < m_loc_pad) — coverage check of
    the frontier arithmetic."""
    from repro.ft.online.state import (
        deposit_boundary,
        initial_sweep_state,
        run_steps,
    )

    A, _ = ragged_reference
    comm = SimComm(RP)
    state = run_steps(comm, initial_sweep_state(comm, A, RB),
                      1 + 2 * RGEOM.levels)  # one whole panel -> (1, leaf)
    state, r = deposit_boundary(comm, state)
    assert r == 1
    blocks, n_cols = harvest_trailing(state, r)
    assert n_cols == RN - RB
    cut = r * RB
    for i, blk in enumerate(blocks):
        consumed = min(max(cut - i * RGEOM.m_loc_pad, 0), RGEOM.m_loc_pad)
        assert blk.shape == (RGEOM.m_loc_pad - consumed, n_cols)
