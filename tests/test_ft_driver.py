"""End-to-end FT sweep driver: the paper's headline claim as a regression.

A lane dies at any panel, at any TSQR or trailing-combine tree level, is
respawned and rebuilt from its re-read initial slice plus single-source
buddy fetches — and the finished factorization (R, per-panel factors, AND
recovery bundles) is bit-identical to the failure-free windowed sweep.
Death is simulated by NaN-poisoning everything the lane holds, so any read
of dead state fails the bit-identity oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SimComm, sweep_geometry
from repro.ft import (
    FailureSchedule,
    UnrecoverableFailure,
    ft_caqr_sweep,
    iter_sweep_points,
    sweep_point,
)
from sweep_reference import eager_sweep

# square case: the sweep crosses row-ownership boundaries, so the kill
# matrix covers target-lane rotation and consumed (inactive) lanes too
P, M_LOC, N, B = 4, 8, 16, 4
N_PANELS, LEVELS = N // B, 2


def _matrix(P_=P, m_loc=M_LOC, n=N, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P_, m_loc, n)), jnp.float32)


def _leaves(*trees):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(trees)]


def _assert_bit_identical(got, ref):
    for g, r in zip(
        _leaves(got.R, got.factors, got.bundles),
        _leaves(ref.R, ref.factors, ref.bundles),
    ):
        assert np.array_equal(g, r), "driver output differs from failure-free sweep"


@pytest.fixture(scope="module")
def reference():
    A = _matrix()
    ref = eager_sweep(A, SimComm(P), B)
    return A, ref


def _all_points(n_panels=N_PANELS, levels=LEVELS):
    return list(iter_sweep_points(n_panels, levels))


def test_failure_free_driver_matches_windowed_sweep(reference):
    """With no schedule, the level-stepped driver IS the windowed sweep."""
    A, ref = reference
    got = ft_caqr_sweep(A, SimComm(P), B)
    _assert_bit_identical(got, ref)
    assert got.events == []


@pytest.mark.parametrize("lane", range(P))
@pytest.mark.parametrize(
    "point",
    _all_points(),
    ids=lambda p: f"p{p[0]}-{p[1]}{p[2]}",
)
def test_kill_matrix_single_source_rebuild(reference, point, lane):
    """Every lane x every phase/level x every panel: kill, rebuild from
    single-source buddy fetches, finish — bit-identical to failure-free."""
    A, ref = reference
    sched = FailureSchedule(events={point: [lane]})
    got = ft_caqr_sweep(A, SimComm(P), B, schedule=sched)
    _assert_bit_identical(got, ref)
    (event,) = got.events
    assert event.point == point and event.lane == lane
    # the single-source ledger: every artifact came from exactly one
    # surviving lane, never the failed one
    assert all(src != lane for src in event.reads.values())
    assert all(0 <= src < P for src in event.reads.values())
    # mid-tree deaths must actually fetch something
    if point[1] != "leaf" or point[0] > 0:
        assert event.reads, f"no fetches recorded for {point}"


def test_two_failures_in_different_panels(reference):
    A, ref = reference
    sched = FailureSchedule(events={
        sweep_point(0, "trailing", 1): [2],
        sweep_point(2, "tsqr", 0): [1],
    })
    got = ft_caqr_sweep(A, SimComm(P), B, schedule=sched)
    _assert_bit_identical(got, ref)
    assert [(e.point, e.lane) for e in got.events] == [
        ((0, "trailing", 1), 2), ((2, "tsqr", 0), 1),
    ]


def test_same_lane_dies_twice(reference):
    """A lane can die, be rebuilt, and die again panels later — the second
    REBUILD replays through state that itself contains recovered data."""
    A, ref = reference
    sched = FailureSchedule(events={
        sweep_point(0, "trailing", 0): [1],
        sweep_point(3, "trailing", 1): [1],
    })
    got = ft_caqr_sweep(A, SimComm(P), B, schedule=sched)
    _assert_bit_identical(got, ref)
    assert len(got.events) == 2


def test_simultaneous_non_buddy_deaths_recover(reference):
    A, ref = reference
    sched = FailureSchedule(events={sweep_point(1, "trailing", 0): [0, 3]})
    got = ft_caqr_sweep(A, SimComm(P), B, schedule=sched)
    _assert_bit_identical(got, ref)
    assert len(got.events) == 2


def test_buddy_pair_death_is_unrecoverable():
    """Both members of a level-0 pair die at once: the single source that
    holds the needed bundle is dead — the driver must say so, not fabricate."""
    A = _matrix()
    sched = FailureSchedule(events={sweep_point(1, "trailing", 0): [2, 3]})
    with pytest.raises(UnrecoverableFailure):
        ft_caqr_sweep(A, SimComm(P), B, schedule=sched)


def test_recovery_sources_are_tree_buddies(reference):
    """The ledger's sources are exactly the XOR-buddies the paper names:
    lane^1 for the TSQR ladder, lane^(1<<s) for level-s trailing state."""
    A, ref = reference
    lane, lvl = 2, 1
    sched = FailureSchedule(events={sweep_point(1, "trailing", lvl): [lane]})
    got = ft_caqr_sweep(A, SimComm(P), B, schedule=sched)
    _assert_bit_identical(got, ref)
    (event,) = got.events
    assert event.reads["tsqr.ladder"] == lane ^ 1
    assert event.reads[f"trailing.cprime@level{lvl}"] == lane ^ (1 << lvl)
    for s in range(lvl + 1):
        assert event.reads[f"trailing.bundle@level{s}"] == lane ^ (1 << s)
    # panel 0 is complete: its final C' came from the last-level buddy
    assert event.reads["panel0.cprime_final"] == lane ^ (1 << (LEVELS - 1))


@pytest.mark.parametrize("lane", [0, 3, 5, 7])
@pytest.mark.parametrize("point", [
    sweep_point(0, "trailing", 2),
    sweep_point(3, "tsqr", 2),
    sweep_point(7, "trailing", 1),
    sweep_point(5, "leaf"),
], ids=lambda p: f"p{p[0]}-{p[1]}{p[2]}")
def test_kill_matrix_p8_spot(point, lane):
    """Three-level tree (P=8), square sweep: deeper-buddy recovery paths."""
    P8, m8, n8, b8 = 8, 8, 32, 4
    A = _matrix(P8, m8, n8, seed=1)
    comm = SimComm(P8)
    ref = eager_sweep(A, comm, b8)
    got = ft_caqr_sweep(A, comm, b8, schedule=FailureSchedule(events={point: [lane]}))
    _assert_bit_identical(got, ref)


# -- ragged geometry: the general-shape sweep under the same kill matrix ----
#
# P=4, m_loc=6, n=10, b=4: unaligned lane heights AND a ragged last panel —
# the padded sweep_geometry runs at (8, 12) with 3 panels, and every REBUILD
# (including re-reading the respawned lane's *padded* initial slice) must
# reproduce the failure-free general-shape sweep bit for bit.
RP, RM_LOC, RN, RB = 4, 6, 10, 4
RGEOM = sweep_geometry(RP, RM_LOC, RN, RB)
assert (RGEOM.m_loc_pad, RGEOM.n_work, RGEOM.n_panels) == (8, 12, 3)


@pytest.fixture(scope="module")
def ragged_reference():
    A = _matrix(RP, RM_LOC, RN, seed=3)
    ref = eager_sweep(A, SimComm(RP), RB)
    return A, ref


def test_failure_free_ragged_driver_matches_sweep(ragged_reference):
    A, ref = ragged_reference
    got = ft_caqr_sweep(A, SimComm(RP), RB)
    _assert_bit_identical(got, ref)
    assert got.events == []
    assert got.R.shape == (RP, RGEOM.k, RN)


@pytest.mark.parametrize("lane", [0, 1, 3])
@pytest.mark.parametrize("point", [
    sweep_point(0, "leaf"),
    sweep_point(0, "trailing", 1),
    sweep_point(1, "tsqr", 0),
    sweep_point(2, "trailing", 0),   # ragged last panel, mid-trailing
    sweep_point(2, "tsqr", 1),       # ragged last panel, deep butterfly
], ids=lambda p: f"p{p[0]}-{p[1]}{p[2]}")
def test_kill_matrix_ragged_spot(ragged_reference, point, lane):
    """Ragged-geometry spot kills (tier-1): single-source REBUILD over
    padded panels, bit-identical to the failure-free general-shape sweep."""
    A, ref = ragged_reference
    sched = FailureSchedule(events={point: [lane]})
    got = ft_caqr_sweep(A, SimComm(RP), RB, schedule=sched)
    _assert_bit_identical(got, ref)
    (event,) = got.events
    assert event.point == point and event.lane == lane
    assert all(src != lane for src in event.reads.values())


def test_kill_matrix_wide_spot():
    """Wide geometry (n > P*m_loc): the trailing-only R2 columns survive a
    mid-sweep death and REBUILD bit-identically too."""
    Pw, mw, nw, bw = 4, 4, 24, 4
    A = _matrix(Pw, mw, nw, seed=4)
    comm = SimComm(Pw)
    ref = eager_sweep(A, comm, bw)
    sched = FailureSchedule(events={sweep_point(2, "trailing", 1): [2]})
    got = ft_caqr_sweep(A, comm, bw, schedule=sched)
    _assert_bit_identical(got, ref)
    assert got.R.shape == (Pw, Pw * mw, nw)  # [R1 R2]


@pytest.mark.slow
@pytest.mark.parametrize("lane", range(RP))
def test_kill_matrix_ragged_exhaustive(ragged_reference, lane):
    """Every lane x every phase/level x every (padded) panel of the ragged
    geometry (slow tier)."""
    A, ref = ragged_reference
    for pt in iter_sweep_points(RGEOM.n_panels, LEVELS):
        got = ft_caqr_sweep(
            A, SimComm(RP), RB, schedule=FailureSchedule(events={pt: [lane]})
        )
        _assert_bit_identical(got, ref)


@pytest.mark.slow
@pytest.mark.parametrize("lane", range(8))
def test_kill_matrix_p8_exhaustive(lane):
    """Full 3-level kill matrix on the tall P=8 case (slow tier)."""
    P8, m8, n8, b8 = 8, 16, 32, 4
    A = _matrix(P8, m8, n8, seed=2)
    comm = SimComm(P8)
    ref = eager_sweep(A, comm, b8)
    for k in range(n8 // b8):
        for pt in (
            [sweep_point(k, "leaf")]
            + [sweep_point(k, ph, s) for s in range(3) for ph in ("tsqr", "trailing")]
        ):
            got = ft_caqr_sweep(
                A, comm, b8, schedule=FailureSchedule(events={pt: [lane]})
            )
            _assert_bit_identical(got, ref)
