"""Hypothesis property suite for elastic execution (ISSUE 7 satellite).

Strategies draw (geometry, kill sweep-point from ``ft.iter_sweep_points``,
semantics, re-grow point) and assert the three elastic invariants:

* SHRINK/BLANK/REBUILD all reproduce the failure-free R within
  ``repro.kernels.ref.tolerances`` (REBUILD bitwise, elastic sign-fixed —
  row re-hosting changes reduction shapes);
* event ledgers are consistent (one heal per kill, transition kinds match
  semantics, final world live-count is P minus unreplaced deaths);
* the scheduled-shrink differential oracle is **bitwise** identical to
  the online-detected path at the same point (shared controller code).
"""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not in this image")
from hypothesis import given, settings, strategies as st

from repro.core import SimComm
from repro.core.caqr import sweep_geometry
from repro.ft import (
    FailureSchedule,
    Semantics,
    SweepOrchestrator,
    ft_caqr_sweep,
    ft_caqr_sweep_elastic,
    iter_sweep_points,
)
from repro.ft.online.detect import ScriptedKiller
from repro.kernels.ref import tolerances
from sweep_reference import eager_sweep

_SETTINGS = dict(max_examples=15, deadline=None)

# small geometries across the shape taxonomy: aligned, ragged rows,
# ragged cols, wide; b=4 tiles (the CPU-XLA bitwise-stable envelope)
_GEOMETRIES = [
    (2, 8, 8, 4),     # aligned, tall
    (4, 4, 12, 4),    # aligned, square-ish
    (4, 6, 10, 4),    # ragged rows + cols (the acceptance geometry)
    (2, 6, 16, 4),    # ragged rows, wide
]


def _signfix(R):
    s = np.sign(np.diag(R))
    s = np.where(s == 0, 1.0, s)
    return R * s[:, None]


def _close(Ra, Rb):
    rtol, atol = tolerances(jnp.float32)
    np.testing.assert_allclose(_signfix(np.asarray(Ra)),
                               _signfix(np.asarray(Rb)),
                               rtol=rtol, atol=atol)


def _case(geom_idx, point_frac, lane_frac, seed):
    P, m_loc, n, b = _GEOMETRIES[geom_idx]
    geom = sweep_geometry(P, m_loc, n, b)
    points = list(iter_sweep_points(geom.n_panels, geom.levels))
    point = points[int(point_frac * (len(points) - 1))]
    lane = int(lane_frac * (P - 1))
    A = jnp.asarray(
        np.random.default_rng(seed).standard_normal((P, m_loc, n)),
        jnp.float32)
    ref = eager_sweep(A, SimComm(P), b)
    return P, b, A, np.asarray(ref.R[0]), point, lane, points


@settings(**_SETTINGS)
@given(
    geom_idx=st.integers(0, len(_GEOMETRIES) - 1),
    point_frac=st.floats(0, 1),
    lane_frac=st.floats(0, 1),
    seed=st.integers(0, 2**16),
    semantics=st.sampled_from(
        [Semantics.SHRINK, Semantics.BLANK, Semantics.REBUILD]),
)
def test_any_semantics_reproduces_r(geom_idx, point_frac, lane_frac, seed,
                                    semantics):
    P, b, A, R_ref, point, lane, _ = _case(geom_idx, point_frac, lane_frac,
                                           seed)
    sched = FailureSchedule(events={point: [lane]})
    res = ft_caqr_sweep(A, SimComm(P), b, schedule=sched,
                        semantics=semantics)
    if semantics is Semantics.REBUILD:
        # the paper's guarantee is stronger: bitwise, replicated layout
        assert np.array_equal(np.asarray(res.R[0]), R_ref)
    else:
        _close(res.R, R_ref)
        assert res.world.n_live == P - 1
        kinds = [t.kind for t in res.transitions]
        assert kinds == [semantics.value]
    # ledger consistency: exactly one heal, at the drawn point and lane
    assert [(e.point, e.lane) for e in res.events] == [(tuple(point), lane)]


@settings(**_SETTINGS)
@given(
    geom_idx=st.integers(0, len(_GEOMETRIES) - 1),
    point_frac=st.floats(0, 1),
    lane_frac=st.floats(0, 1),
    seed=st.integers(0, 2**16),
)
def test_scheduled_oracle_bitwise_vs_online(geom_idx, point_frac, lane_frac,
                                            seed):
    P, b, A, _, point, lane, _ = _case(geom_idx, point_frac, lane_frac, seed)
    sched = FailureSchedule(events={point: [lane]})
    oracle = ft_caqr_sweep_elastic(A, SimComm(P), b, schedule=sched,
                                   semantics=Semantics.SHRINK)
    online = SweepOrchestrator(
        A, SimComm(P), b, fault_hooks=[ScriptedKiller({point: [lane]})],
        semantics=Semantics.SHRINK,
    ).run()
    assert np.array_equal(np.asarray(oracle.R), np.asarray(online.R))
    assert [(e.point, e.lane) for e in online.events] == \
        [(e.point, e.lane) for e in oracle.events]
    assert online.transitions == oracle.transitions
    assert online.world == oracle.world


@settings(**_SETTINGS)
@given(
    geom_idx=st.integers(0, len(_GEOMETRIES) - 1),
    point_frac=st.floats(0, 1),
    grow_frac=st.floats(0, 1),
    seed=st.integers(0, 2**16),
)
def test_regrow_reproduces_r(geom_idx, point_frac, grow_frac, seed):
    """Kill + re-grow at a drawn later point still reproduces R, and the
    returning lane restores the live count when the grow fires before the
    sweep ends."""
    P, b, A, R_ref, point, _, points = _case(geom_idx, point_frac, 0.99,
                                             seed)
    grow_at = points[int(grow_frac * (len(points) - 1))]
    sched = FailureSchedule(events={point: [P - 1]})
    res = ft_caqr_sweep_elastic(A, SimComm(P), b, schedule=sched,
                                semantics=Semantics.SHRINK, grow_at=grow_at)
    _close(res.R, R_ref)
    kinds = [t.kind for t in res.transitions]
    assert set(kinds) <= {"shrink", "grow"}
    # the drawn kill point addresses the running epoch: repeated grows can
    # re-partition epochs so the point never comes up — a kill fired
    # (events non-empty) always yields exactly one shrink transition
    if res.events:
        assert kinds.count("shrink") == 1
    if kinds and kinds[-1] == "grow":
        assert res.world.n_live == \
            res.transitions[-1].world_before.n_live + 1
