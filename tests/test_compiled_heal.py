"""The online orchestrator's SimComm heal as one compiled program.

``compiled_heal`` traces ``recover_lanes`` whole into one cached program
(``SimHeal``). It must be the eager REBUILD run compiled: the same bits on
every leaf of the healed state, the same read ledger, the same
``UnrecoverableFailure``. It is built once per (lanes, point) and reused by
every later orchestrator, and a fresh program traces whatever
``recover_lanes`` the orchestrator module holds at that time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SimComm
from repro.ft import UnrecoverableFailure, ft_caqr_sweep_online, sweep_point
from repro.ft.coding import MDSScheme, XORPairScheme
from repro.ft.driver import obliterate_state, recover_lanes
from repro.ft.online import orchestrator
from repro.ft.online.detect import ScriptedKiller
from repro.ft.online.state import initial_sweep_state

# 8 lanes (3 tree levels), 3 panels: a first, a middle and a last panel
P, M_LOC, N, B = 8, 4, 12, 4


def _matrix(seed=11):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, M_LOC, N)), jnp.float32)


@functools.lru_cache(maxsize=None)
def _boundary(point, scheme):
    """The sweep's state at the boundary after ``point``, as the
    orchestrator holds it before its fault hooks run."""
    comm = SimComm(P)
    step = orchestrator.compiled_segment(comm, 1)
    s = initial_sweep_state(comm, _matrix(), B)
    while True:
        done = s.cursor
        s = step(s)
        if done == point:
            return scheme.refresh(comm, s)


def _struck(point, lanes, scheme):
    s = _boundary(point, scheme)
    for lane in lanes:
        s = obliterate_state(SimComm(P), s, lane)
    return s


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint8) if x.dtype.kind == "f" else x


HEALS = [(sweep_point(panel, phase, level), [3], XORPairScheme())
         for panel in (0, 1)
         for phase, level in (("tsqr", 0), ("tsqr", 2),
                              ("trailing", 0), ("trailing", 2))]
HEALS.append((sweep_point(1, "trailing", 1), [2, 5], MDSScheme(f=2)))


@pytest.mark.parametrize(
    "point,lanes,scheme", HEALS + [
        # lane 2's XOR buddy (3) dies with it: no live source
        (sweep_point(1, "trailing", 0), [2, 3], XORPairScheme())],
    ids=[f"{p[0]}-{p[1]}{p[2]}-lanes{'_'.join(map(str, l))}-{s.name}"
         for p, l, s in HEALS] + ["buddy-dead-raises"])
def test_compiled_heal_is_the_eager_heal(point, lanes, scheme):
    """The compiled heal against eager ``recover_lanes`` on the same
    poisoned state: every leaf bit for bit (NaN payloads included), the
    same read ledger, the same lanes revived; or the same
    ``UnrecoverableFailure``."""
    comm = SimComm(P)
    state = _struck(point, lanes, scheme)
    heal = orchestrator.compiled_heal(comm, lanes, point, scheme)
    eager_revived, compiled_revived = [], []
    try:
        want, want_events = recover_lanes(
            comm, state, lanes, point, set(lanes),
            on_recovered=eager_revived.append, scheme=scheme)
    except UnrecoverableFailure:
        with pytest.raises(UnrecoverableFailure):
            heal(state, lanes, point, set(lanes), scheme=scheme)
        return
    got, events = heal(state, lanes, point, set(lanes),
                       on_recovered=compiled_revived.append, scheme=scheme)
    assert heal._cache_size() == 1
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(_bits(g), _bits(w))
    assert [(e.point, e.lane, e.reads) for e in events] == \
        [(e.point, e.lane, e.reads) for e in want_events]
    assert compiled_revived == eager_revived == sorted(lanes)
    if not scheme.joint:
        # the source matrix comes back as the caller's own array, not a
        # copy (the joint decode is a jitted program of its own, whose
        # outputs are all fresh arrays, eager or compiled)
        assert got.A0 is state.A0


@pytest.fixture
def no_programs():
    orchestrator._SEGMENT_CACHE.clear()
    yield
    orchestrator._SEGMENT_CACHE.clear()


def _heal_spans(monkeypatch):
    """Record the ``ftqr.heal`` spans' arguments."""
    spans = []
    real = orchestrator.TraceAnnotation

    def record(name, **kw):
        if name == "ftqr.heal":
            spans.append(kw)
        return real(name, **kw)

    monkeypatch.setattr(orchestrator, "TraceAnnotation", record)
    return spans


POINT, LANE = sweep_point(1, "trailing", 0), 3


def _killed_run(A):
    return ft_caqr_sweep_online(
        A, SimComm(P), B, fault_hooks=[ScriptedKiller({POINT: [LANE]})])


def test_heal_program_built_once_and_reused(no_programs, monkeypatch):
    """Two factorizations with the same kill build the heal once and reuse
    it (``program=built`` then ``cached``), with the same events, reads
    and R; clearing ``_SEGMENT_CACHE`` drops the program."""
    spans = _heal_spans(monkeypatch)
    A = _matrix()
    first, second = _killed_run(A), _killed_run(A)
    heal = orchestrator.compiled_heal(SimComm(P), [LANE], POINT,
                                      XORPairScheme())
    assert heal._cache_size() == 1
    assert [s["program"] for s in spans] == ["built", "cached"]
    assert [(e.point, e.lane, e.reads) for e in first.events] == \
        [(e.point, e.lane, e.reads) for e in second.events]
    assert [e.lane for e in first.events] == [LANE]
    assert np.array_equal(first.R, second.R)
    orchestrator._SEGMENT_CACHE.clear()
    assert orchestrator.compiled_heal(SimComm(P), [LANE], POINT,
                                      XORPairScheme())._cache_size() == 0


def test_fresh_heal_traces_the_patched_recover_lanes(no_programs,
                                                     monkeypatch):
    """A ``recover_lanes`` patched into the orchestrator module after the
    cache is cleared is what the next heal program runs: a fault planted in
    the REBUILD reaches the answer."""
    A = _matrix()
    sound = _killed_run(A)
    orchestrator._SEGMENT_CACHE.clear()
    spans = _heal_spans(monkeypatch)
    orig = orchestrator.recover_lanes

    def altered(comm, state, *a, **kw):
        state, events = orig(comm, state, *a, **kw)
        return state.replace(
            C_prime=state.C_prime.at[LANE, -1, -1].add(1e-3)), events

    monkeypatch.setattr(orchestrator, "recover_lanes", altered)
    broken = _killed_run(A)
    assert [s["program"] for s in spans] == ["built"]
    assert not np.array_equal(broken.R, sound.R)


def test_heal_programs_kept_are_bounded(no_programs, monkeypatch):
    """Past ``_HEAL_PROGRAMS`` the least recently used heal program is
    dropped; one used again moves to the back."""
    monkeypatch.setattr(orchestrator, "_HEAL_PROGRAMS", 2)
    comm, xor = SimComm(P), XORPairScheme()
    a = orchestrator.compiled_heal(comm, [1], POINT, xor)
    orchestrator.compiled_heal(comm, [2], POINT, xor)
    assert orchestrator.compiled_heal(comm, [1], POINT, xor) is a
    orchestrator.compiled_heal(comm, [4], POINT, xor)
    kept = [k[3] for k in orchestrator._SEGMENT_CACHE if k[2] == "heal"]
    assert kept == [(1,), (4,)]
