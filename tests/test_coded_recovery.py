"""Coded checksum lanes (``repro.ft.coding``): survive ANY f simultaneous
failures, proven exhaustively.

The XOR-buddy redundancy recovers any single death but walls at a
buddy-pair double kill (``test_online_recovery.py`` pins that wall). The
MDS scheme removes it: ``f`` Vandermonde parity slots over GF(2^8) on the
raw bytes of the protected state let the boundary decode reconstruct any
``t <= f`` simultaneously-dead lanes jointly — bit-exactly, because GF
arithmetic on bit patterns is exact. The proof here is the exhaustive
multi-failure matrix: at P=8 EVERY lane pair (all 28, including every
former XOR-buddy pair) is killed at EVERY sweep point of the 14-point
enumeration under ``MDSScheme(f=2)``, and the finished factorization must
be bitwise-identical to the failure-free run, with the multi-source
decode ledger recorded per death. P=16 runs a spot tier inline and the
full 120-pair matrix under ``-m slow``.

Also gated here: the f=1 degeneration (``MDSScheme(f=1)`` routes single
deaths through the XOR path, so ledger and bits are IDENTICAL to
``XORPairScheme`` — the differential gate), the f+1 boundary
(``UnrecoverableFailure`` names the scheme's tolerance), the
monotonically-stronger property (t > f falls back to the per-lane XOR
loop, so nothing the old scheme recovered is lost), the shard_map leg,
and a property suite over random (P, f, kill set, sweep point) draws —
hypothesis-driven when available, a seeded deterministic grid otherwise
(this image has no hypothesis).
"""
import itertools
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SimComm, sweep_geometry
from repro.ft.online.state import initial_sweep_state, sweep_step
from repro.ft import (
    FailureSchedule,
    MDSScheme,
    UnrecoverableFailure,
    XORPairScheme,
    ft_caqr_sweep,
    ft_caqr_sweep_online,
    iter_sweep_points,
    sweep_point,
)
from repro.ft.coding import (
    GF_EXP,
    GF_LOG,
    generator,
    gf_inv,
    gf_inv_matrix,
    gf_mul,
    pairing_table,
    xor_buddy,
)
from repro.ft.driver import obliterate_state
from repro.ft.online.detect import ScriptedKiller

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the CI image ships without hypothesis
    HAVE_HYPOTHESIS = False

sys.path.insert(0, os.path.dirname(__file__))
from spmd_subprocess_util import run_forced_devices  # noqa: E402

# P=8 kill-matrix geometry: 2 panels x 7 points, 3 tree levels — every
# phase class (leaf, 3 tsqr ladder levels, 3 trailing levels) appears
P8, M8, N8, B8 = 8, 4, 8, 4
G8 = sweep_geometry(P8, M8, N8, B8)
POINTS8 = list(iter_sweep_points(G8.n_panels, G8.levels))
PAIRS8 = list(itertools.combinations(range(P8), 2))
BUDDY_PAIRS8 = sorted({tuple(sorted(p)) for lvl in pairing_table(P8)
                       for p in lvl})

P16, M16, N16, B16 = 16, 4, 8, 4
G16 = sweep_geometry(P16, M16, N16, B16)
POINTS16 = list(iter_sweep_points(G16.n_panels, G16.levels))
PAIRS16 = list(itertools.combinations(range(P16), 2))


def _matrix(P, m_loc, n, seed=7):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)


def _leaves(*trees):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(trees)]


def _assert_bitwise(got, ref, tag=""):
    for g, r in zip(_leaves(got.R, got.factors, got.bundles),
                    _leaves(ref.R, ref.factors, ref.bundles)):
        assert np.array_equal(g, r), f"{tag}: coded recovery is not bitwise"


def _online(A, P, b, kills, scheme, **kw):
    return ft_caqr_sweep_online(
        A, SimComm(P), b, fault_hooks=[ScriptedKiller(dict(kills))],
        scheme=scheme, **kw)


@pytest.fixture(scope="module")
def ref8():
    A = _matrix(P8, M8, N8)
    return A, ft_caqr_sweep(A, SimComm(P8), B8)


@pytest.fixture(scope="module")
def ref16():
    A = _matrix(P16, M16, N16)
    return A, ft_caqr_sweep(A, SimComm(P16), B16)


# -- the GF(2^8) algebra under the scheme -------------------------------------


def test_gf_field_axioms_spot():
    """The exp/log tables implement GF(2^8): spot-check associativity,
    distributivity over XOR, and multiplicative inverses on a seeded
    sample — the properties the decode's exactness argument stands on."""
    rng = np.random.default_rng(11)
    for _ in range(200):
        a, b, c = (int(x) for x in rng.integers(0, 256, 3))
        assert gf_mul(a, gf_mul(b, c)) == gf_mul(gf_mul(a, b), c)
        assert gf_mul(a, b ^ c) == gf_mul(a, b) ^ gf_mul(a, c)
        if a:
            assert gf_mul(a, gf_inv(a)) == 1
    assert GF_EXP[0] == 1 and GF_LOG[1] == 0


@pytest.mark.parametrize("f", [1, 2, 3])
def test_generator_every_submatrix_invertible(f):
    """The MDS property itself: row 0 is all-ones (the plain XOR checksum
    lane), and EVERY f-column submatrix of the f-row Vandermonde generator
    inverts exactly over GF — so any f erasures are decodable."""
    G = generator(f, P8)
    assert np.all(G[0] == 1)
    for cols in itertools.combinations(range(P8), f):
        M = G[:, list(cols)]
        inv = gf_inv_matrix(M)
        # GF matmul: prod[i,j] = XOR_k M[i,k] * inv[k,j]
        prod = np.zeros((f, f), np.uint8)
        for i in range(f):
            for j in range(f):
                acc = 0
                for k in range(f):
                    acc ^= gf_mul(int(M[i, k]), int(inv[k, j]))
                prod[i, j] = acc
        assert np.array_equal(prod, np.eye(f, dtype=np.uint8)), cols


def test_generator_rejects_oversize_world():
    with pytest.raises(ValueError):
        generator(2, 256)


def test_pairing_table_is_xor_buddy_algebra():
    """The canonical pairing home moved into the coding seam; the table is
    still exactly the per-level XOR-buddy involution."""
    for level, pairs in enumerate(pairing_table(P8)):
        seen = set()
        for a, b in pairs:
            assert xor_buddy(a, level) == b and xor_buddy(b, level) == a
            seen |= {a, b}
        assert seen == set(range(P8))


def test_encode_decode_round_trip_mid_sweep():
    """Byte-level seam check, no driver: encode a mid-sweep state, NaN two
    lanes with the real death mask, decode — every protected leaf restored
    bit for bit (uint/bool bookkeeping is untouched by design)."""
    comm = SimComm(P8)
    state = initial_sweep_state(comm, _matrix(P8, M8, N8), B8)
    for _ in range(5):
        state = sweep_step(comm, state)
    scheme = MDSScheme(f=2)
    encoded = scheme.refresh(comm, state)
    struck = encoded
    for lane in (2, 3):
        struck = obliterate_state(comm, struck, lane)
    decoded, reads = scheme.decode_lanes(comm, struck, [2, 3], {2, 3})
    for g, r in zip(_leaves(decoded), _leaves(state)):
        if np.issubdtype(r.dtype, np.floating):
            assert np.array_equal(g, r)
    assert reads == {"coded.parity0": P8, "coded.parity1": P8 + 1,
                     "coded.survivor0": 0, "coded.survivor1": 1,
                     "coded.survivor4": 4, "coded.survivor5": 5,
                     "coded.survivor6": 6, "coded.survivor7": 7}


# -- the exhaustive f=2 kill matrix at P=8 ------------------------------------


def _check_pair_kill(A, ref, P, b, pt, pair, scheme, points):
    got = _online(A, P, b, {pt: list(pair)}, scheme)
    _assert_bitwise(got, ref, tag=f"{pt} kill {pair}")
    assert [(e.point, e.lane) for e in got.events] == \
        [(pt, pair[0]), (pt, pair[1])]
    parity_keys = {f"coded.parity{j}": P + j for j in range(scheme.f)}
    survivors = {f"coded.survivor{i}": i
                 for i in range(P) if i not in pair}
    for e in got.events:
        # the multi-source decode ledger: every survivor + every parity
        # slot was read (contrast the XOR path's single-source entries)
        assert e.reads == {**parity_keys, **survivors}, (pt, pair)


@pytest.mark.parametrize("pt", POINTS8, ids=lambda p: f"{p[0]}-{p[1]}-{p[2]}")
def test_exhaustive_pair_kill_matrix_p8(ref8, pt):
    """THE tentpole gate: every one of the 28 lane pairs — every former
    XOR-buddy pair included — killed simultaneously at this sweep point,
    recovered by the joint GF decode, and the finished factorization is
    bitwise-identical to the failure-free run with the full multi-source
    ledger recorded. Parametrized over all 14 sweep points: 392 double
    kills total, zero tolerance."""
    A, ref = ref8
    scheme = MDSScheme(f=2)
    for pair in PAIRS8:
        _check_pair_kill(A, ref, P8, B8, pt, pair, scheme, POINTS8)


def test_former_buddy_pairs_walled_on_xor_p8(ref8):
    """Regression keep: under the default XOR scheme the SAME buddy-pair
    schedules still raise UnrecoverableFailure — the wall the coded lanes
    remove is real, not an artifact of the new tests."""
    A, _ = ref8
    pt = sweep_point(1, "trailing", 0)
    for pair in BUDDY_PAIRS8[:3]:
        with pytest.raises(UnrecoverableFailure):
            _online(A, P8, B8, {pt: list(pair)}, XORPairScheme())


def test_triple_kill_under_f3_p8(ref8):
    """f is a real knob: MDSScheme(f=3) decodes three simultaneous deaths
    — including a whole buddy *group* — bitwise."""
    A, ref = ref8
    scheme = MDSScheme(f=3)
    for pt, trip in [
        (sweep_point(0, "tsqr", 1), (0, 1, 2)),       # buddy pair + one
        (sweep_point(1, "trailing", 0), (2, 3, 7)),   # the acceptance pair
        (sweep_point(1, "leaf", 0), (4, 5, 6)),
    ]:
        got = _online(A, P8, B8, {pt: list(trip)}, scheme)
        _assert_bitwise(got, ref, tag=f"f3 {pt} {trip}")


def test_f_plus_one_deaths_name_the_boundary(ref8):
    """UnrecoverableFailure is now the f+1 boundary: t > f with no XOR
    escape raises an error that names the scheme's tolerance."""
    A, _ = ref8
    pt = sweep_point(1, "trailing", 0)
    with pytest.raises(UnrecoverableFailure, match="f=2"):
        _online(A, P8, B8, {pt: [0, 1, 2]}, MDSScheme(f=2))
    with pytest.raises(UnrecoverableFailure, match="f=1"):
        _online(A, P8, B8, {pt: [2, 3]}, MDSScheme(f=1))


def test_t_exceeding_f_still_falls_back_to_xor(ref8):
    """Monotonically stronger, never weaker: three simultaneous deaths
    under f=2 exceed the joint decode, but each dead lane still has a live
    XOR source, so the per-lane fallback recovers — exactly what the old
    scheme could do."""
    A, ref = ref8
    pt = sweep_point(0, "trailing", 0)
    got = _online(A, P8, B8, {pt: [0, 2, 4]}, MDSScheme(f=2))
    _assert_bitwise(got, ref, tag="xor fallback t=3>f=2")
    # the fallback ledger is the XOR single-source one, not the decode's
    assert all("coded.parity0" not in e.reads for e in got.events)


# -- P=16: spot tier-1, full matrix slow --------------------------------------


def test_pair_kill_spot_p16(ref16):
    """P=16 spot coverage at tier-1: a buddy pair, a cross-half pair, and
    the lowest/highest lanes, at one point of each phase class."""
    A, ref = ref16
    scheme = MDSScheme(f=2)
    for pt in [sweep_point(0, "leaf", 0), sweep_point(0, "tsqr", 2),
               sweep_point(1, "trailing", 1)]:
        for pair in [(4, 5), (0, 9), (0, 15)]:
            _check_pair_kill(A, ref, P16, B16, pt, pair, scheme, POINTS16)


@pytest.mark.slow
def test_exhaustive_pair_kill_matrix_p16(ref16):
    """The full 120-pair x every-sweep-point matrix at P=16 (slow tier)."""
    A, ref = ref16
    scheme = MDSScheme(f=2)
    for pt in POINTS16:
        for pair in PAIRS16:
            _check_pair_kill(A, ref, P16, B16, pt, pair, scheme, POINTS16)


# -- the f=1 differential gate: MDSScheme(f=1) == XORPairScheme ---------------


@pytest.mark.parametrize("shape", [
    ("aligned", 8, 16, 4), ("ragged", 6, 10, 4), ("wide", 4, 24, 4),
], ids=lambda s: s[0])
def test_mds_f1_bitwise_equals_xor(shape):
    """At f=1 the hybrid rule routes every single death through the XOR
    rebuild path, so MDSScheme(f=1) is indistinguishable from
    XORPairScheme — same bits AND same single-source read ledger — on
    aligned, ragged, and wide geometries, scheduled and online."""
    _, m_loc, n, b = shape
    P, comm = 4, SimComm(4)
    A = _matrix(4, m_loc, n, seed=5)
    n_panels = sweep_geometry(4, m_loc, n, b).n_panels
    pt = sweep_point(min(1, n_panels - 1), "trailing", 0)
    sched = FailureSchedule(events={pt: [2]})
    for tag, run in [
        ("scheduled", lambda s: ft_caqr_sweep(A, comm, b, schedule=sched,
                                              scheme=s)),
        ("online", lambda s: _online(A, P, b, {pt: [2]}, s)),
    ]:
        x = run(XORPairScheme())
        m = run(MDSScheme(f=1))
        _assert_bitwise(m, x, tag=f"f1-diff {tag}")
        assert [(e.point, e.lane, e.reads) for e in x.events] == \
            [(e.point, e.lane, e.reads) for e in m.events], tag
        # the f=1 ledger is single-source: no coded.* reads anywhere
        assert all(not k.startswith("coded.")
                   for e in m.events for k in e.reads), tag


def test_scheduled_equals_online_mds_acceptance():
    """The ISSUE acceptance schedule on the ragged 4-lane geometry: the
    former-buddy-pair kill that raises under XOR recovers under
    MDSScheme(f=2), and the scheduled (trace-time) run is bitwise-equal
    to the online (runtime-detected) one and to the failure-free sweep."""
    P, m_loc, n, b = 4, 6, 10, 4
    A = _matrix(P, m_loc, n, seed=3)
    comm = SimComm(P)
    pt = sweep_point(1, "trailing", 0)
    free = ft_caqr_sweep(A, comm, b)
    with pytest.raises(UnrecoverableFailure):
        ft_caqr_sweep(A, comm, b, schedule=FailureSchedule(events={pt: [2, 3]}))
    sched = ft_caqr_sweep(A, comm, b,
                          schedule=FailureSchedule(events={pt: [2, 3]}),
                          scheme=MDSScheme(f=2))
    onl = _online(A, P, b, {pt: [2, 3]}, MDSScheme(f=2))
    _assert_bitwise(sched, free, tag="scheduled vs free")
    _assert_bitwise(onl, free, tag="online vs free")
    assert [(e.point, e.lane, e.reads) for e in sched.events] == \
        [(e.point, e.lane, e.reads) for e in onl.events]


def test_mds_shard_map_differential():
    """The shard_map leg: the same buddy-pair kill under MDSScheme(f=2)
    on a 4-device mesh — scheduled trace AND online segments — matches
    the SimComm run leaf for leaf."""
    out = run_forced_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimComm
        from repro.ft import FailureSchedule, MDSScheme, ft_caqr_sweep, \\
            sweep_point
        from repro.ft.online.detect import ScriptedKiller
        from repro.launch.spmd_qr import (
            ft_caqr_sweep_online_spmd, ft_caqr_sweep_spmd, make_lane_mesh)

        P_, m_loc, n, b = 4, 6, 10, 4
        rng = np.random.default_rng(3)
        A = jnp.asarray(rng.standard_normal((P_ * m_loc, n)), jnp.float32)
        pt = sweep_point(1, "trailing", 0)
        sched = FailureSchedule(events={pt: [2, 3]})
        mesh = make_lane_mesh(4)
        sim = ft_caqr_sweep(A.reshape(P_, m_loc, n), SimComm(P_), b,
                            schedule=sched, scheme=MDSScheme(f=2))
        for tag, got in [
            ("scheduled", ft_caqr_sweep_spmd(
                A, b, schedule=sched, mesh=mesh, scheme=MDSScheme(f=2))),
            ("online", ft_caqr_sweep_online_spmd(
                A, b, mesh=mesh, fault_hooks=[ScriptedKiller({pt: [2, 3]})],
                scheme=MDSScheme(f=2))),
        ]:
            gl = jax.tree_util.tree_leaves((got.R, got.factors, got.bundles))
            sl = jax.tree_util.tree_leaves((sim.R, sim.factors, sim.bundles))
            assert len(gl) == len(sl)
            for g, s in zip(gl, sl):
                assert np.array_equal(np.asarray(g), np.asarray(s)), tag
            print("OK", tag)
        print("MDS_SPMD_OK")
    """, n_devices=4)
    assert "MDS_SPMD_OK" in out


# -- property suite: random (P, f, kill set, point) draws ---------------------

_PROP_REFS = {}


def _property_check(P, f, kill, pt_idx):
    """One property-suite draw: a kill set of size <= f at a drawn sweep
    point must finish bitwise-identical to the failure-free run."""
    m_loc, n, b = 4, 8, 4
    if P not in _PROP_REFS:
        A = _matrix(P, m_loc, n, seed=17 + P)
        _PROP_REFS[P] = (A, ft_caqr_sweep(A, SimComm(P), b))
    A, ref = _PROP_REFS[P]
    geom = sweep_geometry(P, m_loc, n, b)
    points = list(iter_sweep_points(geom.n_panels, geom.levels))
    pt = points[pt_idx % len(points)]
    got = _online(A, P, b, {pt: sorted(kill)}, MDSScheme(f=f))
    _assert_bitwise(got, ref, tag=f"prop P={P} f={f} {pt} kill={kill}")


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(data=st.data())
    def test_property_any_kill_set_within_f(data):
        P = data.draw(st.sampled_from([4, 8]))
        f = data.draw(st.integers(min_value=1, max_value=3))
        t = data.draw(st.integers(min_value=1, max_value=f))
        kill = data.draw(st.sets(st.integers(0, P - 1),
                                 min_size=t, max_size=t))
        pt_idx = data.draw(st.integers(min_value=0, max_value=30))
        _property_check(P, f, kill, pt_idx)

else:

    _GRID_RNG = np.random.default_rng(2026)
    _GRID = []
    for _P in (4, 8):
        for _f in (1, 2, 3):
            for _ in range(3):
                _t = int(_GRID_RNG.integers(1, _f + 1))
                _kill = tuple(sorted(_GRID_RNG.choice(_P, _t, replace=False)))
                _GRID.append((_P, _f, _kill, int(_GRID_RNG.integers(0, 31))))

    @pytest.mark.parametrize("P,f,kill,pt_idx", _GRID,
                             ids=[f"P{p}-f{f}-k{'_'.join(map(str, k))}"
                                  for p, f, k, _ in _GRID])
    def test_property_any_kill_set_within_f(P, f, kill, pt_idx):
        """Deterministic stand-in for the hypothesis suite (the image has
        no hypothesis): a seeded grid of 18 random draws over the same
        strategy space — any kill set of size <= f, anywhere in the sweep,
        finishes bitwise-identical to the failure-free run."""
        _property_check(P, f, [int(k) for k in kill], pt_idx)


def test_scheme_validation():
    with pytest.raises(ValueError):
        MDSScheme(f=0)
    with pytest.raises(ValueError):
        MDSScheme(f=9)
    assert MDSScheme(f=2).name == "mds" and XORPairScheme().f == 1
