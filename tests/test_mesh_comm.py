"""The two-level ``MeshComm``: 16 lanes, 4 on each of 4 forced host devices.

One subprocess (4 forced CPU devices; the main test process keeps seeing
one) runs every check below and prints its numbers as one JSON line; the
tests read them.

- The Comm primitives under shard_map equal ``SimComm(16)``'s bit for bit:
  they only move data, so bitwise holds on any backend.
- The failure-free online mesh sweep (``ft_caqr_sweep_online_spmd`` with 4
  lanes per chip) against a float64 reference, and against the SimComm(16)
  sweep within a tolerance (see ``test_failure_free_r_near_simcomm``).
- Lane 3 killed after panel 2's first trailing level and healed by the
  mesh heal program: bitwise equal to the failure-free mesh run, reads from
  exactly its XOR buddies 2, 1 (its chip), 7 and 11 (chips 1 and 2), and
  moves across chips exactly the bytes it read from lanes 7 and 11.
"""
import json

import pytest

from spmd_subprocess_util import run_forced_devices

_SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import MeshComm, SimComm
from repro.ft.failures import sweep_point
from repro.dist import compat
from repro.ft.online.detect import ScriptedKiller
from repro.ft.online.orchestrator import ft_caqr_sweep_online
from repro.launch.spmd_qr import ft_caqr_sweep_online_spmd, make_lane_mesh

CHIPS, L = 4, 4
P_ = CHIPS * L
mesh = make_lane_mesh(CHIPS)
sim = SimComm(P_)
out = {}


def on_mesh(fn, x, lane_axis=0, replicated=False):
    spec = P(*([None] * lane_axis + ["qr"]))
    body = lambda xl: fn(MeshComm("qr", L, CHIPS), xl)
    prog = jax.jit(compat.shard_map(body, mesh, in_specs=(spec,),
                                    out_specs=P() if replicated else spec))
    with compat.set_mesh(mesh):
        return np.asarray(prog(x))


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return bool(a.shape == b.shape and a.dtype == b.dtype
                and a.tobytes() == b.tobytes())


rng = np.random.default_rng(5)
x = jnp.asarray(rng.standard_normal((P_, 3, 5)), jnp.float32)
x1 = jnp.asarray(rng.standard_normal((2, P_, 3)), jnp.float32)
for lvl in range(4):
    perm = [(i, i ^ (1 << lvl)) for i in range(P_)]
    out[f"ppermute_level{lvl}"] = same(
        on_mesh(lambda c, v: c.ppermute(v, perm), x), sim.ppermute(x, perm))
# a permutation with no XOR structure: several chip rounds and lane maps
shuffle = [(int(s), int(d)) for s, d in
           enumerate(np.random.default_rng(9).permutation(P_))]
out["ppermute_shuffle"] = same(
    on_mesh(lambda c, v: c.ppermute(v, shuffle), x),
    sim.ppermute(x, shuffle))
# the baseline tree's one-way sends: some lanes receive nothing (zeros)
up = [(i, i - 4) for i in range(P_) if i % 8 == 4]
out["ppermute_partial"] = same(
    on_mesh(lambda c, v: c.ppermute(v, up), x), sim.ppermute(x, up))
for tag, dst, src in [("within", 3, 2), ("across", 3, 11)]:
    out[f"fetch_{tag}_axis0"] = same(
        on_mesh(lambda c, v: c.fetch_lane(v, dst, src), x),
        sim.fetch_lane(x, dst, src))
    out[f"fetch_{tag}_axis1"] = same(
        on_mesh(lambda c, v: c.fetch_lane(v, dst, src, lane_axis=1,
                                          into=-v), x1, lane_axis=1),
        sim.fetch_lane(x1, dst, src, lane_axis=1, into=-x1))
out["where_lane"] = same(
    on_mesh(lambda c, v: c.where_lane(6, v, -v, lane_axis=1), x1,
            lane_axis=1),
    sim.where_lane(6, x1, -x1, lane_axis=1))
out["poison"] = same(on_mesh(lambda c, v: c.poison(v, 13), x),
                     sim.poison(x, 13))
out["axis_index"] = same(
    on_mesh(lambda c, v: c.axis_index(), jnp.zeros(P_, jnp.int32)),
    sim.axis_index())
u = jnp.asarray(rng.integers(0, 256, (P_, 7)), jnp.uint8)
out["xor_reduce"] = same(
    on_mesh(lambda c, v: c.xor_reduce(v), u, replicated=True),
    sim.xor_reduce(u))
one = jnp.where(jnp.arange(P_)[:, None, None] == 9, x, 0.0)
out["psum"] = same(on_mesh(lambda c, v: c.psum(v), one), sim.psum(one))

# -- the sweep ---------------------------------------------------------------
m_loc, n, b = 32, 48, 8
A = jnp.asarray(np.random.default_rng(0).standard_normal((P_ * m_loc, n)),
                jnp.float32)
free = ft_caqr_sweep_online_spmd(A, b, mesh=mesh, lanes_per_chip=L)
ref = ft_caqr_sweep_online(A.reshape(P_, m_loc, n), sim, b)
A64 = np.asarray(A, np.float64)
G = A64.T @ A64
R = np.asarray(free.R[0], np.float64)
out["gram_residual"] = float(np.linalg.norm(R.T @ R - G) / np.trace(G))
out["below_diagonal"] = float(np.abs(np.tril(R, -1)).max() / np.abs(R).max())
out["r_lanes_equal"] = all(same(free.R[i], free.R[0]) for i in range(P_))
out["vs_simcomm"] = float(np.abs(np.asarray(free.R) - np.asarray(ref.R)).max())
out["r_max"] = float(np.abs(np.asarray(ref.R)).max())
out["free_events"] = len(free.events)

point = sweep_point(2, "trailing", 0)
seen = {}


def measure(comm, state):
    # the state at the kill boundary, before the death: one chip's share
    if state.cursor == sweep_point(2, "trailing", 1) and "state" not in seen:
        seen["state"] = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
        seen["n_work"] = state.geom.n_work
    return state


kill = ft_caqr_sweep_online_spmd(
    A, b, mesh=mesh, lanes_per_chip=L,
    fault_hooks=[measure, ScriptedKiller({point: [3]})])
out["healed_bits_differing"] = int(sum(
    int(np.sum(np.asarray(g).view(np.uint32) != np.asarray(f).view(np.uint32)))
    for g, f in zip(jax.tree_util.tree_leaves((kill.R, kill.factors,
                                               kill.bundles)),
                    jax.tree_util.tree_leaves((free.R, free.factors,
                                               free.bundles)))))
(ev,) = kill.events
out["event"] = [list(ev.point), ev.lane]
out["sources"] = sorted(set(ev.reads.values()))
out["reads"] = ev.reads
out["xchip_bytes"] = ev.xchip_bytes
out["chip_state_bytes"] = seen["state"] // CHIPS
out["n_work"] = seen["n_work"]
out["b"] = b
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    text = run_forced_devices(_SCRIPT, n_devices=4)
    line = [ln for ln in text.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("level", range(4))
def test_butterfly_level_matches_simcomm(result, level):
    """Levels 0-1 pair lanes of one chip, levels 2-3 lanes of two chips."""
    assert result[f"ppermute_level{level}"]


@pytest.mark.parametrize("case", ["shuffle", "partial"])
def test_other_permutations_match_simcomm(result, case):
    assert result[f"ppermute_{case}"]


@pytest.mark.parametrize("case", ["within_axis0", "within_axis1",
                                  "across_axis0", "across_axis1"])
def test_fetch_lane_matches_simcomm(result, case):
    """Lane 3 fetches from lane 2 (its chip) and from lane 11 (chip 2)."""
    assert result[f"fetch_{case}"]


@pytest.mark.parametrize("prim", ["where_lane", "poison", "axis_index",
                                  "xor_reduce", "psum"])
def test_lane_primitives_match_simcomm(result, prim):
    assert result[prim]


def test_failure_free_r_matches_float64_reference(result):
    assert result["gram_residual"] < 1e-6
    assert result["below_diagonal"] == 0.0
    assert result["r_lanes_equal"]
    assert result["free_events"] == 0


def test_failure_free_r_near_simcomm(result):
    """The mesh program and SimComm(16) run the same algorithm, but vmap
    each per-lane op over 4 lanes instead of 16: XLA on the CPU may tile
    the batched GEMMs differently and so sum in another order. The
    difference is rounding, held here to the float32 kernel-vs-oracle
    tolerance of ``repro.kernels.ref.tolerances`` relative to R's scale."""
    from repro.kernels.ref import tolerances

    rtol, _ = tolerances("float32")
    assert result["vs_simcomm"] <= rtol * result["r_max"]


def test_healed_r_bitwise_equal_to_failure_free(result):
    assert result["event"] == [[2, "trailing", 0], 3]
    assert result["healed_bits_differing"] == 0


def test_rebuild_reads_only_xor_buddies(result):
    assert result["sources"] == [1, 2, 7, 11]


def test_heal_moves_only_remote_buddy_slices(result):
    """The bytes that crossed chips are the slices read from lanes 7 and 11:
    per earlier panel j, lane 7's level-2 bundle row (W, C_self, C_buddy:
    three b x n_work slices), lane 11's level-3 bundle row (three more),
    and lane 11's final C' sources (C_buddy and C_self from column j*b on,
    Y2 and T). Far below one chip's share of the state."""
    b, n_work = result["b"], result["n_work"]
    expect = 0
    for artifact, src in result["reads"].items():
        if src not in (7, 11):
            continue
        panel, what = artifact.split(".")
        j = int(panel[len("panel"):])
        if what.startswith("bundle@level"):
            expect += 3 * b * n_work * 4
        else:
            assert what == "cprime_final", artifact
            expect += (2 * b * (n_work - j * b) + 2 * b * b) * 4
    assert expect > 0
    assert result["xchip_bytes"] == expect
    assert result["xchip_bytes"] < result["chip_state_bytes"]
