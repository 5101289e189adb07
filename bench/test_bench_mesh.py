"""The ``tsqr_mesh4.kill1`` cell driven end to end at a tiny size on 4
forced CPU devices (16 lanes, 4 per device), sound and with faults planted
in the program: each fault must turn ``correct`` false, and the heal's
cross-chip byte counter must show a gather of the state planted into the
heal.

One subprocess runs every case (jax fixes the device count when it starts)
and prints one JSON line per case; the tests read them. Only the look for
a TPU is skipped (``allow_cpu``); set-up, window and comparison are the
benchmark's own. The limits are the tiny size's, as in
``test_bench_faults.py``.
"""
from __future__ import annotations

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import contextlib, json, os, shutil, sys, tempfile
cache = tempfile.mkdtemp(prefix="cc_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
sys.path[:0] = ["src", "."]
import jax, jax.numpy as jnp
from bench import run_cell
from repro.core.comm import MeshComm
from repro.ft import driver
from repro.ft.online import orchestrator
from repro.ft.online.state import state_lane_axes
from repro.launch import spmd_qr

TINY = {"config": {"m_rows": 256, "n_cols": 32, "panel": 4},
        "limits": {"gram_residual": 1e-5}}
SEED = 2 ** 40 + 11


@contextlib.contextmanager
def planted(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def run(tag, trace=False):
    spmd_qr._ONLINE_STEPS.clear()
    orchestrator._SEGMENT_CACHE.clear()
    jax.clear_caches()
    res, _ = run_cell.run("tsqr_mesh4.kill1", SEED, 0.3, trace,
                          allow_cpu=True, overrides=TINY)
    print("CASE " + json.dumps({"case": tag, **res}), flush=True)


run("sound", trace=True)

# a butterfly level between chips (levels 2 and 3: every lane exchanges)
# hands each chip back its own block instead of the partner chip's
ppermute = MeshComm.ppermute


def no_cross_exchange(self, x, perm):
    if len(perm) == self.axis_size() and all(
            s // self.L != d // self.L for s, d in perm):
        return x
    return ppermute(self, x, perm)


with planted(MeshComm, "ppermute", no_cross_exchange):
    run("no_cross_exchange")

# the REBUILD reads one more artifact, from lane 6: no buddy of lane 3
rebuild = driver.rebuild_state


def non_buddy_read(comm, state, lane, point, dead=frozenset()):
    state, reads = rebuild(comm, state, lane, point, dead)
    src = 6
    r0 = comm.fetch_lane(state.R_rows[0], lane, src)
    return (state.replace(R_rows=(r0,) + state.R_rows[1:]),
            {**reads, "panel0.r_rows.again": src})


with planted(driver, "rebuild_state", non_buddy_read):
    run("non_buddy_read")

# the healed lane's running C' one ulp off in its last column (the rows
# of R that its pair combines deposit)
recover = spmd_qr.recover_lanes


def one_ulp(comm, state, newly, *a, **kw):
    state, events = recover(comm, state, newly, *a, **kw)
    x = state.C_prime
    nudged = x.at[..., -1].set(jnp.nextafter(x[..., -1], jnp.inf))
    return state.replace(C_prime=comm.where_lane(newly[0], nudged, x)), events


with planted(spmd_qr, "recover_lanes", one_ulp):
    run("one_ulp")


# the heal first fetches every lane of chips 1-3, every leaf, onto chip 0
def gather_first(comm, state, newly, *a, **kw):
    axes = jax.tree_util.tree_leaves(state_lane_axes(state))
    for x, ax in zip(jax.tree_util.tree_leaves(state), axes):
        if ax >= 0:
            for lane in range(comm.L, comm.axis_size()):
                comm.fetch_lane(x, lane % comm.L, lane, lane_axis=ax)
    return recover(comm, state, newly, *a, **kw)


with planted(spmd_qr, "recover_lanes", gather_first):
    run("gather", trace=True)
shutil.rmtree(cache, ignore_errors=True)
"""


@pytest.fixture(scope="module")
def cases():
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from spmd_subprocess_util import run_forced_devices

    out = run_forced_devices(_SCRIPT, n_devices=4, timeout=900)
    rows = [json.loads(ln[len("CASE "):]) for ln in out.splitlines()
            if ln.startswith("CASE ")]
    return {r["case"]: r for r in rows}


def test_sound_run_is_correct(cases):
    r = cases["sound"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["programs_built_in_window"]["value"] == 0
    assert r["checks"]["non_buddy_reads"]["value"] == 0
    assert r["device"]["count"] == 4
    assert r["metrics"]["rebuild_s"]["value"] > 0
    assert r["metrics"]["heal_xchip_mib"]["value"] > 0


@pytest.mark.parametrize("fault", ["no_cross_exchange", "non_buddy_read",
                                   "one_ulp"])
def test_fault_turns_correct_false(cases, fault):
    r = cases[fault]
    assert r["correct"] is False, r["checks"]


def test_fault_is_caught_by_its_check(cases):
    checks = {f: cases[f]["checks"] for f in
              ("no_cross_exchange", "non_buddy_read", "one_ulp")}
    assert (checks["no_cross_exchange"]["gram_residual"]["value"]
            > checks["no_cross_exchange"]["gram_residual"]["limit"])
    assert checks["non_buddy_read"]["non_buddy_reads"]["value"] >= 1
    assert checks["one_ulp"]["r_bits_differing"]["value"] >= 1


def test_planted_gather_shows_in_heal_bytes(cases):
    """The gather moves chips 1-3's lanes of every leaf: at least their
    slices of the source matrix and of the working matrix, 12 lanes of
    16 x 32 float32 each, on top of the sound heal's bytes."""
    sound = cases["sound"]["metrics"]["heal_xchip_mib"]["value"] * 2**20
    gather = cases["gather"]["metrics"]["heal_xchip_mib"]["value"] * 2**20
    assert gather >= sound + 2 * 12 * 16 * 32 * 4


# -- the two new readers, on hand-made inputs ----------------------------------


def _reader(name):
    import importlib.util

    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_xchip_s_reads_the_programs_collectives_per_chip():
    """Per chip the union of collective-permute start/done, psum and
    all-reduce intervals; all-gather (the fault hook's) and kernels are
    not counted; mean over chips, per factorization."""
    import types

    from bench.trace_reduce import Trace

    ms = 1_000_000
    ops = [("collective-permute-start.1", 10 * ms, 11 * ms, 0),
           ("collective-permute-done.1", 11 * ms, 14 * ms, 0),
           ("psum.2", 13 * ms, 15 * ms, 0),          # overlaps: union 5 ms
           ("all-gather.5", 20 * ms, 60 * ms, 0),
           ("wy_apply.1", 60 * ms, 90 * ms, 0),
           ("all-reduce.3", 30 * ms, 33 * ms, 1),
           ("collective-permute-done.7", 95 * ms, 120 * ms, 1)]  # clipped
    tr = Trace(ops=ops, spans=[("bench.window", 0, 100 * ms)])
    read = _reader("xchip_s").read
    ctx = types.SimpleNamespace(
        trace=tr, telemetry={"chips": 2, "factorizations": 2})
    assert read(ctx) == pytest.approx((0.005 + 0.008) / 2 / 2)
    one_chip = types.SimpleNamespace(trace=tr,
                                     telemetry={"factorizations": 2})
    assert read(one_chip) is None


def test_heal_xchip_mib_is_the_median_heal_in_mib():
    import types

    read = _reader("heal_xchip_mib").read
    tel = {"kill": True, "heal_xchip_bytes": [2**20, 3 * 2**20, 2**21]}
    assert read(types.SimpleNamespace(telemetry=tel)) == 2.0
    assert read(types.SimpleNamespace(telemetry={"kill": True})) is None
    assert read(types.SimpleNamespace(
        telemetry={"kill": False, "heal_xchip_bytes": [0]})) is None
