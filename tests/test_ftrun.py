"""FT training runtime (DESIGN.md §14): optimizer-internal FT-CAQR sweeps.

Gates the tentpole invariants:

* a lane killed *inside* the optimizer-internal sweep of a training step is
  healed in place — params and loss curve bitwise-identical to the
  failure-free run (caqr_muon routing and the PowerSGD bridge);
* async double-buffered segment execution is bitwise-identical to sync;
* a run suspended mid-factorization resumes across the checkpoint boundary
  bitwise-identically (sweep wire format v2 carries the MDS parity slots;
  v1 stays loadable and its parity-less resume window fails honestly).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core.caqr import block_row_layout
from repro.core.comm import SimComm
from repro.ft.failures import prev_sweep_point
from repro.data.pipeline import DataConfig
from repro.ft.coding import MDSScheme, UnrecoverableFailure
from repro.ft.online.detect import NaNSentinelDetector, ScriptedKiller
from repro.ft.online.orchestrator import SweepOrchestrator
from repro.ft.semantics import Semantics
from repro.ckpt.sweep import load_sweep_state, save_sweep_state
from repro.train.loop import TrainConfig
from repro.train.ftrun import (
    FTRunConfig,
    FTTrainer,
    QREngine,
    StepSweepKiller,
    SuspendAfter,
    SuspendSweep,
    TrainingSuspended,
    plan_muon_tasks,
)


@pytest.fixture(scope="module", autouse=True)
def _drop_train_executables():
    """This module compiles full training steps (transformer fwd/bwd per
    optimizer, plus the Muon/PowerSGD programs) in-process — by far the
    largest executables in the tier-1 suite. Free them at teardown: left
    resident, the accumulated XLA compile state can crash a later module's
    first large compile (observed as a backend_compile segfault in
    test_online_recovery.py when the full suite runs in one process)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def cfg():
    return get_smoke("tinyllama-1.1b")


@pytest.fixture(scope="module")
def dcfg(cfg):
    return DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=1)


def _tcfg(**kw):
    base = dict(steps=4, lr=1e-2, warmup=2, n_lanes=4, diskless_every=2,
                log_every=100, semantics=Semantics.REBUILD)
    base.update(kw)
    return TrainConfig(**base)


def _params_equal(a, b) -> bool:
    eq = jax.tree_util.tree_map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b)
    return all(jax.tree_util.tree_leaves(eq))


# -- engine unit behavior ----------------------------------------------------


def test_engine_q_is_orthonormal_and_ft():
    rng = np.random.default_rng(0)
    M = jnp.asarray(rng.standard_normal((128, 48)), jnp.float32)
    eng = QREngine(n_lanes=4, panel_width=16)
    Q = eng.orthonormalize(M)
    assert Q.shape == M.shape
    err = np.abs(np.asarray(Q.T @ Q) - np.eye(48)).max()
    assert err < 1e-4
    # killed-lane sweep returns the bitwise-identical Q
    killer = ScriptedKiller({(0, "trailing", 0): [2]})
    eng_k = QREngine(n_lanes=4, panel_width=16, fault_hooks=[killer])
    Qk = eng_k.orthonormalize(M)
    assert np.array_equal(np.asarray(Q), np.asarray(Qk))


def test_engine_async_matches_sync():
    rng = np.random.default_rng(1)
    M = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    kill = {(1, "trailing", 0): [1]}
    Qs = QREngine(n_lanes=4, fault_hooks=[ScriptedKiller(kill)]) \
        .orthonormalize(M)
    Qa = QREngine(n_lanes=4, async_segments=True,
                  fault_hooks=[ScriptedKiller(kill)]).orthonormalize(M)
    assert np.array_equal(np.asarray(Qs), np.asarray(Qa))


def test_nonblocking_probe_matches_poll():
    rng = np.random.default_rng(2)
    M = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    comm = SimComm(4)
    from repro.ft.driver import obliterate_state
    from repro.ft.online.state import initial_sweep_state

    st = initial_sweep_state(comm, block_row_layout(M, 4), 16)
    st_dead = obliterate_state(comm, st, 3)
    det_poll, det_probe = NaNSentinelDetector(), NaNSentinelDetector()
    assert det_poll.poll(comm, st_dead) == [3]
    handle = det_probe.probe(comm, st_dead)
    assert det_probe.collect(comm, handle) == [3]
    # re-arm after revive, silent when healthy
    det_probe.revive(3)
    assert det_probe.collect(comm, det_probe.probe(comm, st)) == []


def test_task_planner_smoke_model(cfg):
    import repro.models.transformer as tf

    params = tf.init_params(cfg, jax.random.key(0))
    tasks = plan_muon_tasks(params, min_qr_size=8192)
    names = {t.name for t in tasks}
    # all FFN slices route; every routed slice shares the (128, 64) geometry
    assert any("ffn" in n for n in names)
    assert all((t.rows, t.cols) == (128, 64) for t in tasks)
    assert all(t.name.endswith(("#0", "#1")) for t in tasks)


# -- training bitwise identity ----------------------------------------------


def test_muon_kill_inside_sweep_bitwise(cfg, dcfg):
    tcfg = _tcfg(optimizer="caqr_muon")
    ref = FTTrainer(cfg, tcfg, dcfg)
    hist_ref = ref.run()

    killer = StepSweepKiller(at_step=2, lane=1)
    tr = FTTrainer(cfg, tcfg, dcfg, qr_fault_hooks=[killer])
    hist = tr.run()

    assert killer.fired and killer.struck[0] == 2
    assert _params_equal(ref.state.params, tr.state.params)
    assert [h["loss"] for h in hist_ref] == [h["loss"] for h in hist]
    # the kill healed inside the sweep: no training-level rewind happened
    assert [h["step"] for h in hist] == list(range(tcfg.steps))


def test_muon_async_segments_bitwise(cfg, dcfg):
    tcfg = _tcfg(optimizer="caqr_muon")
    killer_s = StepSweepKiller(at_step=1, lane=3)
    sync = FTTrainer(cfg, tcfg, dcfg, qr_fault_hooks=[killer_s])
    sync.run()
    killer_a = StepSweepKiller(at_step=1, lane=3)
    asyn = FTTrainer(cfg, tcfg, dcfg, FTRunConfig(async_segments=True),
                     qr_fault_hooks=[killer_a])
    asyn.run()
    assert killer_s.fired and killer_a.fired
    assert _params_equal(sync.state.params, asyn.state.params)


def test_powersgd_bridge_kill_bitwise(cfg, dcfg):
    tcfg = _tcfg(optimizer="adamw")
    fcfg = FTRunConfig(compression_rank=4, compression_min_size=4096)
    ref = FTTrainer(cfg, tcfg, dcfg, fcfg)
    hist_ref = ref.run()
    assert ref._tasks, "nothing routed through the bridge"

    killer = StepSweepKiller(at_step=1, lane=2)
    tr = FTTrainer(cfg, tcfg, dcfg,
                   FTRunConfig(compression_rank=4, compression_min_size=4096),
                   qr_fault_hooks=[killer])
    hist = tr.run()
    assert killer.fired
    assert _params_equal(ref.state.params, tr.state.params)
    assert [h["loss"] for h in hist_ref] == [h["loss"] for h in hist]


def test_powersgd_bridge_trains(cfg, dcfg):
    tcfg = _tcfg(optimizer="adamw", steps=8)
    tr = FTTrainer(cfg, tcfg, dcfg,
                   FTRunConfig(compression_rank=8,
                               compression_min_size=4096))
    hist = tr.run()
    assert hist[-1]["loss"] < hist[0]["loss"]


# -- suspend / resume across the checkpoint boundary -------------------------


def test_suspend_resume_bitwise(cfg, dcfg, tmp_path):
    tcfg = _tcfg(optimizer="caqr_muon", ckpt_dir=str(tmp_path))
    ref = FTTrainer(cfg, tcfg, dcfg)
    ref.run()

    tr = FTTrainer(cfg, tcfg, dcfg,
                   FTRunConfig(suspend_after_boundaries=290))
    with pytest.raises(TrainingSuspended) as exc:
        tr.run()
    assert 0 < exc.value.step < tcfg.steps

    resumed = FTTrainer.resume(cfg, tcfg, dcfg)
    assert resumed._pending_resume is not None
    assert resumed._pending_resume[0] == exc.value.task
    resumed.run()
    assert _params_equal(ref.state.params, resumed.state.params)


# -- sweep-state wire format v2 ----------------------------------------------


def _mid_sweep_state(scheme=None, boundaries=3):
    rng = np.random.default_rng(7)
    A0 = block_row_layout(
        jnp.asarray(rng.standard_normal((128, 64)), jnp.float32), 4)
    orch = SweepOrchestrator(A0, SimComm(4), 16, scheme=scheme,
                             boundary_hooks=[SuspendAfter(boundaries)])
    with pytest.raises(SuspendSweep) as exc:
        orch.run()
    return A0, exc.value.state


def _finish(state, **kw):
    return SweepOrchestrator.from_state(state, SimComm(4), **kw).run()


def test_wire_v1_still_loads_and_finishes(tmp_path):
    A0, st = _mid_sweep_state()
    ref = _finish(st)
    p = save_sweep_state(str(tmp_path / "v1"), st, version=1)
    res = _finish(load_sweep_state(p))
    assert np.array_equal(np.asarray(ref.R), np.asarray(res.R))


def test_wire_v2_mds_parity_survives_suspension(tmp_path):
    A0, st = _mid_sweep_state(scheme=MDSScheme(2))
    assert st.code is not None
    ref = _finish(st, scheme=MDSScheme(2))
    pt = prev_sweep_point(st.cursor, st.geom.n_panels, st.geom.levels)

    # v2 resume: an XOR-buddy PAIR died while suspended — joint decode from
    # the persisted parity slots, bitwise-identical completion
    p2 = save_sweep_state(str(tmp_path / "v2"), st)
    st2 = load_sweep_state(p2)
    assert st2.code is not None
    res = _finish(st2, scheme=MDSScheme(2),
                  fault_hooks=[ScriptedKiller({pt: [0, 1]})])
    assert np.array_equal(np.asarray(ref.R), np.asarray(res.R))

    # v1 resume: same deaths, no persisted parity — honestly unrecoverable
    # (this is exactly the re-encode vulnerability window v2 closes)
    p1 = save_sweep_state(str(tmp_path / "v1"), st, version=1)
    st1 = load_sweep_state(p1)
    assert st1.code is None
    with pytest.raises(UnrecoverableFailure):
        _finish(st1, scheme=MDSScheme(2),
                fault_hooks=[ScriptedKiller({pt: [0, 1]})])
