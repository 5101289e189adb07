"""The harness's correctness check, driven end to end on the CPU at a tiny
size with the timed path broken underneath: each fault a cell can have must
turn ``correct`` false, and the unbroken path must leave it true.

Only the look for a TPU is skipped (``allow_cpu``); each cell's set-up,
window and comparison are the benchmark's own. The limits are the tiny size's
(float32 on the CPU reads a Gram residual near 1e-7 there); every fault
below misses them by orders of magnitude.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run_cell

TINY_LIMITS = {"gram_residual": 1e-5}
TINY_CAQR = {"config": {"m_rows": 64, "n_cols": 16, "panel": 4, "lanes": 4},
             "limits": TINY_LIMITS}
TINY_KILL = {**TINY_CAQR, "traffic": {
    "kill": {"lane": 1, "panel": 2, "phase": "trailing", "level": 0}}}
TINY = {"tsqr_tall.free": TINY_CAQR, "tsqr_tall.kill1": TINY_KILL}


@pytest.fixture(autouse=True)
def fresh_programs(monkeypatch, tmp_path):
    """No persistent cache, and no compiled segment carried from one
    (broken or sound) program to the next."""
    from repro.ft.online import orchestrator

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    orchestrator._SEGMENT_CACHE.clear()
    jax.clear_caches()
    yield
    orchestrator._SEGMENT_CACHE.clear()
    jax.clear_caches()


def run_tiny(workload, seed=2 ** 40 + 3):
    result, _ = run_cell.run(workload, seed, 0.3, False, allow_cpu=True,
                             overrides=TINY[workload])
    return result


@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run_is_correct(workload):
    result = run_tiny(workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert list(result["checks"])[-1] == "programs_built_in_window"
    assert result["checks"]["programs_built_in_window"]["value"] == 0
    json.dumps(result)


def test_traced_run_reads_its_own_window(tmp_path):
    """``--trace 1`` drives the same check under the profiler and adds the
    traced window, the device's busy time and the breakdown; off the chip
    the peaks are unknown, so no roofline or peak share is read."""
    result, _ = run_cell.run("tsqr_tall.free", 2 ** 33 + 1, 0.3, True,
                             allow_cpu=True, overrides=TINY_CAQR,
                             trace_dir=str(tmp_path / "trace"))
    assert result["correct"], result["checks"]
    device = result["device"]
    assert device["window_s"] > 0 and device["busy_s"] >= 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not {"qr_mfu", "trailing_roofline", "panel_roofline"} & set(
        result["metrics"])
    assert list(result)[-1] == "checks"


# -- faults planted in the program --------------------------------------------


def _trailing_update_unchanged(monkeypatch):
    """A step that returns its state unchanged: every trailing combine
    hands back the C' it was given."""
    from repro.ft.online import state

    orig = state.trailing_combine_level

    def broken(comm, C_prime, *a, **kw):
        return orig(comm, C_prime, *a, **kw)._replace(C_prime=C_prime)

    monkeypatch.setattr(state, "trailing_combine_level", broken)


def _half_the_rows(monkeypatch):
    """Half of the batch left out: the sweep starts from a matrix whose
    upper half of lanes is zero."""
    from repro.ft.online import orchestrator

    orig = orchestrator.initial_sweep_state

    def broken(comm, A0, b):
        keep = jnp.arange(A0.shape[0]) < A0.shape[0] // 2
        return orig(comm, jnp.where(keep[:, None, None], A0, 0), b)

    monkeypatch.setattr(orchestrator, "initial_sweep_state", broken)


def _no_exchange(monkeypatch):
    """The exchange between lanes left out: every lane receives its own
    block instead of its butterfly partner's."""
    from repro.core.comm import SimComm

    monkeypatch.setattr(SimComm, "ppermute", lambda self, x, perm: x)


def _r_altered(monkeypatch):
    """An answer altered where it is produced: one entry of R negated."""
    from repro.ft.online import orchestrator

    orig = orchestrator.finalize

    def broken(comm, s):
        R, factors, bundles = orig(comm, s)
        return R.at[..., 0, 1].multiply(-1.0), factors, bundles

    monkeypatch.setattr(orchestrator, "finalize", broken)


def _heal_altered(monkeypatch):
    """The REBUILD's answer altered: one entry of the rebuilt lane's live
    window is nudged."""
    from repro.ft.online import orchestrator

    orig = orchestrator.recover_lanes

    def broken(comm, state, *a, **kw):
        state, events = orig(comm, state, *a, **kw)
        return state.replace(
            C_local=state.C_local.at[1, -1, -1].add(1e-3)), events

    monkeypatch.setattr(orchestrator, "recover_lanes", broken)


def _r_rotated(monkeypatch):
    """An answer that is not triangular: the first two rows of R turned by
    a plane rotation where R is produced. ``R^T R`` is unchanged, so the
    Gram residual passes, and the failure-free R of set-up is turned alike,
    so the bitwise comparison passes too."""
    from repro.ft.online import orchestrator

    orig = orchestrator.finalize

    def broken(comm, s):
        R, factors, bundles = orig(comm, s)
        r0, r1 = R[..., 0, :], R[..., 1, :]
        R = R.at[..., 0, :].set(0.6 * r0 + 0.8 * r1)
        R = R.at[..., 1, :].set(-0.8 * r0 + 0.6 * r1)
        return R, factors, bundles

    monkeypatch.setattr(orchestrator, "finalize", broken)


FAULTS = [
    ("tsqr_tall.free", _trailing_update_unchanged),
    ("tsqr_tall.free", _half_the_rows),
    ("tsqr_tall.free", _no_exchange),
    ("tsqr_tall.free", _r_altered),
    ("tsqr_tall.free", _r_rotated),
    ("tsqr_tall.kill1", _heal_altered),
]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, f in FAULTS])
def test_fault_turns_correct_false(workload, plant, monkeypatch):
    plant(monkeypatch)
    result = run_tiny(workload)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] >= 1


def test_control_lowers_every_matmul_precision():
    """The control (``readings.py --control``) takes the sweep's matmuls one
    step below HIGHEST: HIGH on XLA's dots, DEFAULT inside the Pallas
    kernels (which have no HIGH). The CPU ignores matmul precision, so the numbers of the
    control can only be read on the chip (PERF.md); here the test shows
    that the lowered precision reaches the timed path's programs."""
    code = r"""
import sys, jax, jax.numpy as jnp
sys.path[:0] = ["src", "."]
from bench import run_cell
print(run_cell.lower_precision())
from repro.kernels import wy_apply, panel_qr, stacked_qr
from repro.core import householder
from repro.core.comm import SimComm
from repro.ft.online.state import initial_sweep_state, run_steps
assert wy_apply._dot.keywords["precision"] == jax.lax.Precision.DEFAULT
assert panel_qr.MATMUL_PRECISION == jax.lax.Precision.DEFAULT
assert stacked_qr.MATMUL_PRECISION == jax.lax.Precision.DEFAULT
assert householder.MATMUL_PRECISION == jax.lax.Precision.HIGH
comm = SimComm(4)
s = initial_sweep_state(comm, jnp.ones((4, 16, 16), jnp.float32), 4)
text = jax.jit(lambda s: run_steps(comm, s, 5)).lower(s).as_text()
assert "HIGHEST" not in text and "HIGH" in text, "precision not lowered"
print("ok")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")

