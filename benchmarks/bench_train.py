"""Training-runtime benchmarks: what optimizer-internal FT-QR costs a
training step, and what the async double-buffered segment path buys back.

(a) *Boundary cost, sync vs async*: the engine's sweep boundaries each pay
    a detector poll plus segment dispatch. With ``async_segments=True`` the
    orchestrator dispatches the NEXT segment speculatively before the
    boundary's poll result arrives, overlapping dispatch with detection;
    the poll itself is one compiled probe dispatch. Measured as
    per-boundary wall time over a full
    ``orthonormalize`` sweep, interleaved sync/async so box drift cancels.
    The gate demands async strictly cheaper than sync per boundary.

(b) *Step cost, free vs killed*: an FT training step whose optimizer-
    internal sweep loses a lane pays one REBUILD; measured as the killed
    step's wall time against the same step of a failure-free run.

``benchmarks/run.py`` stores the record under ``BENCH_core.json``'s
``"train"`` key, gates BEFORE recording (a regressed run never becomes the
next baseline), and floors the recorded gated ratio at 90% of the previous
baseline so one lucky-fast run cannot ratchet the bar below noise.
``CI_ALLOW_TRAIN_REGRESSION=1`` acknowledges a known regression.
"""
from __future__ import annotations

import os
import statistics
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# gated ratios may regress this much over the recorded baseline before CI
# fails (the async-vs-sync gate is intra-run and absolute: async must
# simply win)
REGRESSION_TOLERANCE = 1.25
# measurement methodology version: bump when the meaning of a gated number
# changes, so the gate re-records instead of comparing incomparables
_METHOD = 1


def _wall_once(fn) -> float:
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return (time.perf_counter() - t0) * 1e6


def _wall(fn, reps: int) -> float:
    return min(_wall_once(fn) for _ in range(reps))


def bench_boundary_cost(quick: bool = False) -> Dict:
    """(a): per-boundary sweep cost, sync vs async double-buffered."""
    from repro.train.ftrun import QREngine

    P, pw, (m, n) = (4, 16, (128, 64)) if quick else (4, 16, (256, 128))
    rng = np.random.default_rng(31)
    M = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    reps = 3 if quick else 5

    def sweep(asynch: bool):
        eng = QREngine(n_lanes=P, panel_width=pw, async_segments=asynch)
        eng.orthonormalize(M)
        return eng

    # compile both paths (segment programs are cached process-wide)
    eng_s, eng_a = sweep(False), sweep(True)
    boundaries = eng_s.boundaries
    assert eng_a.boundaries == boundaries, "async ran a different sweep"

    us_sync = _wall(lambda: sweep(False), reps)
    us_async = _wall(lambda: sweep(True), reps)
    # interleaved ratio: each rep measures async and sync back to back, so
    # slow drift of the box inflates both sides and cancels
    ratio = statistics.median(
        _wall_once(lambda: sweep(True)) / max(_wall_once(lambda: sweep(False)), 1e-9)
        for _ in range(reps)
    )
    return {
        "method": _METHOD,
        "config": {"P": P, "panel_width": pw, "m": m, "n": n, "quick": quick,
                   "boundaries": boundaries},
        "us_sync_sweep": us_sync,
        "us_async_sweep": us_async,
        "us_sync_per_boundary": us_sync / boundaries,
        "us_async_per_boundary": us_async / boundaries,
        "async_vs_sync": ratio,
    }


def bench_step_cost(quick: bool = False) -> Dict:
    """(b): FT training step wall time, failure-free vs a lane killed
    inside the step's optimizer-internal sweep (one REBUILD)."""
    from repro.configs import get_smoke
    from repro.data.pipeline import DataConfig
    from repro.ft.semantics import Semantics
    from repro.train.loop import TrainConfig
    from repro.train.ftrun import FTTrainer, StepSweepKiller

    steps = 3 if quick else 4
    kill_step = 1
    cfg = get_smoke("tinyllama-1.1b")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=1)
    tcfg = TrainConfig(steps=steps, lr=1e-2, warmup=2, n_lanes=4,
                       diskless_every=steps + 1, log_every=10_000,
                       semantics=Semantics.REBUILD, optimizer="caqr_muon")

    free = FTTrainer(cfg, tcfg, dcfg)
    hist_free = free.run()
    killer = StepSweepKiller(at_step=kill_step, lane=2)
    killed = FTTrainer(cfg, tcfg, dcfg, qr_fault_hooks=[killer])
    hist_kill = killed.run()
    assert killer.fired, "the kill never landed inside the optimizer sweep"
    assert [h["loss"] for h in hist_free] == [h["loss"] for h in hist_kill], \
        "killed run is not bitwise-identical to failure-free"

    us_free = hist_free[kill_step]["dt"] * 1e6
    us_kill = hist_kill[kill_step]["dt"] * 1e6
    # steady-state floor: the cheapest post-compile step of the free run
    us_steady = min(h["dt"] for h in hist_free[1:]) * 1e6
    return {
        "config": {"steps": steps, "kill_step": kill_step, "quick": quick},
        "us_step_free": us_free,
        "us_step_killed": us_kill,
        "us_step_steady": us_steady,
        "us_rebuild_delta": us_kill - us_free,
        "kill_vs_free": us_kill / max(us_free, 1e-9),
    }


def suite(quick: bool = False) -> Dict:
    return {
        "boundary": bench_boundary_cost(quick),
        "step": bench_step_cost(quick),
    }


def check_regression(train: Dict, baseline: Optional[Dict]) -> Tuple[bool, str]:
    """Gate for ``run.py``/``ci.sh``. One intra-run absolute — the async
    double-buffered path must be strictly cheaper per boundary than sync —
    plus a baseline gate on the per-boundary sync cost (same quick-tier
    only).
    ``CI_ALLOW_TRAIN_REGRESSION=1`` acknowledges a failure without
    greening it."""
    allow = os.environ.get("CI_ALLOW_TRAIN_REGRESSION") == "1"
    av = train["boundary"]["async_vs_sync"]
    if av >= 1.0:
        msg = (f"async segments are NOT cheaper than sync per boundary "
               f"({av:.2f}x, must be < 1.0)")
        return (True, msg + " — acknowledged via CI_ALLOW_TRAIN_REGRESSION=1"
                ) if allow else (False, msg)
    got = train["boundary"]["us_sync_per_boundary"]
    if not baseline:
        return True, (f"train async {av:.2f}x, boundary "
                      f"{got:.0f}us (no baseline recorded yet)")
    base_b = baseline.get("boundary", {})
    comparable = (base_b.get("config", {}).get("quick")
                  == train["boundary"]["config"]["quick"]
                  and base_b.get("method") == train["boundary"]["method"])
    if not comparable:
        return True, (f"train async {av:.2f}x (baseline "
                      "from the other tier/method; not comparable)")
    base = base_b["us_sync_per_boundary"]
    if got <= base * REGRESSION_TOLERANCE:
        return True, (f"train async {av:.2f}x, boundary "
                      f"{got:.0f}us vs baseline {base:.0f}us: OK")
    msg = (f"train per-boundary cost REGRESSED: {got:.0f}us vs baseline "
           f"{base:.0f}us (> {REGRESSION_TOLERANCE:.2f}x tolerance)")
    if allow:
        return True, msg + " — acknowledged via CI_ALLOW_TRAIN_REGRESSION=1"
    return False, msg


def baseline_to_record(train: Dict, baseline: Optional[Dict]) -> Dict:
    """What a passing run persists: the fresh measurement with the gated
    per-boundary cost floored at 90% of the previous comparable baseline
    (one lucky-fast run cannot set a bar ordinary runs miss by noise)."""
    import copy

    rec = copy.deepcopy(train)
    if not baseline:
        return rec
    base_b = baseline.get("boundary", {})
    comparable = (base_b.get("config", {}).get("quick")
                  == train["boundary"]["config"]["quick"]
                  and base_b.get("method") == train["boundary"]["method"])
    if comparable:
        rec["boundary"]["us_sync_per_boundary"] = max(
            train["boundary"]["us_sync_per_boundary"],
            base_b["us_sync_per_boundary"] * 0.9,
        )
    return rec


def main() -> None:
    import json

    print(json.dumps(suite(quick=False), indent=1))


if __name__ == "__main__":
    main()
