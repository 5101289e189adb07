"""Online recovery demo: a lane dies at a wall-clock-chosen moment, the
NaN-sentinel detector *discovers* it (nothing is scripted into the traced
program), the orchestrator synthesizes the REBUILD, and the finished
factorization is bit-identical to the failure-free sweep.

This is the paper's actual execution model (§II): failures happen at
arbitrary runtime moments and survivors find out at the next collective —
contrast with ``examples/failure_recovery_training.py``, where deaths are
scheduled at trace time. The sweep runs as compiled ``sweep_step`` segments
under host control (``repro.ft.online``); between segments the host polls
the detector and repairs whatever it finds.

Also shown: suspending the factorization mid-sweep to an ``.npz``
(``repro.ckpt.save_sweep_state``) and resuming it in a fresh state machine.

Run: PYTHONPATH=src python examples/online_recovery.py [--after-ms N]
(--after-ms picks the wall-clock kill deadline; 0 = first boundary, the CI
smoke setting)
"""
import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import load_sweep_state, save_sweep_state
from repro.core import SimComm
from repro.ft import SweepOrchestrator
from repro.ft.online.detect import NaNSentinelDetector, WallClockKiller
from repro.ft.online.state import initial_sweep_state, sweep_step

ap = argparse.ArgumentParser()
ap.add_argument("--after-ms", type=float, default=0.0,
                help="wall-clock delay before the injected lane death")
args = ap.parse_args()

# b=4 tiles: the bitwise-equality envelope documented in DESIGN.md §8 —
# at larger tiles CPU XLA may reassociate batched gemms and REBUILD is
# then only numerically (not bitwise) identical
P, m_loc, n, b = 4, 8, 32, 4
rng = np.random.default_rng(0)
A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)
comm = SimComm(P)

print(f"=== online FT-CAQR: {P*m_loc}x{n}, {n//b} panels, {P} lanes, "
      f"kill lane 2 after ~{args.after_ms:.0f}ms of wall clock ===")
ref = SweepOrchestrator(A, comm, b).run()  # the failure-free run

killer = WallClockKiller(after_s=args.after_ms / 1e3, lane=2)
orch = SweepOrchestrator(A, comm, b, detector=NaNSentinelDetector(),
                         fault_hooks=[killer])
res = orch.run()

print(f"ran {orch.segments_run} compiled segments; "
      f"death struck after point {killer.struck_at}")
for e in res.events:
    print(f"  detected at panel {e.point[0]} ({e.point[1]} level {e.point[2]}):"
          f" lane {e.lane} rebuilt from survivors {e.sources} in"
          f" {e.elapsed_s*1e3:.0f}ms ({len(e.reads)} single-source fetches)")
identical = all(
    np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(
        jax.tree_util.tree_leaves((res.R, res.factors, res.bundles)),
        jax.tree_util.tree_leaves((ref.R, ref.factors, ref.bundles)),
    )
)
print(f"R + factors + bundles bit-identical to failure-free sweep: {identical}")
assert identical and len(res.events) == 1

# === suspend / resume ======================================================
print("\n=== suspend mid-sweep, resume from the .npz ===")
state = initial_sweep_state(comm, A, b)
for _ in range(9):
    state = sweep_step(comm, state)
with tempfile.TemporaryDirectory() as d:
    path = save_sweep_state(os.path.join(d, "sweep"), state)
    kb = os.path.getsize(path) / 1024
    print(f"suspended at cursor {state.cursor} -> {kb:.0f} KiB on disk")
    resumed = SweepOrchestrator.from_state(load_sweep_state(path), comm).run()
same = all(
    np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(resumed.R),
                    jax.tree_util.tree_leaves(ref.R)))
print(f"resumed factorization bit-identical to uninterrupted run: {same}")
assert same
