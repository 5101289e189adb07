"""Fault-tolerant demos: the paper's algorithm and the training loop both
survive lane deaths under each FT-MPI semantics (paper §II), with REBUILD
provably bit-identical to the failure-free run.

Part 1 drives the paper's actual workload — the windowed FT-CAQR sweep —
under a failure schedule via ``repro.ft.driver``: lanes die at scheduled
tree levels of scheduled panels, each is rebuilt from its re-read initial
slice plus single-source buddy fetches, and the finished factorization is
checked bit-for-bit against the failure-free sweep.

Parts 2/3 show the same semantics on the training loop (REBUILD / SHRINK).

Run: PYTHONPATH=src python examples/failure_recovery_training.py [--steps N]
(--steps 8 is the CI smoke setting; default 40 shows real convergence)
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core import SimComm
from repro.data.pipeline import DataConfig
from repro.ft import FailureSchedule, ft_caqr_sweep, sweep_point
from repro.ft.semantics import Semantics
from repro.train import TrainConfig, Trainer

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=40)
args = ap.parse_args()

# === 1. FT-CAQR sweep: lanes die mid-factorization, REBUILD finishes =======
# b=4 / m_loc=8 tiles are the CPU-XLA bitwise-stable envelope (same
# geometry as examples/online_recovery.py), so the bit-identity below is
# asserted, not just printed
P, m_loc, n, b = 4, 8, 32, 4
rng = np.random.default_rng(0)
A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)
comm = SimComm(P)

print(f"=== FT-CAQR sweep: {P*m_loc}x{n}, {n//b} panels, {P} lanes ===")
ref = ft_caqr_sweep(A, comm, b)  # the failure-free run
schedule = FailureSchedule(events={
    sweep_point(2, "trailing", 1): [2],  # mid trailing-combine tree
    sweep_point(5, "tsqr", 0): [1],      # mid TSQR butterfly, later panel
    sweep_point(7, "leaf"): [2],         # same lane dies a second time
})
res = ft_caqr_sweep(A, comm, b, schedule=schedule)
for e in res.events:
    print(f"  death at panel {e.point[0]} ({e.point[1]} level {e.point[2]}): "
          f"lane {e.lane} rebuilt from survivors {e.sources} in "
          f"{e.elapsed_s*1e3:.0f}ms ({len(e.reads)} single-source fetches)")
identical = all(
    np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(
        jax.tree_util.tree_leaves((res.R, res.factors, res.bundles)),
        jax.tree_util.tree_leaves((ref.R, ref.factors, ref.bundles)),
    )
)
print(f"R + factors + bundles bit-identical to failure-free sweep: {identical}")
assert identical, "REBUILD must be bit-identical to the failure-free sweep"

# === 2. training under REBUILD =============================================
cfg = get_smoke("tinyllama-1.1b")
dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=7)
steps = args.steps
fail_step = max(1, steps // 2)

print(f"\n=== reference training run ({steps} steps, no failures) ===")
ref_tr = Trainer(cfg, TrainConfig(steps=steps, lr=8e-3, warmup=5, n_lanes=4,
                                  diskless_every=5, log_every=10), dcfg)
ref_tr.run()

print(f"\n=== REBUILD: lane 2 dies at step {fail_step}, "
      f"restored from its buddy ===")
reb = Trainer(cfg, TrainConfig(steps=steps, lr=8e-3, warmup=5, n_lanes=4,
                               diskless_every=5, log_every=10,
                               semantics=Semantics.REBUILD), dcfg)
reb.run(FailureSchedule(events={fail_step: [2]}))
same = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(ref_tr.state.params),
                    jax.tree_util.tree_leaves(reb.state.params))
)
print(f"REBUILD final params bit-identical to failure-free run: {same}")

# === 3. training under SHRINK ==============================================
print(f"\n=== SHRINK: lane 1 dies at step {max(1, steps // 3)}, "
      f"world shrinks to 3 lanes ===")
shr = Trainer(cfg, TrainConfig(steps=steps, lr=8e-3, warmup=5, n_lanes=4,
                               diskless_every=5, log_every=10,
                               semantics=Semantics.SHRINK), dcfg)
hist = shr.run(FailureSchedule(events={max(1, steps // 3): [1]}))
print(f"continued with {hist[-1]['lanes']} lanes, "
      f"final loss {hist[-1]['loss']:.4f}")
