"""Quickstart: FT-CAQR of a general matrix + recovery from a lane failure.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import SimComm, caqr_factorize, ft_tsqr
from repro.core import recovery as rec

# --- 1. QR of a general matrix, distributed over 8 lanes -------------------
P, m_loc, n, b = 8, 64, 256, 16
rng = np.random.default_rng(0)
A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)

res = caqr_factorize(A, SimComm(P), panel_width=b)
R = np.asarray(res.R[0])
R_ref = np.linalg.qr(np.asarray(A).reshape(-1, n), mode="r")
err = np.abs(np.abs(R) - np.abs(R_ref)).max() / np.abs(R_ref).max()
print(f"FT-CAQR of {P*m_loc}x{n} matrix on {P} lanes: |R - R_lapack| rel = {err:.2e}")
print(f"R replicated on all lanes: {bool(np.all(np.asarray(res.R) == R))}")

# --- 2. kill a lane mid-update; recover from ONE buddy ----------------------
comm = SimComm(P)
panel = jnp.asarray(rng.standard_normal((P, m_loc, b)), jnp.float32)
trailing = jnp.asarray(rng.standard_normal((P, m_loc, 32)), jnp.float32)
fac = ft_tsqr(panel, comm)
clean = rec.run_ft_trailing(trailing, fac, comm)
faulty = rec.run_ft_trailing(
    trailing, fac, comm, fail_at_level=1, failed_lane=3, A_stacked=trailing
)
print(f"recovery after killing lane 3 at tree level 1: "
      f"bitwise-equal={np.array_equal(np.asarray(clean), np.asarray(faulty))}")

# --- 3. the full sweep under a failure schedule (end-to-end REBUILD) --------
from repro.ft import FailureSchedule, ft_caqr_sweep, sweep_point

n_small = 64  # 4 panels, 3 tree levels
A_small = A[:, :, :n_small]
ref = ft_caqr_sweep(A_small, SimComm(P), panel_width=b)
schedule = FailureSchedule(events={
    sweep_point(1, "trailing", 2): [3],   # lane 3 dies mid trailing tree
    sweep_point(3, "tsqr", 0): [5],       # lane 5 dies mid TSQR, last panel
})
res_ft = ft_caqr_sweep(A_small, SimComm(P), panel_width=b, schedule=schedule)
print(f"sweep with {len(res_ft.events)} lane deaths: R bitwise-equal to "
      f"failure-free={np.array_equal(np.asarray(res_ft.R), np.asarray(ref.R))}")
for e in res_ft.events:
    print(f"  panel {e.point[0]} {e.point[1]} level {e.point[2]}: lane "
          f"{e.lane} rebuilt from survivors {e.sources} "
          f"({len(e.reads)} single-source fetches, {e.elapsed_s*1e3:.0f}ms)")
