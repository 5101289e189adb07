"""Pallas kernels for the CAQR compute hot-spots.

panel_qr    - Householder panel factorization (geqrt) in VMEM
stacked_qr  - TSQR tree combine (tpqrt) + fused trailing combine
wy_apply    - fused compact-WY application C - Y (T^T (Y^T C))

ops.py is the dispatch seam ``repro.core`` routes through: wrappers that
resolve the per-op execution policy (compiled pallas / compiled xla /
interpret / oracle — backend.py holds the static engine policy), pad
up to the pallas engines' alignment contract, consult the autotune.py
block-shape cache, and fall back to the pure-jnp oracles in ref.py.
See DESIGN.md §2 and §10.
"""
from repro.kernels import autotune, backend, ops, ref

__all__ = ["autotune", "backend", "ops", "ref"]
