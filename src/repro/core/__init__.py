"""Core FT-CAQR library (the paper's contribution).

Layers:
  householder - WY/T compact representation substrate
  tsqr        - local chain + distributed baseline-tree / FT-butterfly TSQR
  trailing    - trailing-matrix update, Algorithm 1 (baseline) and 2 (FT)
  caqr        - full panel-sweep FT-CAQR of general matrices
  recovery    - failure injection + single-source REBUILD recovery
  comm        - SPMD/simulated communication abstraction
"""
from repro.core.comm import AxisComm, MeshComm, SimComm
from repro.core.householder import (
    WY,
    StackedQR,
    apply_q,
    apply_qt,
    build_t,
    householder_qr,
    householder_qr_masked,
    q_dense,
    stacked_apply_q,
    stacked_apply_qt,
    stacked_qr,
)
from repro.core.tsqr import (
    ChainFactors,
    DistTSQRFactors,
    baseline_tsqr,
    dist_orthonormalize,
    ft_tsqr,
    ft_tsqr_level,
    ft_tsqr_q,
    local_tsqr,
    local_tsqr_q,
    tsqr_orthonormalize,
)
from repro.core.trailing import (
    RecoveryBundle,
    TrailingLevelStep,
    trailing_combine_level,
    trailing_update_baseline,
    trailing_update_ft,
)
from repro.core.caqr import (
    CAQRResult,
    PanelFactors,
    SweepGeometry,
    assemble_R,
    block_row_layout,
    caqr_apply_qt,
    caqr_apply_qt_batched,
    caqr_factorize,
    caqr_factorize_batched,
    caqr_factorize_spmd,
    lane_geometry,
    pad_to_geometry,
    panel_geometry,
    sweep_geometry,
)
from repro.core import lstsq, recovery

__all__ = [
    "AxisComm", "MeshComm", "SimComm", "WY", "StackedQR", "apply_q", "apply_qt",
    "build_t", "householder_qr", "householder_qr_masked", "q_dense",
    "stacked_apply_q", "stacked_apply_qt", "stacked_qr", "ChainFactors",
    "DistTSQRFactors", "baseline_tsqr", "dist_orthonormalize", "ft_tsqr",
    "ft_tsqr_level", "ft_tsqr_q", "local_tsqr", "local_tsqr_q",
    "tsqr_orthonormalize", "RecoveryBundle", "TrailingLevelStep",
    "trailing_combine_level", "trailing_update_baseline",
    "trailing_update_ft", "CAQRResult", "PanelFactors", "SweepGeometry",
    "assemble_R", "block_row_layout", "caqr_apply_qt",
    "caqr_apply_qt_batched",
    "caqr_factorize", "caqr_factorize_batched", "caqr_factorize_spmd",
    "lane_geometry", "pad_to_geometry", "panel_geometry", "sweep_geometry",
    "recovery", "lstsq",
]
