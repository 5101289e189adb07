"""Plain float64 references on the host. Numpy only: nothing here imports
the program or takes anything it computed besides the answer under test."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def gram(blocks: Iterable[np.ndarray]) -> np.ndarray:
    """``A^T A`` in float64, summed over row blocks of ``A``."""
    G = None
    for blk in blocks:
        b64 = np.asarray(blk, np.float64)
        G = b64.T @ b64 if G is None else G + b64.T @ b64
    return G


def gram_residual(R: np.ndarray, G: np.ndarray) -> float:
    """``||R^T R - A^T A||_F / ||A||_F^2`` with ``G = A^T A``: zero for an
    exact R, independent of the conditioning of A and of the signs a QR
    chooses for its rows."""
    R64 = np.asarray(R, np.float64)
    return float(np.linalg.norm(R64.T @ R64 - G) / np.trace(G))


def below_diagonal(R: np.ndarray) -> float:
    """``max |R[i, j]|, i > j`` over ``max |R|``: zero for an upper
    triangular R. The Gram residual cannot see a rotation of R; with this
    at zero, the two pin R down to the signs of its rows."""
    R64 = np.abs(np.asarray(R, np.float64))
    return float(np.tril(R64, -1).max(initial=0.0) / R64.max())


def lstsq_error(A: np.ndarray, rhs: np.ndarray, x: Optional[np.ndarray]
                ) -> float:
    """``||x - x_ref||_F / ||x_ref||_F`` against ``numpy.linalg.lstsq`` in
    float64; infinite when no solution came back."""
    if x is None:
        return float("inf")
    x_ref, *_ = np.linalg.lstsq(np.asarray(A, np.float64),
                                np.asarray(rhs, np.float64), rcond=None)
    return float(np.linalg.norm(np.asarray(x, np.float64) - x_ref)
                 / np.linalg.norm(x_ref))
