"""The windowed sweep that ``caqr_factorize`` runs: its reference file.

``caqr_factorize`` is the sweep state machine of ``repro.ft.online.state``
run to completion and compiled as one program; panel ``k``'s trailing
update touches only the live window ``A[:, k*b:]``. Checked here on tall and
square, aligned and kernel-unaligned shapes against float64 references: R
against ``A^T A``, the implicit-Q replay through ``caqr_apply_qt``, and the
recovery bundles through the paper's single-source C' rebuild."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import SimComm, caqr_apply_qt, caqr_factorize


SHAPES = [
    (4, 16, 32, 4),    # tall
    (8, 16, 128, 8),   # square (full target-lane rotation + dead lanes)
    (8, 32, 64, 8),
    (4, 32, 128, 8),   # square, multi-panel per lane
    (2, 48, 48, 12),   # kernel-unaligned b, square
]


def _matrix(rng, P, m_loc, n):
    return jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)


def _gram(A):
    Af = np.asarray(A, np.float64).reshape(-1, A.shape[-1])
    return Af.T @ Af


@pytest.mark.parametrize("P,m_loc,n,b", SHAPES)
def test_windowed_against_lapack_and_gram(rng, P, m_loc, n, b):
    """R is upper triangular, the same on every lane (the FT broadcast),
    and ``R^T R`` equals the float64 ``A^T A``."""
    A = _matrix(rng, P, m_loc, n)
    res = caqr_factorize(A, SimComm(P), b)
    R_all = np.asarray(res.R)
    Rc = R_all[0]
    assert R_all.shape == (P, min(P * m_loc, n), n)
    assert np.all(R_all == Rc)
    assert not np.any(np.tril(Rc, -1))
    Rd = Rc.astype(np.float64)
    G = _gram(A)
    np.testing.assert_allclose(Rd.T @ Rd, G, atol=2e-3 * np.abs(G).max())


@pytest.mark.parametrize("P,m_loc,n,b", SHAPES)
def test_windowed_implicit_q_replay(rng, P, m_loc, n, b):
    """The stored factors replay to an orthogonal Q: ``(Q^T A)^T (Q^T A)``
    equals the float64 ``A^T A``."""
    comm = SimComm(P)
    A = _matrix(rng, P, m_loc, n)
    res = caqr_factorize(A, comm, b)
    QtA = caqr_apply_qt(A, res.factors, comm)
    Qf = np.asarray(QtA, np.float64).reshape(-1, n)
    G = _gram(A)
    np.testing.assert_allclose(Qf.T @ Qf, G, atol=2e-3 * np.abs(G).max())


@pytest.mark.parametrize("P,m_loc,n,b", SHAPES)
def test_windowed_bit_identical_bundles(rng, P, m_loc, n, b):
    """Bundles: zero on each panel's dead columns, and enough to rebuild any
    lane's C' after any tree level from its buddy's bundle alone (paper
    §III-C, ``C' = C_buddy - Y W`` with ``Y = I`` for the top of the pair
    and ``Y2`` for the bottom). The rebuilt C' is the C' the lane enters
    the next level with; after the last level, the target lane's is the
    panel's rows of the float64 R."""
    comm = SimComm(P)
    A = _matrix(rng, P, m_loc, n)
    res = caqr_factorize(A, comm, b, collect_bundles=True)
    bd = res.bundles
    W, C_self, C_buddy, Y2 = (np.asarray(x, np.float64)
                              for x in (bd.W, bd.C_self, bd.C_buddy, bd.Y2))
    was_top = np.asarray(bd.self_was_top)
    n_panels, L = W.shape[:2]
    for name in ("W", "C_self", "C_buddy"):
        bundle = np.asarray(getattr(bd, name))
        for k in range(n_panels):
            assert not np.any(bundle[k][..., :k * b])
    R64 = np.linalg.qr(np.asarray(A, np.float64).reshape(-1, n), "r")
    tol = 1e-4 * np.abs(R64).max()
    for k in range(n_panels):
        for lvl in range(L):
            for lane in range(P):
                src = lane ^ (1 << lvl)
                Y = Y2[k, lvl, src] if was_top[k, lvl, src] else np.eye(b)
                rebuilt = C_buddy[k, lvl, src] - Y @ W[k, lvl, src]
                if lvl + 1 < L:
                    want = C_self[k, lvl + 1, lane]
                elif lane == (k * b) // m_loc:
                    want = R64[k * b:(k + 1) * b]
                    rows = np.arange(b)
                    rebuilt = rebuilt * np.sign(rebuilt[rows, k * b + rows])[:, None]
                    want = want * np.sign(want[rows, k * b + rows])[:, None]
                else:
                    continue
                np.testing.assert_allclose(rebuilt, want, rtol=0, atol=tol)
