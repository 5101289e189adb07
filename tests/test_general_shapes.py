"""General-shape FT-CAQR: the differential property harness.

The paper is titled *Fault Tolerant QR Factorization for General Matrices* —
this file is where "general" is enforced. Every shape class the padded
``sweep_geometry`` unlocks (ragged last panel, unaligned lane heights, wide
matrices, degenerate tiny problems) is run differentially against
``numpy.linalg.qr`` (sign-fixed R), the Gram identity, and the implicit-Q
replay's orthogonality, with and without bundles, plus the batched
front-end.

Two tiers live here:

* a deterministic case matrix that always runs (tier-1 — it must pass on a
  bare image);
* a hypothesis-driven harness drawing random ``(P, m_loc, n, b, scale)``
  tuples, which runs whenever hypothesis is importable. It is NOT hidden
  behind a silent module-level ``importorskip``: the deterministic tier
  keeps running without hypothesis, and ``tools/ci.sh`` fails loudly when
  hypothesis is absent so the property tier cannot silently vanish from CI.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    SimComm,
    caqr_apply_qt,
    caqr_apply_qt_batched,
    caqr_factorize,
    caqr_factorize_batched,
    pad_to_geometry,
    sweep_geometry,
)
from repro.core.lstsq import caqr_lstsq
from sweep_reference import eager_sweep

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # noqa: SIM105 — ci.sh gates this; tier-1 keeps running
    HAVE_HYPOTHESIS = False


def _signfix(R):
    """Canonical row signs: multiply each row by the sign of its diagonal."""
    s = np.sign(np.diag(R))
    s = np.where(s == 0, 1.0, s)
    return R * s[:, None]


def _check_general_shape(P, m_loc, n, b, scale=1.0, seed=0,
                         collect_bundles=False):
    """The differential oracle: one general-shape factorization, checked
    against numpy's QR (sign-fixed), the Gram identity, and the replayed
    implicit Q's orthogonality."""
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((P, m_loc, n)) * scale).astype(np.float32)
    comm = SimComm(P)
    res = caqr_factorize(jnp.asarray(A), comm, b,
                         collect_bundles=collect_bundles)

    Af = A.reshape(-1, n)
    K = min(P * m_loc, n)
    assert res.R.shape == (P, K, n)
    R = np.asarray(res.R[0])
    # FT broadcast property: R replicated bit-identically on every lane
    assert np.all(np.asarray(res.R) == R)

    # differential vs LAPACK (sign-fixed rows; both upper trapezoidal)
    R_ref = np.linalg.qr(Af, mode="r")
    tol = 5e-3 * max(np.abs(R_ref).max(), 1e-30)
    np.testing.assert_allclose(_signfix(R), _signfix(R_ref), rtol=0, atol=tol)

    # Gram identity: R^T R == A^T A (sign-independent)
    G = Af.T @ Af
    gtol = 3e-3 * max(np.abs(G).max(), 1e-30)
    np.testing.assert_allclose(R.T @ R, G, rtol=0, atol=gtol)

    # implicit-Q replay orthogonality: (Q^T A)^T (Q^T A) == A^T A — the
    # apply returns the padded-row layout; zero pad rows do not perturb
    # the Gram product
    QtA = np.asarray(caqr_apply_qt(jnp.asarray(A), res.factors, comm))
    Qf = QtA.reshape(-1, n)
    np.testing.assert_allclose(Qf.T @ Qf, G, rtol=0, atol=gtol)
    return res


# Deterministic case matrix (always runs): every shape class by name.
CASES = {
    "aligned-tall": (4, 8, 16, 4),
    "ragged-panel": (4, 8, 10, 4),       # n % b != 0
    "ragged-lanes": (4, 6, 8, 4),        # m_loc % b != 0
    "ragged-both": (4, 6, 10, 4),
    "wide": (4, 4, 40, 4),               # n > P*m_loc
    "wide-ragged": (4, 3, 21, 4),
    "square-unaligned": (2, 5, 10, 4),   # n == m, neither aligned
    "single-column": (2, 4, 1, 4),       # n = 1
    "b-wider-than-n": (2, 8, 3, 8),      # b > n
    "short-lanes": (4, 2, 6, 4),         # m_loc < b
}


@pytest.mark.parametrize("shape", CASES.values(), ids=CASES.keys())
def test_general_shapes_scan_sweep(shape):
    """Without bundles: the compiled sweep computes none."""
    res = _check_general_shape(*shape)
    assert res.bundles is None


@pytest.mark.parametrize(
    "shape",
    [CASES[k] for k in ("ragged-both", "wide-ragged", "short-lanes")],
    ids=["ragged-both", "wide-ragged", "short-lanes"],
)
def test_general_shapes_windowed_sweep(shape):
    """With bundles collected, the same general shapes: the bundles live
    at the padded geometry, one per panel."""
    res = _check_general_shape(*shape, collect_bundles=True)
    geom = sweep_geometry(shape[0], shape[1], shape[2], shape[3])
    assert res.bundles.W.shape[0] == geom.n_panels
    assert res.bundles.W.shape[-1] == geom.n_work


def test_scales_do_not_break_raggedness():
    for scale in (1e-3, 1e3):
        _check_general_shape(4, 6, 10, 4, scale=scale, seed=7)


def test_ragged_equals_explicitly_padded_aligned_bitwise():
    """The contract behind the whole refactor, stated bitwise: factorizing a
    ragged matrix IS factorizing its zero-padded aligned embedding — same
    ops, same floats. (This is also what pins the aligned path to the seed:
    aligned inputs take the identical code with zero padding elided.) Both
    run the sweep stepped eagerly, so the same ops run on the same floats
    whatever XLA would fuse around a pad inside one compiled program."""
    P, m_loc, n, b = 4, 6, 10, 4
    rng = np.random.default_rng(11)
    A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)
    comm = SimComm(P)
    geom = sweep_geometry(P, m_loc, n, b)
    A_pad = pad_to_geometry(comm, A, geom)
    assert A_pad.shape == (P, geom.m_loc_pad, geom.n_work)

    ragged = eager_sweep(A, comm, b)
    aligned = eager_sweep(A_pad, comm, b)
    # R: the ragged result is the [:k, :n] slice of the aligned assembly
    assert np.array_equal(
        np.asarray(ragged.R), np.asarray(aligned.R)[:, :geom.k, :n]
    )
    # factors and bundles: bit-identical trees (both live in padded space)
    for g, r in zip(
        jax.tree_util.tree_leaves((ragged.factors, ragged.bundles)),
        jax.tree_util.tree_leaves((aligned.factors, aligned.bundles)),
    ):
        assert np.array_equal(np.asarray(g), np.asarray(r))


def test_ragged_against_float64(rng):
    """The ragged geometry (unaligned lanes and a ragged last panel) against
    the float64 Gram matrix and float64 LAPACK R."""
    P, m_loc, n, b = 4, 6, 10, 4
    comm = SimComm(P)
    A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)
    R = np.asarray(caqr_factorize(A, comm, b).R[0], np.float64)
    Af = np.asarray(A, np.float64).reshape(-1, n)
    G = Af.T @ Af
    np.testing.assert_allclose(R.T @ R, G, rtol=0, atol=1e-5 * np.abs(G).max())
    np.testing.assert_allclose(_signfix(R), _signfix(np.linalg.qr(Af, "r")),
                               rtol=0, atol=1e-5 * np.abs(R).max())


def test_batched_vmap_front_end(rng):
    """A stack of ragged problems through one vmapped sweep equals the
    per-problem loop, and the batched Q^T replay conforms."""
    batch, P, m_loc, n, b = 3, 4, 6, 10, 4
    comm = SimComm(P)
    A = jnp.asarray(rng.standard_normal((batch, P, m_loc, n)), jnp.float32)
    res = caqr_factorize_batched(A, comm, b)
    assert res.R.shape == (batch, P, min(P * m_loc, n), n)
    for i in range(batch):
        one = caqr_factorize(A[i], comm, b)
        np.testing.assert_allclose(
            np.asarray(res.R[i]), np.asarray(one.R), rtol=2e-5, atol=2e-5
        )
    QtA = caqr_apply_qt_batched(A, res.factors, comm)
    for i in range(batch):
        Qf = np.asarray(QtA[i]).reshape(-1, n)
        Af = np.asarray(A[i]).reshape(-1, n)
        G = Af.T @ Af
        np.testing.assert_allclose(
            Qf.T @ Qf, G, atol=3e-3 * np.abs(G).max()
        )


def test_sweep_geometry_invariants():
    """The static geometry rules the padding correctness rests on."""
    for P in (2, 4, 8):
        for m_loc in (1, 2, 5, 6, 8):
            for n in (1, 3, 10, 16, 40):
                for b in (1, 3, 4, 8):
                    g = sweep_geometry(P, m_loc, n, b)
                    assert g.m_loc_pad % b == 0 and g.m_loc_pad >= b
                    assert g.m_loc_pad >= m_loc
                    assert g.k == min(P * m_loc, n)
                    assert g.n_panels * b >= g.k
                    assert g.n_panels * b <= P * g.m_loc_pad
                    assert g.n_work >= max(n, g.n_panels * b)
                    if m_loc % b == 0 and n % b == 0 and n <= P * m_loc:
                        assert g.aligned


# ---------------------------------------------------------------------------
# Hypothesis tier: random shapes drawn from the full general-shape space.
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        p_pow=st.integers(1, 3),
        m_loc=st.integers(1, 12),
        n=st.integers(1, 24),
        b=st.sampled_from([1, 2, 3, 4, 8]),
        scale=st.sampled_from([1e-3, 1.0, 1e3]),
        seed=st.integers(0, 2**16),
    )
    def test_general_shape_differential_harness(p_pow, m_loc, n, b, scale, seed):
        """Random (m, n, b, P, scale) including ragged/wide/tiny degenerate
        shapes: sign-fixed R vs numpy, Gram identity, Q^T orthogonality."""
        _check_general_shape(2**p_pow, m_loc, n, b, scale=scale, seed=seed)

    @settings(max_examples=10, deadline=None)
    @given(
        m_loc=st.integers(2, 10),
        n=st.integers(1, 20),
        rhs=st.integers(1, 3),
        seed=st.integers(0, 2**16),
    )
    def test_lstsq_differential_harness(m_loc, n, rhs, seed):
        """caqr_lstsq vs numpy.linalg.lstsq on random general shapes (basic
        solution on wide problems: trailing components pinned to zero)."""
        P, b = 4, 4
        rng = np.random.default_rng(seed)
        A = rng.standard_normal((P, m_loc, n)).astype(np.float32)
        bv = rng.standard_normal((P, m_loc, rhs)).astype(np.float32)
        x = np.asarray(caqr_lstsq(jnp.asarray(A), jnp.asarray(bv),
                                  SimComm(P), b))
        K = min(P * m_loc, n)
        Af, bf = A.reshape(-1, n), bv.reshape(-1, rhs)
        if K == n:  # tall: unique LS solution
            x_ref, *_ = np.linalg.lstsq(Af, bf, rcond=None)
            np.testing.assert_allclose(x, x_ref, rtol=5e-2, atol=5e-3)
        else:  # wide: basic solution solves the system exactly
            assert np.all(x[K:] == 0)
            np.testing.assert_allclose(
                Af @ x, bf, rtol=0,
                atol=5e-4 * max(np.abs(bf).max(), 1.0),
            )
else:

    @pytest.mark.skip(reason="hypothesis not installed — deterministic tier "
                             "above still ran; tools/ci.sh fails loudly here")
    def test_general_shape_differential_harness():
        pass
