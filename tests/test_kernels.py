"""Per-kernel shape sweeps vs the pure-jnp oracles, across execution routes.

The per-op policy (DESIGN.md §10) gives every op three executions: compiled
(engine ``pallas`` on TPU, else ``xla`` — a static policy), the Pallas
interpreter, and the jnp oracle. The sweeps here force each non-oracle mode
in turn and gate it against the oracle at ``ref.tolerances(dtype)``; the
ragged parity matrix adds odd/unaligned shapes and bf16. Native-pallas
cells run only where the policy runs Pallas (loud skip elsewhere).

Stacked-op inputs are QR-derived R factors, not raw ``triu`` of a Gaussian:
a random upper-triangular matrix is exponentially ill-conditioned (cond
~1e17 at b=64), which would turn an honest reduction-order difference
between two routes into O(1) output differences and gate nothing.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import backend, ops, ref

MODES = [backend.MODE_COMPILED, backend.MODE_INTERPRET]


@pytest.fixture(params=MODES)
def route(request):
    """Force every op to one execution mode; restore the automatic policy."""
    backend.force_mode(request.param)
    yield request.param
    backend.force_mode(None)


def _allclose(a, b, dtype=jnp.float32, scale=1.0):
    rtol, atol = ref.tolerances(dtype)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol * scale, atol=atol * scale)


def _qr_factor(rng, b, dtype=jnp.float32):
    """A realistically-conditioned upper-triangular b x b R factor."""
    return jnp.asarray(
        np.linalg.qr(rng.standard_normal((2 * b, b)))[1], dtype)


@pytest.mark.parametrize("m,b", [(32, 8), (64, 16), (256, 32), (128, 128)])
@pytest.mark.parametrize("row_start", [0, 8])
def test_panel_qr_sweep(rng, route, m, b, row_start):
    A = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    _allclose(ops.panel_qr(A, row_start), ref.panel_qr(A, row_start))


@pytest.mark.parametrize("b", [8, 16, 64, 128])
def test_stacked_qr_sweep(rng, route, b):
    R1 = _qr_factor(rng, b)
    R2 = _qr_factor(rng, b)
    _allclose(ops.stacked_qr(R1, R2), ref.stacked_qr(R1, R2))


@pytest.mark.parametrize("m,b,n", [(64, 16, 48), (256, 32, 300), (128, 64, 64)])
def test_wy_apply_sweep(rng, route, m, b, n):
    Y = jnp.asarray(rng.standard_normal((m, b)), jnp.float32) * 0.1
    T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    C = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    _allclose(ops.wy_apply(Y, T, C, block_n=64), ref.wy_apply(Y, T, C))


@pytest.mark.parametrize("b,n", [(16, 40), (32, 128), (64, 96)])
def test_stacked_apply_sweep(rng, route, b, n):
    Y2 = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    Ct = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    Cb = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    _allclose(
        ops.stacked_apply(Y2, T, Ct, Cb, block_n=32),
        ref.stacked_apply(Y2, T, Ct, Cb),
    )


# -- the parity matrix: route x dtype on odd/ragged shapes -------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("m,b,n", [(30, 12, 17), (9, 5, 11), (37, 12, 25)])
def test_parity_matrix_ragged(rng, route, dtype, m, b, n):
    """Every op, every non-oracle route, f32 AND bf16, at shapes that
    exercise the full padding contract (odd rows, unaligned widths)."""
    A = jnp.asarray(rng.standard_normal((m, b)), dtype)
    _allclose(ops.panel_qr(A, 0), ref.panel_qr(A, 0), dtype=dtype)

    R1, R2 = _qr_factor(rng, b, dtype), _qr_factor(rng, b, dtype)
    _allclose(ops.stacked_qr(R1, R2), ref.stacked_qr(R1, R2), dtype=dtype)

    Y = jnp.asarray(rng.standard_normal((m, b)), dtype) * 0.1
    T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), dtype)) * 0.1
    C = jnp.asarray(rng.standard_normal((m, n)), dtype)
    _allclose(ops.wy_apply(Y, T, C), ref.wy_apply(Y, T, C), dtype=dtype)

    Ct = jnp.asarray(rng.standard_normal((b, n)), dtype)
    Cb = jnp.asarray(rng.standard_normal((b, n)), dtype)
    _allclose(ops.stacked_apply(T, T, Ct, Cb),
              ref.stacked_apply(T, T, Ct, Cb), dtype=dtype)

    from repro.kernels import fused_sweep as _fused

    W = jnp.asarray(rng.standard_normal((m, b + 7)), dtype)
    _allclose(ops.panel_qr_apply(W, 0, b),
              _fused.panel_qr_apply_ref(W, 0, b), dtype=dtype)


@pytest.mark.parametrize("op", backend.OPS)
def test_native_pallas_parity(rng, op):
    """The pallas engine itself, where the static policy runs it (TPU;
    skipped elsewhere — tools/kernel_smoke.py reports which, loudly)."""
    if backend.compiled_engine(op) != backend.ENGINE_PALLAS:
        pytest.skip(f"the policy runs no native Pallas for {op} here")
    backend.force_mode(backend.MODE_COMPILED, op)
    try:
        if op == "panel_qr":
            A = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
            _allclose(ops.panel_qr(A, 0), ref.panel_qr(A, 0))
        elif op == "stacked_qr":
            R1, R2 = _qr_factor(rng, 16), _qr_factor(rng, 16)
            _allclose(ops.stacked_qr(R1, R2), ref.stacked_qr(R1, R2))
        elif op == "wy_apply":
            Y = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32) * 0.1
            T = jnp.triu(jnp.asarray(rng.standard_normal((8, 8)),
                                     jnp.float32)) * 0.1
            C = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
            _allclose(ops.wy_apply(Y, T, C), ref.wy_apply(Y, T, C))
        elif op == "stacked_apply":
            T = jnp.triu(jnp.asarray(rng.standard_normal((8, 8)),
                                     jnp.float32)) * 0.1
            Ct = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            Cb = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            _allclose(ops.stacked_apply(T, T, Ct, Cb),
                      ref.stacked_apply(T, T, Ct, Cb))
        else:  # fused_sweep
            from repro.kernels import fused_sweep as _fused

            W = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
            _allclose(ops.panel_qr_apply(W, 0, 8),
                      _fused.panel_qr_apply_ref(W, 0, 8))
    finally:
        backend.force_mode(None, op)


def test_kernel_panel_consistency_with_core(rng):
    """Kernel output plugs into the same WY algebra as the core path."""
    from repro.core.householder import apply_qt

    A = jnp.asarray(rng.standard_normal((96, 16)), jnp.float32)
    Y, T, R = ops.panel_qr(A, 0)
    QtA = apply_qt(Y, T, A)
    np.testing.assert_allclose(np.asarray(QtA[:16]), np.asarray(R), atol=3e-5)
    assert np.abs(np.asarray(QtA[16:])).max() < 3e-5
