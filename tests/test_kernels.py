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


# Row starts by name: mid-tile (off every 8- and 128-row boundary), negative
# (counts as 0, every row live: a lane whose rows all lie past the panel's
# diagonal) and the last legal one, m - b.
_ROW_STARTS = {"mid": lambda m, b: (m - b) // 2 + 3,
               "neg": lambda m, b: -5,
               "last": lambda m, b: m - b}


@pytest.mark.parametrize("m,b,zero_col", [
    pytest.param(32, 8, None, id="32-8"),
    pytest.param(64, 16, None, id="64-16"),
    pytest.param(256, 32, None, id="256-32"),
    pytest.param(128, 128, None, id="128-128"),
    # heights of 3 and 5 lane tiles, one with a degenerate all-zero column
    pytest.param(384, 128, None, id="384-128"),
    pytest.param(384, 128, 5, id="384-128-zero5"),
    pytest.param(640, 128, 70, id="640-128-zero70"),
])
@pytest.mark.parametrize("row_start", [0, 8, "mid", "neg", "last"])
def test_panel_qr_sweep(rng, route, m, b, zero_col, row_start):
    A = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    if zero_col is not None:
        A = A.at[:, zero_col].set(0.0)
    if row_start in _ROW_STARTS:
        row_start = _ROW_STARTS[row_start](m, b)
    _allclose(ops.panel_qr(A, row_start),
              ref.panel_qr(A, max(row_start, 0)))


def test_panel_qr_vmap_is_unbatched_bitwise(rng):
    """The sweep's vmapped call and the heal's unbatched calls run one
    kernel body, bit for bit: a healed lane's R equals the failure-free
    lane's only if they do."""
    from repro.kernels import panel_qr as _panel

    lanes, m, b = 16, 256, 128
    A = jnp.asarray(rng.standard_normal((lanes, m, b)), jnp.float32)
    rs = jnp.asarray(rng.integers(0, m - b + 1, lanes), jnp.int32)
    call = lambda a, r: _panel.panel_qr(a, r, interpret=True)
    batched = jax.vmap(call)(A, rs)
    for i in range(lanes):
        for got, want in zip(call(A[i], rs[i]), batched):
            assert np.array_equal(np.asarray(got), np.asarray(want[i]))


def test_update_rows_by_tile_height():
    """The column update's step height is a pure function of the tile's
    static shape: 32 rows of the benchmark's 15744-row leaf tile, the whole
    tile for the tree combine's 256 rows and the service's 1024."""
    from repro.kernels.panel_qr import update_rows

    assert update_rows(15744, 128) == 32
    assert update_rows(16384, 128) == 32
    assert update_rows(8192, 128) == 64
    assert update_rows(1024, 128) == 128
    assert update_rows(256, 128) == 128
    assert update_rows(256, 128, sub=16) == 128
    assert update_rows(16, 8) == 8
    assert update_rows(40, 12) == 16


def test_panel_qr_update_step_changes_no_bit(rng, monkeypatch):
    """Stepping the update slab by slab, as taller tiles do, gives the
    same bits as one whole-tile step: the step decides only which finished
    rows are updated for nothing."""
    from repro.kernels import panel_qr as _panel

    m, b = 512, 128
    A = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    rs = jnp.asarray(37, jnp.int32)
    whole = _panel.panel_qr(A, rs, interpret=True)
    monkeypatch.setattr(_panel, "_UPDATE_VREGS", 1)
    assert _panel.update_rows(m, b) == 8
    jax.clear_caches()
    try:
        stepped = _panel.panel_qr(A, rs, interpret=True)
    finally:
        jax.clear_caches()
    for got, want in zip(stepped, whole):
        assert np.array_equal(np.asarray(got), np.asarray(want))


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
        else:  # stacked_apply
            T = jnp.triu(jnp.asarray(rng.standard_normal((8, 8)),
                                     jnp.float32)) * 0.1
            Ct = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            Cb = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            _allclose(ops.stacked_apply(T, T, Ct, Cb),
                      ref.stacked_apply(T, T, Ct, Cb))
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
