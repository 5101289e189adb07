"""The kernel-dispatch seam: padding fallback at unaligned shapes, parity of
the dispatched core entry points against the pure-jnp path, and the
backend-aware interpret default (satellites of the windowed-sweep PR)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import householder as hh
from repro.kernels import backend, ops, ref


@pytest.fixture
def forced_kernels():
    """Force the core->kernel dispatch on (padding path runs on CPU in
    interpret mode), restoring the automatic policy afterwards."""
    backend.use_kernels(True)
    yield
    backend.use_kernels(None)


def _allclose(a, b, rtol=None, atol=None, dtype=jnp.float32):
    trtol, tatol = ref.tolerances(dtype)
    rtol = trtol if rtol is None else rtol
    atol = tatol if atol is None else atol
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


# --- ops-level padding: unaligned shapes (m % 8 != 0, b % 128 != 0) --------


@pytest.mark.parametrize("m,b,row_start", [(30, 12, 0), (52, 20, 8), (9, 5, 0)])
def test_panel_qr_unaligned_padding(rng, m, b, row_start):
    A = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    _allclose(ops.panel_qr(A, row_start), ref.panel_qr(A, row_start))


@pytest.mark.parametrize("b", [5, 12, 30])
def test_stacked_qr_unaligned_padding(rng, b):
    R1 = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32))
    R2 = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32))
    _allclose(ops.stacked_qr(R1, R2), ref.stacked_qr(R1, R2))


@pytest.mark.parametrize("m,b,n", [(30, 12, 17), (44, 20, 50)])
def test_wy_apply_unaligned_padding(rng, m, b, n):
    Y = jnp.asarray(rng.standard_normal((m, b)), jnp.float32) * 0.1
    T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    C = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    _allclose(ops.wy_apply(Y, T, C, block_n=64), ref.wy_apply(Y, T, C))


@pytest.mark.parametrize("b,n", [(12, 20), (20, 33)])
def test_stacked_apply_unaligned_padding(rng, b, n):
    Y2 = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    T = jnp.triu(jnp.asarray(rng.standard_normal((b, b)), jnp.float32)) * 0.1
    Ct = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    Cb = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    _allclose(
        ops.stacked_apply(Y2, T, Ct, Cb, block_n=32),
        ref.stacked_apply(Y2, T, Ct, Cb),
    )


def test_padding_matches_unpadded_kernel(rng):
    """Zero-padding to the alignment contract is exact in exact arithmetic
    (padded rows/columns only ever add zero terms to inner products and
    produce degenerate tau=0 reflectors); in floats the only difference is
    XLA regrouping reductions at the larger size, so padded vs direct kernel
    agree to roundoff."""
    m, b = 16, 8  # aligned rows, unaligned width -> pads to (136, 128)
    A = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
    from repro.kernels import panel_qr as _panel

    direct = _panel.panel_qr(A, jnp.asarray(0, jnp.int32))
    # the padding contract belongs to the pallas routes; the default
    # compiled/xla engine runs at natural shapes, so force interpret here
    backend.force_mode(backend.MODE_INTERPRET, "panel_qr")
    try:
        padded = ops.panel_qr(A, 0)
    finally:
        backend.force_mode(None, "panel_qr")
    _allclose(direct, padded, rtol=1e-5, atol=1e-5)


# --- core entry points dispatch through the kernels ------------------------


def test_core_dispatch_parity(rng, forced_kernels):
    """householder_qr_masked / stacked_qr / apply_qt / stacked_apply_qt give
    the same numbers with the kernel dispatch forced on."""
    A = jnp.asarray(rng.standard_normal((40, 12)), jnp.float32)
    rs = jnp.asarray(0, jnp.int32)
    wy_k = hh.householder_qr_masked(A, rs)
    wy_p = hh._householder_qr_masked(A, rs)
    _allclose(wy_k, wy_p, rtol=3e-4, atol=3e-4)

    R1 = jnp.triu(jnp.asarray(rng.standard_normal((12, 12)), jnp.float32))
    R2 = jnp.triu(jnp.asarray(rng.standard_normal((12, 12)), jnp.float32))
    _allclose(hh.stacked_qr(R1, R2), hh._stacked_qr(R1, R2))

    C = jnp.asarray(rng.standard_normal((40, 20)), jnp.float32)
    _allclose(hh.apply_qt(wy_p.Y, wy_p.T, C), hh._apply_qt(wy_p.Y, wy_p.T, C))

    sq = hh._stacked_qr(R1, R2)
    Ct = jnp.asarray(rng.standard_normal((12, 20)), jnp.float32)
    Cb = jnp.asarray(rng.standard_normal((12, 20)), jnp.float32)
    _allclose(hh.stacked_apply_qt(sq, Ct, Cb), hh._stacked_apply_qt(sq, Ct, Cb))


def test_dispatch_skips_lane_stacked_and_non_f32(rng, forced_kernels):
    """Explicitly lane-stacked (leading-axis) arrays and non-f32 calls stay
    on the pure path. (Vmapped call sites see 2-D per-lane tracers and DO
    dispatch — covered by test_forced_kernel_caqr_sweep_matches_pure.)"""
    Y3 = jnp.zeros((2, 8, 4), jnp.float32)
    assert not hh._kernel_dispatch(Y3)
    Yi = jnp.zeros((8, 4), jnp.int32)
    assert not hh._kernel_dispatch(Yi)
    assert hh._kernel_dispatch(jnp.zeros((8, 4), jnp.float32))
    under_vmap = []
    jax.vmap(lambda y: under_vmap.append(hh._kernel_dispatch(y)) or y)(Y3)
    assert under_vmap == [True]


def test_forced_kernel_caqr_sweep_matches_pure(rng):
    """The full windowed CAQR sweep through the kernel seam (padding path,
    interpret mode, vmapped under SimComm) matches the pure sweep."""
    from repro.core import SimComm, caqr_factorize

    P, m_loc, n, b = 4, 16, 32, 8
    comm = SimComm(P)
    A = jnp.asarray(rng.standard_normal((P, m_loc, n)), jnp.float32)
    backend.use_kernels(True)
    try:
        R_k = np.asarray(caqr_factorize(A, comm, b).R[0])
    finally:
        backend.use_kernels(None)
    backend.use_kernels(False)
    try:
        R_p = np.asarray(caqr_factorize(A, comm, b).R[0])
    finally:
        backend.use_kernels(None)
    np.testing.assert_allclose(R_k, R_p, rtol=3e-4, atol=3e-4)


# --- backend-aware interpret default ---------------------------------------


def test_interpret_default_single_source_of_truth():
    expected = jax.default_backend() != "tpu"
    assert backend.interpret_default() is expected
    assert ops._interpret() is expected
    assert backend.resolve_interpret(None) is expected
    assert backend.resolve_interpret(True) is True
    assert backend.resolve_interpret(False) is False


def test_kernels_run_without_explicit_interpret(rng):
    """Kernel modules no longer hardcode interpret=True — calling them with
    the default must work on this (non-TPU) backend."""
    from repro.kernels import panel_qr as _panel
    from repro.kernels import stacked_qr as _stacked
    from repro.kernels import wy_apply as _wy

    A = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    Y, T, R = _panel.panel_qr(A, jnp.asarray(0, jnp.int32))
    assert R.shape == (8, 8)
    R1 = jnp.triu(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    Y2, T2, R2 = _stacked.stacked_qr(R1, R1)
    assert R2.shape == (8, 8)
    C = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    out = _wy.wy_apply(Y, T, C, block_n=8)
    assert out.shape == C.shape


# --- the per-op execution policy (DESIGN.md §10) ----------------------------


@pytest.fixture
def clean_policy(monkeypatch):
    """Start from the automatic policy with no env overrides; restore it."""
    for var in ("REPRO_NO_KERNELS", "REPRO_FORCE_KERNELS",
                "REPRO_KERNEL_MODE"):
        monkeypatch.delenv(var, raising=False)
    for op in backend.OPS:
        monkeypatch.delenv(f"REPRO_KERNEL_MODE_{op.upper()}", raising=False)
    backend.use_kernels(None)
    backend.force_mode(None)
    yield monkeypatch
    backend.use_kernels(None)
    backend.force_mode(None)


def test_auto_policy_is_compiled_everywhere(clean_policy):
    for op in backend.OPS:
        assert backend.kernel_mode(op) == backend.MODE_COMPILED


def test_env_global_and_per_op_mode(clean_policy):
    clean_policy.setenv("REPRO_KERNEL_MODE", "oracle")
    assert backend.kernel_mode("panel_qr") == backend.MODE_ORACLE
    # the per-op variable beats the global one
    clean_policy.setenv("REPRO_KERNEL_MODE_PANEL_QR", "interpret")
    assert backend.kernel_mode("panel_qr") == backend.MODE_INTERPRET
    assert backend.kernel_mode("wy_apply") == backend.MODE_ORACLE
    # 'auto' resolves back to compiled
    clean_policy.setenv("REPRO_KERNEL_MODE", "auto")
    assert backend.kernel_mode("wy_apply") == backend.MODE_COMPILED


def test_env_invalid_mode_warns_and_is_ignored(clean_policy):
    clean_policy.setenv("REPRO_KERNEL_MODE", "turbo")
    with pytest.warns(UserWarning, match="REPRO_KERNEL_MODE"):
        assert backend.kernel_mode("panel_qr") == backend.MODE_COMPILED


def test_force_mode_beats_env(clean_policy):
    clean_policy.setenv("REPRO_KERNEL_MODE", "oracle")
    backend.force_mode(backend.MODE_INTERPRET, "stacked_qr")
    assert backend.kernel_mode("stacked_qr") == backend.MODE_INTERPRET
    assert backend.kernel_mode("panel_qr") == backend.MODE_ORACLE
    backend.force_mode(None, "stacked_qr")
    assert backend.kernel_mode("stacked_qr") == backend.MODE_ORACLE


def test_no_kernels_env_beats_mode_env(clean_policy):
    clean_policy.setenv("REPRO_KERNEL_MODE", "compiled")
    clean_policy.setenv("REPRO_NO_KERNELS", "1")
    assert backend.kernel_mode("wy_apply") == backend.MODE_ORACLE
    assert not backend.dispatch_enabled()


def test_use_kernels_beats_everything(clean_policy):
    clean_policy.setenv("REPRO_NO_KERNELS", "1")
    backend.use_kernels(True)
    assert backend.kernel_mode("panel_qr") == backend.MODE_COMPILED
    assert backend.dispatch_enabled()
    backend.use_kernels(False)
    backend.force_mode(backend.MODE_COMPILED)  # still loses to use_kernels
    assert backend.kernel_mode("panel_qr") == backend.MODE_ORACLE
    assert not backend.dispatch_enabled()


def test_static_engine_policy_off_tpu(clean_policy):
    """Off TPU the compiled mode runs the xla engine for every op — a
    written policy, not the outcome of a probe."""
    assert backend.platform() != "tpu"
    for op in backend.OPS:
        assert backend.compiled_engine(op) == backend.ENGINE_XLA
    assert backend.engine_report() == {op: backend.ENGINE_XLA
                                       for op in backend.OPS}


def test_tpu_lowering_failure_raises(clean_policy, rng):
    """On a TPU-like backend every op routes to native Pallas; a Pallas
    kernel that cannot lower (here: the CPU backend underneath) raises its
    lowering error — no fallback to another engine."""
    clean_policy.setattr(backend, "platform", lambda: "tpu")
    for op in backend.OPS:
        assert backend.compiled_engine(op) == backend.ENGINE_PALLAS
    A = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    with pytest.raises(Exception, match="(?i)interpret"):
        jax.block_until_ready(ops.panel_qr(A, 0))


def test_oracle_route_for_unsupported_dtype(clean_policy, rng):
    """Dtypes outside the kernel envelope silently take the oracle leg even
    in compiled mode (f64 here; the result IS the oracle's, bit for bit)."""
    A = jnp.asarray(rng.standard_normal((16, 8)))  # f32 by default
    A64 = jnp.asarray(np.asarray(A, np.float64))
    if A64.dtype != jnp.float64:
        pytest.skip("x64 disabled on this build")
    got = ops.panel_qr(A64, 0)
    want = ref.panel_qr(A64, 0)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_autotune_lookup_drives_dispatch(clean_policy, rng):
    """A tuned cell's params are consulted on dispatch (and cleared cells
    fall back to the static defaults) — numerics are unroll-invariant."""
    from repro.kernels import autotune

    A = jnp.asarray(rng.standard_normal((24, 6)), jnp.float32)
    autotune.clear()
    try:
        base = ops.panel_qr(A, 0)
        variant = autotune.current_variant("panel_qr")
        autotune._CELLS[autotune.cell_key(
            "panel_qr", A.shape, A.dtype, variant)] = {
                "params": {"unroll": 4}, "us": 1.0}
        tuned = ops.panel_qr(A, 0)
        for g, w in zip(jax.tree_util.tree_leaves(base),
                        jax.tree_util.tree_leaves(tuned)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=3e-6, atol=3e-6)
    finally:
        autotune.clear()
