"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so the Pallas kernels and one sweep
segment are compiled here at the shapes the production sweep gives them
(``configs.paper_qr.PRODUCTION``: 8 SimComm lanes of 8192 rows, b = 128,
trailing windows from 3968 down to 128 columns; 16384 rows per lane on the
four-chip mesh). What Mosaic or XLA refuses — a dynamic slice it cannot
lower, a block over the VMEM limit, a program over the HBM — fails here,
with no chip. Nothing runs: these say nothing about results or times.

The topology is described inside a module fixture (a worker that cannot
describe it skips), so importing this file loads no TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

B = 128
M_LOC = 8192           # 65536 rows over 8 SimComm lanes
M_LOC_MESH = 16384     # 65536 rows over a 4-chip lane mesh
LANES = 8
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be read
    # back without one: keep these compiles out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,lanes", [(M_LOC, None), (M_LOC, LANES),
                                     (M_LOC_MESH, None)])
def test_panel_qr_compiles(one_chip, m, lanes):
    from repro.kernels import panel_qr

    fn = lambda a, rs: panel_qr.panel_qr(a, rs, interpret=False)
    a, rs = (m, B), ()
    if lanes:
        fn, a, rs = jax.vmap(fn), (lanes,) + a, (lanes,)
    _compile(fn, _shape(one_chip, a), _shape(one_chip, rs, jnp.int32))


@pytest.mark.parametrize("lanes", [None, LANES])
def test_stacked_qr_compiles(one_chip, lanes):
    from repro.kernels import stacked_qr

    fn = lambda r1, r2: stacked_qr.stacked_qr(r1, r2, interpret=False)
    r = (B, B)
    if lanes:
        fn, r = jax.vmap(fn), (lanes,) + r
    _compile(fn, _shape(one_chip, r), _shape(one_chip, r))


@pytest.mark.parametrize("m,n,lanes", [
    (M_LOC, 4096 - B, None), (M_LOC, B, None), (M_LOC, 4096, LANES),
    (M_LOC_MESH, 4096 - B, None)])
def test_wy_apply_compiles(one_chip, m, n, lanes):
    from repro.kernels import wy_apply

    fn = lambda y, t, c: wy_apply.wy_apply(y, t, c, interpret=False)
    shapes = [(m, B), (B, B), (m, n)]
    if lanes:
        fn, shapes = jax.vmap(fn), [(lanes,) + s for s in shapes]
    _compile(fn, *(_shape(one_chip, s) for s in shapes))


@pytest.mark.parametrize("n", [4096 - B, B])
def test_stacked_apply_compiles(one_chip, n):
    from repro.kernels import stacked_qr

    fn = lambda y2, t, ct, cb: stacked_qr.stacked_apply(
        y2, t, ct, cb, interpret=False)
    _compile(fn, *(_shape(one_chip, s) for s in [(B, B), (B, B), (B, n),
                                                  (B, n)]))


def test_sweep_segment_compiles(one_chip, monkeypatch):
    """The leaf-apply point of panel 0 — the widest trailing update — as
    the orchestrator compiles it on a TPU: SimComm(8) lanes of 8192 x 4096,
    the core dispatching into the Pallas kernels (steered on here: this
    process's backend is the CPU). It fits the chip's HBM."""
    from repro.core.comm import SimComm
    from repro.ft.failures import sweep_point
    from repro.ft.online.state import initial_sweep_state, run_steps
    from repro.kernels import backend

    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    comm = SimComm(LANES)
    state = jax.eval_shape(
        lambda a: initial_sweep_state(comm, a, B),
        jax.ShapeDtypeStruct((LANES, M_LOC, 4096), jnp.float32))
    while state.cursor != sweep_point(0, "trailing", 0):
        state = jax.eval_shape(lambda s: run_steps(comm, s, 1), state)
    state = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), state)
    compiled = _compile(lambda s: run_steps(comm, s, 1), state)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"
