"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so the Pallas kernels and one sweep
segment are compiled here at the shapes the production sweep gives them
(``configs.paper_qr.PRODUCTION``: 8 SimComm lanes of 8192 rows, b = 128,
trailing windows from 3968 down to 128 columns; 16384 rows per lane on the
four-chip mesh; for the panel QR also the benchmark's 16 lanes of 15744
rows; the ``tsqr_mesh4`` cell's 4 lanes of 31360 rows per chip, its sweep
segments and its heal on the described 2x2 mesh). What Mosaic or XLA
refuses — a dynamic slice it cannot lower, a block over the VMEM limit, a
program over the HBM — fails here, with no chip. Nothing runs: these say
nothing about results or times.

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
M_LOC_CELL = 15744     # the tsqr_tall benchmark: 250000 rows over 16 lanes
LANES_CELL = 16
M_MESH4, N_MESH4 = 500000, 1000   # the tsqr_mesh4 cell: 16 lanes, 4 per chip
LANES_MESH4, CHIPS_MESH4 = 16, 4
M_LOC_MESH4 = 31360    # its 31250-row blocks padded to the panel
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


def _device_bytes(compiled) -> int:
    """Bytes one device holds for a program: arguments, outputs and
    temporaries (per device for a partitioned program)."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


@pytest.mark.parametrize("m,lanes", [(M_LOC, None), (M_LOC, LANES),
                                     (M_LOC_MESH, None),
                                     (M_LOC_CELL, LANES_CELL),
                                     (M_LOC_CELL, None)])
def test_panel_qr_compiles(one_chip, m, lanes):
    from repro.kernels import panel_qr

    fn = lambda a, rs: panel_qr.panel_qr(a, rs, interpret=False)
    a, rs = (m, B), ()
    if lanes:
        fn, a, rs = jax.vmap(fn), (lanes,) + a, (lanes,)
    _compile(fn, _shape(one_chip, a), _shape(one_chip, rs, jnp.int32))


def test_panel_qr_compiles_mesh4_leaf(one_chip):
    """The tsqr_mesh4 leaf: one chip's 4 lanes of 31360 rows, vmapped; its
    6 * m * b words (91.9 MiB) sit just under the 100 MiB VMEM cap."""
    from repro.kernels import panel_qr

    fn = jax.vmap(lambda a, rs: panel_qr.panel_qr(a, rs, interpret=False))
    compiled = _compile(
        fn, _shape(one_chip, (LANES_MESH4 // CHIPS_MESH4, M_LOC_MESH4, B)),
        _shape(one_chip, (LANES_MESH4 // CHIPS_MESH4,), jnp.int32))
    assert _device_bytes(compiled) < HBM_BYTES


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


def test_tall_heal_compiles(one_chip, monkeypatch):
    """The tsqr_tall.kill1 cell's heal as the orchestrator compiles it on a
    TPU (``SimHeal``): lane 3 of 16 SimComm lanes of 15744 x 1024 rebuilt
    after panel 4's first trailing level, from lanes 2, 1, 7 and 11, its
    replay in the Pallas kernels. The source matrix is handed back, not
    copied, and the program fits the chip's HBM."""
    from repro.core.comm import SimComm
    from repro.ft.coding import XORPairScheme
    from repro.ft.failures import sweep_point
    from repro.ft.online import orchestrator
    from repro.ft.online.state import initial_sweep_state, run_steps
    from repro.kernels import backend

    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    monkeypatch.setattr(orchestrator, "_SEGMENT_CACHE", {})
    comm = SimComm(LANES_CELL)
    point = sweep_point(4, "trailing", 0)
    state = jax.eval_shape(
        lambda a: initial_sweep_state(comm, a, B),
        jax.ShapeDtypeStruct((LANES_CELL, 250000 // LANES_CELL, 1000),
                             jnp.float32))
    while state.cursor != sweep_point(4, "trailing", 1):
        state = jax.eval_shape(lambda s: run_steps(comm, s, 1), state)
    state = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), state)
    heal = orchestrator.compiled_heal(comm, [3], point, XORPairScheme())
    compiled = heal._call.lower(state).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (reads,) = heal.ledgers.values()
    assert sorted(set(reads[3].values())) == [1, 2, 7, 11]
    mem = compiled.memory_analysis()
    a0 = state.A0.size * state.A0.dtype.itemsize
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
    assert mem.output_size_in_bytes < total - a0 + 2**20
    assert _device_bytes(compiled) < HBM_BYTES, (
        f"{_device_bytes(compiled) / 2**30:.2f} GiB")


@pytest.fixture(scope="module")
def mesh4(one_chip):
    """The described 2x2 host as a 1-D mesh of its four chips, and the
    tsqr_mesh4 state's abstract entry layout (SimComm(16) global arrays,
    lane axes sharded over the chips)."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices[:CHIPS_MESH4]), ("qr",))


def _mesh4_state(mesh, cursor):
    """The tsqr_mesh4 sweep state at ``cursor``, abstract, sharded as the
    online SPMD path holds it between segments."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.comm import SimComm
    from repro.ft.online.state import (
        initial_sweep_state,
        run_steps,
        state_lane_axes,
    )

    comm = SimComm(LANES_MESH4)
    state = jax.eval_shape(
        lambda a: initial_sweep_state(comm, a, B),
        jax.ShapeDtypeStruct(
            (LANES_MESH4, M_MESH4 // LANES_MESH4, N_MESH4), jnp.float32))
    while state.cursor != cursor:
        state = jax.eval_shape(lambda s: run_steps(comm, s, 1), state)

    def spec(ax):
        return P() if ax < 0 else P(*([None] * ax + ["qr"]))

    return jax.tree_util.tree_map(
        lambda x, ax: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec(ax))),
        state, state_lane_axes(state))


@pytest.mark.parametrize("program,level", [("segment", 0), ("segment", 2),
                                           ("heal", None)])
def test_mesh4_program_compiles(mesh4, monkeypatch, program, level):
    """The tsqr_mesh4 cell's programs for a described 2x2 v5e: trailing
    combine points of panel 0 as MeshComm segments, 4 lanes per chip —
    level 0 (with the leaf apply, the widest trailing update) pairs lanes
    of one chip and moves nothing across chips, level 2 pairs chips by a
    collective-permute — and the heal of lane 3 after panel 4's first
    trailing level (the cell's kill), which fetches from lanes 7 and 11 on
    other chips. Each is partitioned four ways and each chip's share fits
    its HBM."""
    from repro.dist import compat
    from repro.ft.coding import XORPairScheme
    from repro.ft.failures import sweep_point
    from repro.kernels import backend
    from repro.launch.spmd_qr import make_spmd_sweep_step

    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    step = make_spmd_sweep_step(mesh4, lanes_per_chip=LANES_MESH4
                                // CHIPS_MESH4)
    if program == "segment":
        state = _mesh4_state(mesh4, sweep_point(0, "trailing", level))
        jitted = step.program(state)
        crosses = level >= 2
    else:
        point = sweep_point(4, "trailing", 0)
        state = _mesh4_state(mesh4, sweep_point(4, "trailing", 1))
        heal = step.heal(state, [3], point, XORPairScheme())
        assert sorted(set(heal.reads[3].values())) == [1, 2, 7, 11]
        assert 0 < heal.xchip_bytes < 2**30 // 8
        jitted, crosses = heal.program, True
    with compat.set_mesh(mesh4):
        compiled = jitted.lower(state).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"num_partitions={CHIPS_MESH4}" in text
    assert ("collective-permute" in text) == crosses
    assert _device_bytes(compiled) < HBM_BYTES, (
        f"{_device_bytes(compiled) / 2**30:.2f} GiB")


@pytest.mark.parametrize("where", ["one_chip", "mesh4"])
def test_sentinel_probe_compiles(one_chip, mesh4, where):
    """The detector's sentinel probe at a benchmark cell's first trailing
    boundary (the two 1024-column fields of 15744 or 31360 rows per lane),
    for a described v5e and for the described 2x2 mesh: ONE fusion reading
    each lane's head elements in place — no copy of a field, no
    temporaries, no collective — returning one float per lane."""
    from repro.core.comm import SimComm
    from repro.ft.failures import sweep_point
    from repro.ft.online.state import initial_sweep_state, run_steps
    from repro.ft.online.detect import _probed, _sentinel_probe

    point = sweep_point(0, "trailing", 0)
    if where == "mesh4":
        state = _mesh4_state(mesh4, point)
    else:
        comm = SimComm(LANES_CELL)
        state = jax.eval_shape(
            lambda a: initial_sweep_state(comm, a, B),
            jax.ShapeDtypeStruct((LANES_CELL, M_LOC_CELL, N_MESH4),
                                 jnp.float32))
        while state.cursor != point:
            state = jax.eval_shape(lambda s: run_steps(comm, s, 1), state)
        state = jax.tree_util.tree_map(
            lambda x: _shape(one_chip, x.shape, x.dtype), state)
    fields = _probed(state)
    assert max(x.size for x in fields) >= LANES_CELL * M_LOC_CELL * 1024
    compiled = _sentinel_probe.lower(fields).compile()
    text = compiled.as_text()
    assert text.count(" fusion(") == 1
    for op in (" copy(", "all-gather", "all-reduce", "collective-permute"):
        assert op not in text, op
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert compiled.out_info.shape == (LANES_CELL,)
