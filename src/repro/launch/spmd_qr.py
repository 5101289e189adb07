"""Production SPMD entry for the FT-CAQR sweep: ``shard_map`` over a 1-D
lane mesh (paper §II's execution model, one process per lane).

``ft_caqr_sweep_spmd`` runs the same Comm-generic driver the simulator runs
(``repro.ft.driver``), but over ``AxisComm`` inside ``shard_map``: each
device holds one lane's block-row, every exchange lowers to a real
``collective-permute``/``all-reduce``, and the failure schedule — static
Python data — is broadcast to every lane at trace time (each lane's compiled
program contains the full schedule, the SPMD analogue of the paper's
agreed-on failure detection). Death is the Comm death-mask representation
(DESIGN.md §8): the scheduled lane NaN-masks its own state, REBUILD fetches
are point-to-point permutes from the single surviving buddy.

Output layout: the gathered global result is **leaf-for-leaf identical to a
``SimComm`` run** — the body reinserts the lane axis exactly where the
simulator's batching puts it — so the two paths are directly comparable
with ``jax.tree_util`` equality and no reshaping. That equivalence (R,
factors, bundles, post-REBUILD state, bit for bit) is the repo's SPMD
oracle, gated by ``tests/test_spmd_ft_driver.py`` on aligned, ragged, and
wide geometries.

Scheduling caveats inherited from tracing the whole sweep into one program:
``RecoveryEvent.elapsed_s`` records trace time, not device time (use
``benchmarks/bench_spmd.py`` for measured SPMD REBUILD cost), and an
unrecoverable schedule raises ``UnrecoverableFailure`` at trace time,
before any device computes.

The *online* entrypoints below (``make_spmd_sweep_step`` /
``ft_caqr_sweep_online_spmd``) lift both caveats by not tracing the sweep
as one program: the host orchestrator runs shard_map ``sweep_step``
segments and discovers failures at runtime between them (DESIGN.md §9) —
REBUILD latency is then real wall clock and recoverability is judged when
the death actually happens.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.caqr import PanelFactors
from repro.core.comm import AxisComm, SimComm
from repro.core.trailing import RecoveryBundle
from repro.dist import compat
from repro.ft.driver import FTSweepDriver, FTSweepResult
from repro.ft.failures import FailureSchedule
from repro.ft.online.state import state_lane_axes, sweep_step

# Lane-axis position of every per-lane leaf in the SimComm result layout.
# The shard_map body expands a size-1 axis there; with the matching out_spec
# the gathered global arrays are layout-identical to a SimComm run.
_R_LANE_AXIS = 0
_FACTORS_LANE_AXIS = PanelFactors(
    leaf_Y=1, leaf_T=1, level_Y2=2, level_T=2,
    row_start=1, active=1, target=1,
)
_BUNDLE_LANE_AXIS = RecoveryBundle(
    W=2, C_self=2, C_buddy=2, Y2=2, T=2, self_was_top=2,
)


def make_lane_mesh(n_lanes: Optional[int] = None, axis_name: str = "qr"):
    """1-D device mesh, one CAQR lane per device (default: all devices).

    ``n_lanes`` must be a power of two (the butterfly's requirement). On a
    CPU host, force a multi-device platform with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (see ``examples/spmd_quickstart.py``).
    """
    if n_lanes is None:
        n_lanes = len(jax.devices())
    return compat.make_mesh((n_lanes,), (axis_name,))


def pow2_lanes(n_devices: Optional[int] = None) -> int:
    """Largest power-of-two lane count usable on ``n_devices`` (default:
    the visible device count). The butterfly needs 2^k lanes, so a non-pow2
    training pod (e.g. P=48 hosts) runs its optimizer-internal sweeps on
    the largest power-of-two prefix (32) and leaves the rest to data
    parallelism — the FT training runtime sizes its lane mesh with this."""
    if n_devices is None:
        n_devices = len(jax.devices())
    assert n_devices >= 1
    return 1 << (n_devices.bit_length() - 1)


def ft_caqr_sweep_spmd(
    A: jax.Array,
    panel_width: int,
    schedule: Optional[FailureSchedule] = None,
    mesh=None,
    axis_name: str = "qr",
    scheme=None,
) -> FTSweepResult:
    """Run the windowed FT-CAQR sweep under ``shard_map`` on a device mesh.

    A: the full ``(m, n)`` matrix; rows are block-sharded over the mesh's
        lane axis (``m`` must divide by the lane count — each lane re-reads
        its own contiguous block-row on REBUILD, the paper's data-source
        model). Any per-lane shape ``ft_caqr_sweep`` accepts works: ragged
        and wide geometries run at the padded ``sweep_geometry`` inside the
        mapped body, identically to the simulator.
    panel_width: b.
    schedule: static lane-death schedule, broadcast to every lane at trace
        time; ``None`` = failure-free.
    mesh: a 1-D mesh from ``make_lane_mesh`` (default: one lane per visible
        device). The lane count must be a power of two.

    Returns ``FTSweepResult`` with the *SimComm layout*: ``R`` is
    ``(P, min(m,n), n)`` (per-lane replicated copies), factors/bundles carry
    the lane axis where the simulator's batching puts it, and ``events``
    holds the trace-time REBUILD ledger (single-source reads per artifact).
    """
    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    n_lanes = mesh.shape[axis_name]
    m, n = A.shape
    assert m % n_lanes == 0, (
        f"rows ({m}) must block-shard evenly over {n_lanes} lanes"
    )
    events_log = []

    def body(A_local):
        drv = FTSweepDriver(A_local, AxisComm(axis_name), panel_width, schedule,
                            scheme=scheme)
        res = drv.run()
        events_log.append(res.events)
        factors = jax.tree_util.tree_map(
            jnp.expand_dims, res.factors, _FACTORS_LANE_AXIS)
        bundles = jax.tree_util.tree_map(
            jnp.expand_dims, res.bundles, _BUNDLE_LANE_AXIS)
        return jnp.expand_dims(res.R, _R_LANE_AXIS), factors, bundles

    spec_of = lambda lane_axis: P(
        *([None] * lane_axis + [axis_name]))
    out_specs = (
        spec_of(_R_LANE_AXIS),
        jax.tree_util.tree_map(spec_of, _FACTORS_LANE_AXIS),
        jax.tree_util.tree_map(spec_of, _BUNDLE_LANE_AXIS),
    )
    mapped = compat.shard_map(
        body, mesh, in_specs=P(axis_name, None), out_specs=out_specs)
    with compat.set_mesh(mesh):
        R, factors, bundles = jax.jit(mapped)(A)
    # the trace populated the static event ledger exactly once (fresh jit)
    (events,) = events_log
    return FTSweepResult(R=R, factors=factors, bundles=bundles, events=events)


# -- online (runtime-detected) path ------------------------------------------


def make_spmd_sweep_step(mesh=None, axis_name: str = "qr"):
    """Shard_map segment backend for the online orchestrator.

    Returns ``step(state) -> state`` executing ONE sweep point of the
    reified state machine (``repro.ft.online.state.sweep_step``) under
    ``shard_map`` over the lane mesh. Between calls the ``SweepState``
    lives as *global* lane-sharded arrays in the SimComm layout — the
    host-side orchestrator probes sentinels, injects/obliterates and
    REBUILDs on that global layout with the SimComm mask primitives, while
    every compiled segment runs the AxisComm program on the devices. One
    program is compiled per cursor position (the treedef carries the
    cursor) and cached for the lifetime of the returned callable;
    ``step.program(state)`` returns it (e.g. to read its compiled text).

    Per-leaf specs come from ``state_lane_axes``; the body squeezes each
    leaf's size-1 lane axis so the AxisComm step sees true per-lane locals,
    and re-expands on the way out, keeping the gathered global layout
    leaf-for-leaf identical to a SimComm run (the §8 oracle, extended to
    every intermediate boundary state).
    """
    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    n_lanes = mesh.shape[axis_name]
    cache = {}

    def spec_of(lane_axis):
        if lane_axis < 0:
            # no lane axis: checksum-lane parity slots (repro.ft.coding)
            # are global values, replicated across the mesh
            return P()
        return P(*([None] * lane_axis + [axis_name]))

    def program(state):
        """The jitted shard_map program of ``state``'s sweep point."""
        key = jax.tree_util.tree_structure(state)
        fn = cache.get(key)
        if fn is None:
            in_axes = state_lane_axes(state)
            out_struct = jax.eval_shape(
                lambda s: sweep_step(SimComm(n_lanes), s), state)
            out_axes = state_lane_axes(out_struct)

            def body(s_shard):
                local = jax.tree_util.tree_map(
                    lambda x, ax: x if ax < 0 else jnp.squeeze(x, axis=ax),
                    s_shard, in_axes)
                out = sweep_step(AxisComm(axis_name), local)
                return jax.tree_util.tree_map(
                    lambda x, ax: x if ax < 0 else jnp.expand_dims(x, ax),
                    out, out_axes)

            fn = jax.jit(compat.shard_map(
                body, mesh,
                in_specs=(jax.tree_util.tree_map(spec_of, in_axes),),
                out_specs=jax.tree_util.tree_map(spec_of, out_axes),
            ))
            cache[key] = fn
        return fn

    def step(state):
        with compat.set_mesh(mesh):
            return program(state)(state)

    step.program = program
    return step


def make_spmd_step_factory(axis_name: str = "qr", devices=None):
    """Per-world segment-runner factory for the *elastic* orchestrator.

    An elastic transition (``repro.ft.elastic``) changes the lane count
    mid-run; the orchestrator then calls ``factory(n_slots)`` and gets a
    fresh ``make_spmd_sweep_step`` over a new 1-D mesh of the first
    ``n_slots`` surviving devices — ``shard_map`` re-meshed over the
    shrunken lane axis. Pair it with ``elastic_policy="fold"`` so the new
    slot count is a power of two no larger than the survivor count (a
    SHRINK world must fit on the devices that are left)."""
    devices = list(devices) if devices is not None else list(jax.devices())

    def factory(n_slots: int):
        assert n_slots <= len(devices), (n_slots, len(devices))
        mesh = compat.make_mesh((n_slots,), (axis_name,),
                                devices=devices[:n_slots])
        return make_spmd_sweep_step(mesh, axis_name)

    return factory


def ft_caqr_sweep_elastic_spmd(
    A: jax.Array,
    panel_width: int,
    detector=None,
    mesh=None,
    axis_name: str = "qr",
    semantics=None,
    **orchestrator_kw,
):
    """Elastic online sweep on the SPMD path: like
    ``ft_caqr_sweep_online_spmd`` but with SHRINK/BLANK semantics — a
    detected death is healed from its buddy and the sweep re-meshes over
    the shrunken lane axis at the next panel boundary (fold policy:
    floor-pow2 of the survivor count, so the new mesh fits on surviving
    devices). Returns ``repro.ft.elastic.ElasticSweepResult``."""
    from repro.ft.online.orchestrator import SweepOrchestrator
    from repro.ft.semantics import Semantics

    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    n_lanes = mesh.shape[axis_name]
    m, n = A.shape
    assert m % n_lanes == 0, (
        f"rows ({m}) must block-shard evenly over {n_lanes} lanes"
    )
    orch = SweepOrchestrator(
        A.reshape(n_lanes, m // n_lanes, n), SimComm(n_lanes), panel_width,
        detector=detector,
        step_fn=make_spmd_sweep_step(mesh, axis_name),
        step_factory=make_spmd_step_factory(axis_name),
        semantics=semantics if semantics is not None else Semantics.SHRINK,
        elastic_policy="fold",
        **orchestrator_kw,
    )
    return orch.run()


# One segment runner per (mesh, axis): repeated online sweeps on a mesh share
# its compiled per-point programs, like ``compiled_segment`` under SimComm.
_ONLINE_STEPS = {}


def ft_caqr_sweep_online_spmd(
    A: jax.Array,
    panel_width: int,
    detector=None,
    mesh=None,
    axis_name: str = "qr",
    **orchestrator_kw,
) -> FTSweepResult:
    """Online recovery on the production SPMD path: host-side orchestrator,
    shard_map segments, runtime failure detection — no trace-time schedule.

    ``A`` is the full ``(m, n)`` matrix, row-sharded over the lane mesh like
    ``ft_caqr_sweep_spmd``. Extra keywords (``fault_hooks``,
    ``segment_points``, ``store``/``persist_every``, ...) pass through to
    ``repro.ft.online.orchestrator.SweepOrchestrator``. The result layout is
    the SimComm layout, directly comparable to both the simulator and the
    scheduled SPMD entry — a runtime-detected kill is bitwise-identical to
    the same kill expressed as a trace-time ``FailureSchedule``
    (``tests/test_spmd_ft_driver.py``).
    """
    from repro.ft.online.orchestrator import SweepOrchestrator

    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    n_lanes = mesh.shape[axis_name]
    m, n = A.shape
    assert m % n_lanes == 0, (
        f"rows ({m}) must block-shard evenly over {n_lanes} lanes"
    )
    key = (mesh, axis_name)
    if key not in _ONLINE_STEPS:
        _ONLINE_STEPS[key] = make_spmd_sweep_step(mesh, axis_name)
    orch = SweepOrchestrator(
        A.reshape(n_lanes, m // n_lanes, n), SimComm(n_lanes), panel_width,
        detector=detector,
        step_fn=_ONLINE_STEPS[key],
        **orchestrator_kw,
    )
    return orch.run()
