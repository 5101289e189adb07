"""Production SPMD entry for the FT-CAQR sweep: ``shard_map`` over a 1-D
device mesh (paper §II's execution model: each lane is a process, and
each device holds one or more lanes).

``ft_caqr_sweep_spmd`` runs the same Comm-generic driver the simulator runs
(``repro.ft.driver``), but over ``AxisComm`` inside ``shard_map``, one lane
per device: each device holds one lane's block-row, every exchange lowers
to a real ``collective-permute``/``all-reduce``, and the failure schedule —
static Python data — is broadcast to every lane at trace time (each lane's
compiled program contains the full schedule, the SPMD analogue of the
paper's agreed-on failure detection). Death is the Comm death-mask
representation (DESIGN.md §8): the scheduled lane NaN-masks its own state,
REBUILD fetches are point-to-point permutes from the single surviving buddy.

Output layout: the gathered global result is **leaf-for-leaf identical to a
``SimComm`` run** — the body reinserts the lane axis exactly where the
simulator's batching puts it — so the two paths are directly comparable
with ``jax.tree_util`` equality and no reshaping. That equivalence (R,
factors, bundles, post-REBUILD state, bit for bit) is the repo's SPMD
oracle, gated by ``tests/test_spmd_ft_driver.py`` on aligned, ragged, and
wide geometries.

Scheduling caveats inherited from tracing the whole sweep into one program:
``RecoveryEvent.elapsed_s`` records trace time, not device time, and an
unrecoverable schedule raises ``UnrecoverableFailure`` at trace time,
before any device computes.

The *online* entrypoints below (``make_spmd_sweep_step`` /
``ft_caqr_sweep_online_spmd``) lift both caveats by not tracing the sweep
as one program: the host orchestrator runs shard_map ``sweep_step``
segments and discovers failures at runtime between them (DESIGN.md §9) —
REBUILD latency is then real wall clock and recoverability is judged when
the death actually happens. They place ``lanes_per_chip`` consecutive
lanes on each device under the two-level ``MeshComm`` (butterfly levels
below ``log2 lanes_per_chip`` stay on the chip), and heal a dead lane with
one ``MeshHeal`` program on the lanes' own devices.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.caqr import PanelFactors
from repro.core.comm import AxisComm, MeshComm, SimComm
from repro.core.trailing import RecoveryBundle
from repro.dist import compat
from repro.ft.driver import (FTSweepDriver, FTSweepResult, HealProgram,
                             recover_lanes)
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


def _spec_of(axis_name: str):
    def spec_of(lane_axis):
        if lane_axis < 0:
            # no lane axis: checksum-lane parity slots (repro.ft.coding)
            # are global values, replicated across the mesh
            return P()
        return P(*([None] * lane_axis + [axis_name]))

    return spec_of


def make_lane_mesh(n_devices: Optional[int] = None, axis_name: str = "qr"):
    """1-D device mesh of ``n_devices`` devices (default: all devices). The
    scheduled entry puts one CAQR lane on each device; the online entries
    put ``lanes_per_chip`` lanes on each.

    ``n_devices`` must be a power of two (the butterfly's requirement). On a
    CPU host, force a multi-device platform with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (see ``examples/spmd_quickstart.py``).
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    return compat.make_mesh((n_devices,), (axis_name,))


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
    mesh: a 1-D mesh from ``make_lane_mesh`` (default: every visible
        device), one lane on each device. The lane count must be a power
        of two.

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

    spec_of = _spec_of(axis_name)
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


def _lane_comm(axis_name: str, lanes_per_chip: int, n_chips: int):
    """The comm of ``lanes_per_chip`` lanes on each of ``n_chips`` devices:
    ``AxisComm`` at one lane a device (its arrays carry no lane axis),
    ``MeshComm`` above. ``MeshComm`` computes the same values at one lane,
    but XLA on the CPU lowers its vmaps over one lane to other arithmetic
    than the unbatched per-lane program (1 ulp in a panel's T factor), and
    the unbatched program is the one that matches SimComm bit for bit."""
    if lanes_per_chip == 1:
        return AxisComm(axis_name)
    return MeshComm(axis_name, lanes_per_chip, n_chips)


def _mesh_program(mesh, axis_name: str, lanes_per_chip: int, fn, state):
    """``fn(comm, local_state) -> local_state`` as one jitted shard_map
    program over the lane-sharded ``state``, under ``_lane_comm``: each
    device's block of a leaf is the SimComm layout of its own lanes, which
    ``fn`` gets as it is (``MeshComm``) or with the one lane's axis
    squeezed away (``AxisComm``).

    The program returns only the leaves ``fn`` computes: a leaf ``fn``
    hands back unchanged (the source matrix, the stored panels a step does
    not touch) is the caller's own array again, so a program never holds
    two copies of the state. Which leaves those are is read from one
    trace of ``fn`` on the per-device shapes (``make_jaxpr`` with the mesh
    axis bound), before the program is built. Returns ``(call, program,
    probe)``: ``call(state) -> state``, the jitted program of the state,
    and the comm of that trace (its ``xchip_bytes``)."""
    n_chips = mesh.shape[axis_name]
    spec_of = _spec_of(axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(state)
    in_axes = state_lane_axes(state)
    in_ax = jax.tree_util.tree_leaves(in_axes)
    probe = _lane_comm(axis_name, lanes_per_chip, n_chips)

    def local_shape(shape, ax):
        if ax < 0:
            return tuple(shape)
        shape = list(shape)
        if probe.batched:
            shape[ax] //= n_chips
        else:
            del shape[ax]
        return tuple(shape)

    def to_local(xs, comm):
        if comm.batched:
            return xs
        return [x if ax < 0 else jnp.squeeze(x, axis=ax)
                for x, ax in zip(xs, in_ax)]

    plan = {}

    def traced(local_leaves):
        out = fn(probe, jax.tree_util.tree_unflatten(treedef, local_leaves))
        out_leaves, plan["tree"] = jax.tree_util.tree_flatten(out)
        index = {id(x): i for i, x in enumerate(local_leaves)}
        plan["src"] = tuple(index.get(id(x)) for x in out_leaves)
        plan["axes"] = jax.tree_util.tree_leaves(state_lane_axes(out))
        return out_leaves

    jax.make_jaxpr(traced, axis_env=[(axis_name, n_chips)])(
        [jax.ShapeDtypeStruct(local_shape(x.shape, ax), x.dtype)
         for x, ax in zip(leaves, in_ax)])
    src, out_ax = plan["src"], plan["axes"]

    def body(s_shard):
        comm = _lane_comm(axis_name, lanes_per_chip, n_chips)
        ins = to_local(jax.tree_util.tree_leaves(s_shard), comm)
        out = jax.tree_util.tree_leaves(
            fn(comm, jax.tree_util.tree_unflatten(treedef, ins)))
        assert all(x is ins[i] for x, i in zip(out, src) if i is not None), (
            "the program forwards other leaves than its probe trace did")
        return [x if comm.batched or ax < 0 else jnp.expand_dims(x, ax)
                for x, ax, i in zip(out, out_ax, src) if i is None]

    program = jax.jit(compat.shard_map(
        body, mesh,
        in_specs=(jax.tree_util.tree_map(spec_of, in_axes),),
        out_specs=[spec_of(ax) for ax, i in zip(out_ax, src) if i is None],
    ))
    placed = [NamedSharding(mesh, spec_of(ax)) for ax in in_ax]

    def call(s):
        old = jax.tree_util.tree_leaves(s)
        if not old[0].sharding.is_equivalent_to(placed[0], old[0].ndim):
            # a state built off the mesh (the source matrix on one device)
            # is placed once: the leaves a program forwards stay where they
            # are, and would otherwise cross to the mesh on every call
            old = [jax.device_put(x, sh) for x, sh in zip(old, placed)]
            s = jax.tree_util.tree_unflatten(treedef, old)
        with compat.set_mesh(mesh):
            fresh = iter(program(s))
        return jax.tree_util.tree_unflatten(
            plan["tree"], [next(fresh) if i is None else old[i] for i in src])

    return call, program, probe


class MeshHeal(HealProgram):
    """One REBUILD as a shard_map program under ``_lane_comm``, called like
    ``repro.ft.driver.recover_lanes`` (``HealProgram``): the orchestrator's
    heal on the SPMD path.

    The program runs ``recover_lanes`` on each chip's own lane block: the
    respawned lane's replay runs on its chip, over the chip's lanes as the
    sweep's segments run them, and each artifact it reads
    from a buddy moves as that buddy's slice alone — a gather on the chip,
    one collective-permute from another chip. The read ledger and the bytes
    that cross chips (``xchip_bytes``) are static data of the traced
    program."""

    def __init__(self, mesh, axis_name: str, lanes_per_chip: int, state,
                 newly, point, dead, scheme):
        super().__init__(newly)
        self.L = lanes_per_chip
        ledger = {}

        def fn(comm, local):
            left = set(dead)
            healed, events = recover_lanes(
                comm, local, self.newly, point, left,
                on_recovered=left.discard, scheme=scheme)
            ledger.update((e.lane, dict(e.reads)) for e in events)
            return healed

        self._call, self.program, probe = _mesh_program(
            mesh, axis_name, lanes_per_chip, fn, state)
        self.reads = ledger
        self.xchip_bytes = probe.xchip_bytes

    @property
    def chips(self) -> str:
        """The dead lanes' chips, as the ``ftqr.heal`` span names them."""
        return " ".join(str(lane // self.L) for lane in self.newly)

    @property
    def xchip_reads(self) -> int:
        """Artifacts read from a lane on another chip than the dead one."""
        return sum(src // self.L != lane // self.L
                   for lane, reads in self.reads.items()
                   for src in reads.values())

    def _run(self, state):
        return self._call(state), self.reads


def make_spmd_sweep_step(mesh=None, axis_name: str = "qr",
                         lanes_per_chip: int = 1):
    """Shard_map segment backend for the online orchestrator.

    Returns ``step(state) -> state`` executing ONE sweep point of the
    reified state machine (``repro.ft.online.state.sweep_step``) under
    ``shard_map`` over the mesh, ``lanes_per_chip`` lanes on each device
    (``_lane_comm``: ``MeshComm``, or ``AxisComm`` at one lane; the mesh
    axis holds ``P / lanes_per_chip`` devices). Between calls the
    ``SweepState`` lives as *global* lane-sharded arrays in the SimComm
    layout — the host-side orchestrator probes sentinels and injects
    deaths on that global layout with the SimComm mask primitives, while
    every compiled segment runs the per-device program. Each device's
    block of a leaf is the SimComm layout of its own lanes, which the body
    hands to ``sweep_step`` as it is (``MeshComm``) or squeezed to its one
    lane (``AxisComm``). One program is compiled per cursor position (the
    treedef carries the cursor) and cached for the lifetime of the returned
    callable; ``step.program(state)`` returns it (e.g. to read its
    compiled text).

    ``step.heal(state, newly, point, scheme)`` returns the REBUILD of
    ``newly`` at ``point`` as a cached ``MeshHeal`` program, which the
    orchestrator calls in place of ``recover_lanes``; ``step.chips`` and
    ``step.lanes_per_chip`` give the layout.
    """
    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    steps, heals = {}, {}

    def compiled(state):
        key = jax.tree_util.tree_structure(state)
        if key not in steps:
            steps[key] = _mesh_program(mesh, axis_name, lanes_per_chip,
                                       sweep_step, state)
        return steps[key]

    def program(state):
        """The jitted shard_map program of ``state``'s sweep point."""
        return compiled(state)[1]

    def step(state):
        return compiled(state)[0](state)

    def heal(state, newly, point, scheme) -> MeshHeal:
        key = (jax.tree_util.tree_structure(state), tuple(sorted(newly)),
               tuple(point), scheme.name, scheme.f)
        if key not in heals:
            heals[key] = MeshHeal(mesh, axis_name, lanes_per_chip, state,
                                  newly, point, set(newly), scheme)
        return heals[key]

    step.program = program
    step.heal = heal
    step.lanes_per_chip = lanes_per_chip
    step.chips = mesh.shape[axis_name]
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


# One segment runner per (mesh, axis, lanes per chip): repeated online sweeps
# on a mesh share its compiled per-point and heal programs, like
# ``compiled_segment`` under SimComm.
_ONLINE_STEPS = {}


def ft_caqr_sweep_online_spmd(
    A: jax.Array,
    panel_width: int,
    detector=None,
    mesh=None,
    axis_name: str = "qr",
    lanes_per_chip: int = 1,
    **orchestrator_kw,
) -> FTSweepResult:
    """Online recovery on the production SPMD path: host-side orchestrator,
    shard_map segments, runtime failure detection — no trace-time schedule.

    ``A`` is the full ``(m, n)`` matrix, row-sharded over the mesh in
    ``P = devices * lanes_per_chip`` lane blocks (consecutive blocks on a
    device; ``m`` must divide by ``P``). A death is healed by one
    ``MeshHeal`` program on the lanes' own devices, reading only the dead
    lane's buddies. Extra keywords (``fault_hooks``, ``segment_points``,
    ``store``/``persist_every``, ...) pass through to
    ``repro.ft.online.orchestrator.SweepOrchestrator``. The result layout is
    the SimComm layout, directly comparable to both the simulator and the
    scheduled SPMD entry.
    """
    from repro.ft.online.orchestrator import SweepOrchestrator

    if mesh is None:
        mesh = make_lane_mesh(axis_name=axis_name)
    n_lanes = mesh.shape[axis_name] * lanes_per_chip
    m, n = A.shape
    assert m % n_lanes == 0, (
        f"rows ({m}) must block-shard evenly over {n_lanes} lanes"
    )
    key = (mesh, axis_name, lanes_per_chip)
    if key not in _ONLINE_STEPS:
        _ONLINE_STEPS[key] = make_spmd_sweep_step(mesh, axis_name,
                                                  lanes_per_chip)
    orch = SweepOrchestrator(
        A.reshape(n_lanes, m // n_lanes, n), SimComm(n_lanes), panel_width,
        detector=detector,
        step_fn=_ONLINE_STEPS[key],
        **orchestrator_kw,
    )
    return orch.run()
