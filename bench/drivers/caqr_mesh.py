"""Driver: back-to-back FT-CAQR factorizations of one seeded matrix through
``ft_caqr_sweep_online_spmd`` on a mesh of chips, ``lanes_per_chip`` lanes
on each under the two-level ``MeshComm``, optionally with a lane killed at
a fixed sweep point in every factorization and healed by the mesh heal
program on the lanes' own chips.

Config keys: those of ``caqr_sweep`` and ``lanes_per_chip``; the cell uses
``lanes / lanes_per_chip`` chips. Traffic keys: ``kill``, as ``caqr_sweep``.

Everything else is ``caqr_sweep``'s: the timed window, the end-to-end
numbers, the spans and the comparison (``gram_residual``,
``r_below_diagonal``, ``r_bits_differing``, ``wrong_rebuilds``,
``non_buddy_reads``), against the same float64 reference
(``bench/reference.py``). The matrix is ``caqr_sweep``'s, row-sharded over
the chips, consecutive blocks on a chip. Telemetry adds
``heal_xchip_bytes``: per factorization, the bytes its heal moved from one
chip to another (``RecoveryEvent.xchip_bytes``).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# the two-level comm, imported before the rest of the program: a program
# without it cannot run this cell, and the run stops here at once
from repro.core.comm import MeshComm  # noqa: F401
from repro.launch.spmd_qr import ft_caqr_sweep_online_spmd, make_lane_mesh

from bench.drivers import caqr_sweep
from bench.drivers.caqr_sweep import _bits_differing, _lane0, _span, _Spans
from repro.ft.online.detect import ScriptedKiller


class Cell(caqr_sweep.Cell):
    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int,
                 tracing: bool = False):
        super().__init__(config, traffic, limits, seed, tracing)
        self.lanes_per_chip = config["lanes_per_chip"]
        self.chips = self.lanes // self.lanes_per_chip
        self.mesh = make_lane_mesh(self.chips)

    def _factorize(self):
        hooks = [] if self.point is None else [
            ScriptedKiller({self.point: [self.kill_lane]})]
        spans = _Spans() if self.tracing else None
        with _span("bench.factorize", self.tracing):
            if spans is not None:
                spans.enter("bench.segment.leaf")
            res = ft_caqr_sweep_online_spmd(
                self.A, self.b, mesh=self.mesh,
                lanes_per_chip=self.lanes_per_chip, fault_hooks=hooks,
                boundary_hooks=[] if spans is None else [spans])
            R0 = _lane0(res.R).block_until_ready()
            if spans is not None:
                spans.close()
        return R0, res.events

    def setup(self, seconds: float) -> None:
        # caqr_sweep's matrix (its check draws it again), row-sharded over
        # the chips; then the set-up of caqr_sweep
        A = caqr_sweep.gaussian_lanes(caqr_sweep.prng_key(self.seed),
                                      self.lanes, self.m, self.n)
        rows = NamedSharding(self.mesh, P(self.mesh.axis_names[0], None))
        self.A = jax.device_put(A.reshape(self.m, self.n), rows)
        del A
        self.A.block_until_ready()
        saved, self.point = self.point, None
        self.R_free, _ = self._factorize()          # failure-free R
        self.point = saved
        _bits_differing(self.R_free, self.R_free).block_until_ready()
        if self.point is not None:
            self._factorize()                       # warm the heal's shapes

    def telemetry(self) -> dict:
        return {**super().telemetry(),
                "chips": self.chips, "lanes_per_chip": self.lanes_per_chip,
                "heal_xchip_bytes": [sum(e.xchip_bytes for e in ev)
                                     for _, ev in self.records]}
