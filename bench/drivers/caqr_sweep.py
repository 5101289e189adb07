"""Driver: back-to-back FT-CAQR factorizations of one seeded matrix through
``ft_caqr_sweep_online`` (the orchestrator path) on ``SimComm`` lanes of one
chip, optionally with a lane killed at a fixed sweep point in every
factorization and healed by REBUILD.

Traffic keys: ``kill`` (absent, or ``{"lane", "panel", "phase", "level"}``).
Config keys: ``m_rows``, ``n_cols``, ``panel``, ``lanes``.

The window repeats factorizations until ``--seconds`` have passed; each one
ends in ``block_until_ready`` on R, and a kill and its heal lie inside it.
``factor_s`` is the window's wall time over the factorizations completed.

Correctness, after the window and with the program's state freed:

- ``gram_residual``: the first window R against ``A^T A`` in float64 on the
  host, ``||R^T R - A^T A||_F / ||A||_F^2``;
- ``r_below_diagonal``: the largest entry of that R below its diagonal,
  over its largest entry; with the Gram residual it fixes R up to the
  signs of its rows, where the residual alone admits any rotation of R;
- ``r_bits_differing``: elements of every window R that differ bit for bit
  from the failure-free R of the same matrix, computed in set-up. In the
  kill cell this is the recovery guarantee: the healed R is the
  failure-free R;
- ``wrong_rebuilds``: factorizations whose REBUILD log is not exactly the
  planted one (none at all without a kill);
- ``non_buddy_reads``: artifacts a REBUILD read from a lane other than the
  dead lane's XOR buddies.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import reference
from repro.core.comm import SimComm
from repro.ft.failures import sweep_point
from repro.ft.online.detect import ScriptedKiller
from repro.ft.online.orchestrator import ft_caqr_sweep_online


def prng_key(seed: int):
    """A key from any non-negative seed, 64 bits and more included."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def gaussian_lanes(key, lanes: int, m: int, n: int):
    """The ``m x n`` Gaussian matrix in the SimComm block-row layout, made
    on the device in one call."""
    return jax.random.normal(key, (m, n), jnp.float32).reshape(
        lanes, m // lanes, n)


@jax.jit
def _lane0(R):
    return R[0]   # every lane holds the same R


@jax.jit
def _bits_differing(a, b):
    return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32)
                   != jax.lax.bitcast_convert_type(b, jnp.uint32))


class _Spans:
    """Boundary hook: one host span per segment, named by the sweep phase
    the next segment runs (``bench.segment.<phase>``), and
    ``bench.finalize`` from the last boundary to the assembled R."""

    def __init__(self):
        self.open: Optional[TraceAnnotation] = None

    def enter(self, name: str) -> None:
        self.close()
        self.open = TraceAnnotation(name)
        self.open.__enter__()

    def close(self) -> None:
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None

    def __call__(self, orch) -> None:
        cur = orch.state.cursor
        self.enter("bench.finalize" if cur is None
                   else f"bench.segment.{cur[1]}")


class Cell:
    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int,
                 tracing: bool = False):
        self.limits = limits
        self.m, self.n = config["m_rows"], config["n_cols"]
        self.b, self.lanes = config["panel"], config["lanes"]
        self.seed = seed
        self.tracing = tracing
        self.comm = SimComm(self.lanes)
        kill = traffic.get("kill")
        self.kill_lane = None if kill is None else kill["lane"]
        self.point = None if kill is None else sweep_point(
            kill["panel"], kill["phase"], kill["level"])
        levels = self.lanes.bit_length() - 1
        self.buddies = set() if kill is None else {
            self.kill_lane ^ (1 << s) for s in range(levels)}
        self.records: List[tuple] = []
        self.wall = 0.0

    # -- the timed path ------------------------------------------------------

    def _factorize(self):
        hooks = [] if self.point is None else [
            ScriptedKiller({self.point: [self.kill_lane]})]
        spans = _Spans() if self.tracing else None
        with _span("bench.factorize", self.tracing):
            if spans is not None:
                spans.enter("bench.segment.leaf")
            res = ft_caqr_sweep_online(
                self.A, self.comm, self.b, fault_hooks=hooks,
                boundary_hooks=[] if spans is None else [spans])
            R0 = _lane0(res.R).block_until_ready()
            if spans is not None:
                spans.close()
        return R0, res.events

    def setup(self, seconds: float) -> None:
        self.A = gaussian_lanes(prng_key(self.seed), self.lanes, self.m,
                                self.n).block_until_ready()
        saved, self.point = self.point, None
        self.R_free, _ = self._factorize()          # failure-free R
        self.point = saved
        _bits_differing(self.R_free, self.R_free).block_until_ready()
        if self.point is not None:
            self._factorize()                       # warm the heal's shapes

    def window(self, seconds: float) -> None:
        self.records, self.R_first = [], None
        t0 = time.perf_counter()
        with _span("bench.window", self.tracing):
            while True:
                R0, events = self._factorize()
                if self.R_first is None:
                    self.R_first = R0
                self.records.append((_bits_differing(R0, self.R_free),
                                     events))
                del R0
                if time.perf_counter() - t0 >= seconds:
                    break
        self.wall = time.perf_counter() - t0

    # -- what the harness reads ----------------------------------------------

    def window_report(self) -> dict:
        return {"factorizations": len(self.records), "window_s": self.wall}

    def end_to_end(self, peak_bytes: Optional[int]) -> dict:
        return {"factor_s": self.wall / len(self.records),
                "peak_hbm_gib": None if peak_bytes is None
                else peak_bytes / 2 ** 30}

    def telemetry(self) -> dict:
        return {
            "factorizations": len(self.records),
            "window_s": self.wall,
            "shape": {"m": self.m, "n": self.n, "b": self.b,
                      "lanes": self.lanes},
            "rebuild_s": [sum(e.elapsed_s for e in ev)
                          for _, ev in self.records],
            "kill": self.point is not None,
        }

    def release(self) -> None:
        self.R_first = np.asarray(self.R_first)
        self.bits = [int(b) for b, _ in self.records]
        self.A = self.R_free = None

    # -- the comparison ------------------------------------------------------

    def _events_ok(self, events) -> bool:
        if self.point is None:
            return not events
        return (len(events) == 1 and events[0].lane == self.kill_lane
                and tuple(events[0].point) == self.point)

    def check(self):
        A = gaussian_lanes(prng_key(self.seed), self.lanes, self.m, self.n)
        G = reference.gram(np.asarray(A[i]) for i in range(self.lanes))
        del A
        residual = reference.gram_residual(self.R_first, G)
        wrong = [not self._events_ok(ev) for _, ev in self.records]
        non_buddy = sum(1 for _, ev in self.records for e in ev
                        for src in e.reads.values()
                        if src not in self.buddies)
        limits = self.limits
        failed = sum(1 for bits, bad in zip(self.bits, wrong) if bits or bad)
        if residual > limits["gram_residual"]:
            failed = len(self.records)
        below = reference.below_diagonal(self.R_first)
        if below > 0:
            failed = len(self.records)
        checks = [("gram_residual", residual, limits["gram_residual"]),
                  ("r_below_diagonal", below, 0),
                  ("r_bits_differing", sum(self.bits), 0),
                  ("wrong_rebuilds", sum(wrong), 0),
                  ("non_buddy_reads", non_buddy, 0)]
        return len(self.records), failed, checks


def _span(name: str, on: bool):
    return TraceAnnotation(name) if on else contextlib.nullcontext()
