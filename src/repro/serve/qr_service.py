"""QR-as-a-service: continuous batching of FT-CAQR *sweeps*.

The serving counterpart of the token engine (``repro.serve.engine``): many
concurrent ragged-shape factorization / least-squares requests multiplex
through ONE resident compiled ``sweep_step`` program. The cadence is the
decode engine's prefill/insert/generate loop transposed onto panel sweeps:

* **buckets** — every request ``(m, n)`` is zero-padded into one of a few
  compiled geometry buckets ``(m_loc, n_bucket)`` via ``block_row_layout``
  + PR 3's ``sweep_geometry``. Zero padding is exact (DESIGN.md §7), so
  the bucket embedding changes no tenant's answer; a handful of buckets
  bounds the number of compiled geometries the way shape buckets bound a
  serving engine's prefill shapes.
* **continuous batching at panel boundaries** — each :meth:`QRService.tick`
  advances every resident request by exactly one panel (one compiled
  segment of ``1 + 2*levels`` sweep points), then does the boundary work:
  detect/heal, retire, admit. New requests join the resident batch only at
  this boundary (the way new prompts join a decode batch between steps);
  finished requests retire their R / lstsq solution *early* — after
  ``ceil(k_req / b)`` panels, not the full bucket sweep — and free the
  slot.
* **one resident program** — all slots of all buckets dispatch through the
  single process-wide ``repro.ft.online.orchestrator.compiled_segment``
  runner; jax's jit cache specializes it per (bucket, cursor) treedef, so
  after one warm sweep per bucket NO new compilation happens under any
  traffic mix (:attr:`QRService.compiled_programs` counts the resident
  specializations; the serve bench asserts it stays flat).
* **mid-batch failures heal online** — a lane death (``kill_lane``) NaN-
  floods that lane's slice of *every* resident tenant's state. Each slot
  carries its own ``NaNSentinelDetector``; the boundary poll discovers the
  death and the same ``recover_lanes`` REBUILD the orchestrator uses heals
  each tenant from its XOR-buddy bundles — no request is dropped, and
  every retired R stays bitwise-identical to the R of the same request
  served alone with no failure (``tests/test_serve.py``).

Least squares rides the factorization: a request with a right-hand side is
admitted as the augmented matrix ``[A | b]`` (the rhs columns sit beyond
the tenant's ``n_req`` in the bucket, so they are trailing-updated to
``Q^T b`` by the very panels that produce R), and retirement back-solves
``R1 x = (Q^T b)[:k]`` host-side — same semantics as ``caqr_lstsq``
including the wide-problem *basic* solution.

``drain_batched`` is the express static-batch path for offline bulk work:
group the queue by bucket and run each group through
``caqr_factorize_batched`` (one vmapped program per bucket) — the same
results to float32 rounding, no mid-flight admission.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.caqr import (
    block_row_layout,
    caqr_factorize_batched,
    sweep_geometry,
)
from repro.core.comm import SimComm
from repro.ft.driver import RecoveryEvent, obliterate_state, recover_lanes
from repro.ft.failures import prev_sweep_point
from repro.ft.online.detect import NaNSentinelDetector
from repro.ft.online.orchestrator import compiled_segment
from repro.ft.online.state import (
    SweepState,
    deposit_boundary,
    initial_sweep_state,
    panel_points,
)


@dataclasses.dataclass(frozen=True)
class QRRequest:
    """One tenant's problem: factorize ``A`` (and, with ``rhs``, solve
    min ||Ax - rhs||). Host numpy, any ragged shape that fits a bucket."""

    rid: str
    A: np.ndarray                       # (m, n)
    rhs: Optional[np.ndarray] = None    # (m, nrhs)

    @property
    def shape(self) -> Tuple[int, int]:
        return tuple(self.A.shape)

    @property
    def k(self) -> int:
        return min(self.A.shape)


@dataclasses.dataclass
class QRResult:
    """A retired request: the tenant-shaped R slice (and x for lstsq
    requests), plus the service telemetry the bench aggregates."""

    rid: str
    R: np.ndarray                       # (k_req, n_req)
    x: Optional[np.ndarray]             # (n_req, nrhs) or None
    bucket: Tuple[int, int]
    panels: int
    ticks_resident: int
    latency_s: float                    # submit -> retire (incl. queue wait)
    events: List[RecoveryEvent]         # REBUILDs that hit this tenant


@dataclasses.dataclass
class _Slot:
    req: QRRequest
    bucket: Tuple[int, int]
    state: SweepState
    detector: NaNSentinelDetector
    panels_needed: int
    panels_done: int = 0
    admitted_tick: int = 0
    events: List[RecoveryEvent] = dataclasses.field(default_factory=list)


class QRService:
    """Multi-tenant continuous-batching front end over the online sweep.

    Parameters
    ----------
    comm:
        ``SimComm(P)`` — the service drives jitted host segments, which
        (like the orchestrator's) require the SimComm layout.
    panel_width:
        b. One value service-wide: the segment size ``1 + 2*levels``
        depends only on P, so every bucket shares the one resident runner.
    buckets:
        The compiled geometry menu, ``(m_loc, n)`` pairs (per-lane rows,
        working columns incl. any rhs columns). A request picks the first
        bucket that fits (sorted by area — smallest sufficient bucket);
        submission raises if none fits.
    max_slots:
        Resident-batch capacity. Requests beyond it queue and are admitted
        as slots free up — admission is strictly FIFO.
    """

    def __init__(self, comm, panel_width: int = 4,
                 buckets: Sequence[Tuple[int, int]] = ((8, 12),),
                 max_slots: int = 8):
        assert isinstance(comm, SimComm), (
            "QRService drives jitted host segments (SimComm layout); the "
            "SPMD serving path would thread step_fn= like the orchestrator")
        self.comm = comm
        self.P = comm.axis_size()
        self.b = panel_width
        self.buckets = sorted(
            (tuple(bk) for bk in buckets), key=lambda bk: bk[0] * bk[1])
        for m_loc, n in self.buckets:
            assert m_loc >= 1 and n >= 1, (m_loc, n)
        self.max_slots = max_slots
        self.queue: List[QRRequest] = []
        self.slots: List[Optional[_Slot]] = [None] * max_slots
        self.results: Dict[str, QRResult] = {}
        self.tick_count = 0
        self._pending_kills: List[int] = []
        self._submit_t: Dict[str, float] = {}
        self._rid_counter = itertools.count()
        levels = self.P.bit_length() - 1
        self._points_per_panel = 1 + 2 * levels
        # THE resident program (shared with every SweepOrchestrator over
        # the same comm): one jitted segment runner, specialized by jax
        # per (bucket, cursor) treedef.
        self._segment = compiled_segment(comm, self._points_per_panel)

    # -- admission ---------------------------------------------------------

    def select_bucket(self, m: int, n_total: int) -> Tuple[int, int]:
        """Smallest bucket fitting an ``(m, n_total)`` problem (n_total
        counts rhs columns — they ride in the bucket's spare width)."""
        for m_loc, n_b in self.buckets:
            if m <= self.P * m_loc and n_total <= n_b:
                return (m_loc, n_b)
        raise ValueError(
            f"no bucket fits ({m}, {n_total}); buckets={self.buckets}")

    def submit(self, A: np.ndarray, rhs: Optional[np.ndarray] = None,
               rid: Optional[str] = None) -> str:
        """Enqueue a request; it joins the resident batch at the next
        panel boundary with a free slot. Returns the request id."""
        A = np.asarray(A, np.float32)
        assert A.ndim == 2, A.shape
        if rhs is not None:
            rhs = np.asarray(rhs, np.float32)
            assert rhs.shape[0] == A.shape[0], (A.shape, rhs.shape)
        if rid is None:
            rid = f"req{next(self._rid_counter)}"
        n_total = A.shape[1] + (0 if rhs is None else rhs.shape[1])
        self.select_bucket(A.shape[0], n_total)  # fail fast on misfit
        self._submit_t[rid] = time.perf_counter()
        self.queue.append(QRRequest(rid=rid, A=A, rhs=rhs))
        return rid

    def kill_lane(self, lane: int) -> None:
        """Schedule a lane death: at the next boundary, ``lane``'s slice of
        EVERY resident tenant's state is poisoned (the fail-stop model —
        one process dies, all tenants it hosted lose that block-row)."""
        assert 0 <= lane < self.P, lane
        self._pending_kills.append(lane)

    def _admit(self, req: QRRequest, slot_idx: int) -> None:
        n_req = req.A.shape[1]
        nrhs = 0 if req.rhs is None else req.rhs.shape[1]
        bucket = self.select_bucket(req.A.shape[0], n_req + nrhs)
        m_loc, n_b = bucket
        A_aug = req.A if req.rhs is None else np.concatenate(
            [req.A, req.rhs], axis=1)
        A0 = block_row_layout(jnp.asarray(A_aug), self.P, m_loc, n_b)
        state = initial_sweep_state(self.comm, A0, self.b)
        assert panel_points(state.geom) == self._points_per_panel
        panels_needed = -(-req.k // self.b)
        assert panels_needed <= state.geom.n_panels
        self.slots[slot_idx] = _Slot(
            req=req, bucket=bucket, state=state,
            detector=NaNSentinelDetector(), panels_needed=panels_needed,
            admitted_tick=self.tick_count)

    # -- the service cycle -------------------------------------------------

    def tick(self) -> List[QRResult]:
        """One service cycle: advance every resident slot one panel, then
        the boundary work — inject pending kills, detect + heal, retire
        finished tenants, admit queued requests into freed slots. Returns
        the requests retired this tick."""
        active = [s for s in self.slots if s is not None]
        # 1. advance: one compiled panel-segment per resident slot
        for slot in active:
            if slot.state.cursor is not None:
                slot.state = self._segment(slot.state)
            slot.panels_done += 1
        # 2. fault injection (the boundary is where deaths surface)
        kills, self._pending_kills = self._pending_kills, []
        for lane in kills:
            for slot in active:
                slot.state = obliterate_state(self.comm, slot.state, lane)
        # 3. detect + heal every tenant (same REBUILD as the orchestrator)
        for slot in active:
            newly = slot.detector.poll(self.comm, slot.state)
            if newly:
                self._heal(slot, newly)
        # 4. retire
        retired: List[QRResult] = []
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.panels_done >= slot.panels_needed:
                retired.append(self._retire(slot))
                self.slots[i] = None
        # 5. admit (new tenants join at the panel boundary)
        for i, slot in enumerate(self.slots):
            if slot is None and self.queue:
                self._admit(self.queue.pop(0), i)
        self.tick_count += 1
        return retired

    def run_until_drained(self, max_ticks: int = 10_000) -> Dict[str, QRResult]:
        """Tick until the queue and every slot are empty."""
        for _ in range(max_ticks):
            if not self.queue and all(s is None for s in self.slots):
                return self.results
            self.tick()
        raise RuntimeError(f"service not drained after {max_ticks} ticks")

    @property
    def resident(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def compiled_programs(self) -> int:
        """Resident specializations of the one segment runner (per
        bucket-cursor treedef). Flat after warmup — the serve bench and
        tests assert steady-state traffic compiles nothing new."""
        return self._segment._cache_size()

    # -- recovery ----------------------------------------------------------

    def _heal(self, slot: _Slot, newly: List[int]) -> None:
        geom = slot.state.geom
        point = prev_sweep_point(
            slot.state.cursor, geom.n_panels, geom.levels)
        assert point is not None, (
            "death detected on a tenant that never ran a segment")
        dead = set(newly)
        slot.state, events = recover_lanes(
            self.comm, slot.state, sorted(newly), point, dead,
            sync=lambda s: jax.block_until_ready(
                jax.tree_util.tree_leaves(s)),
            on_recovered=slot.detector.revive)
        slot.events.extend(events)

    # -- retirement --------------------------------------------------------

    def _partial_R(self, state: SweepState, n_panels: int) -> np.ndarray:
        """Assemble the upper-trapezoidal R of the first ``n_panels``
        deposited panels (the early-retirement slice of ``assemble_R``:
        identical arithmetic, rows stop at the tenant's frontier)."""
        rows = jnp.stack(state.R_rows[:n_panels])  # (p, P, b, n_work)
        geom = state.geom
        R = rows.swapaxes(0, 1).reshape(
            self.P, n_panels * geom.b, geom.n_work)
        return np.asarray(jnp.triu(R)[0])  # replicated; lane 0's copy

    def _retire(self, slot: _Slot) -> QRResult:
        state, deposited = deposit_boundary(self.comm, slot.state)
        assert deposited >= slot.panels_needed, (deposited, slot.panels_needed)
        req = slot.req
        m_req, n_req = req.shape
        k_req = req.k
        R_full = self._partial_R(state, slot.panels_needed)
        R = R_full[:k_req, :n_req]
        x = None
        if req.rhs is not None:
            # the rhs columns were trailing-updated to Q^T b by the same
            # panels that deposited R: back-solve R1 x1 = (Q^T b)[:k]
            # (wide requests get the basic solution — caqr_lstsq semantics)
            nrhs = req.rhs.shape[1]
            Qtb = R_full[:k_req, n_req:n_req + nrhs]
            x1 = jax.scipy.linalg.solve_triangular(
                jnp.asarray(R[:, :k_req]), jnp.asarray(Qtb), lower=False)
            x = np.asarray(x1)
            if n_req > k_req:
                x = np.concatenate(
                    [x, np.zeros((n_req - k_req, nrhs), x.dtype)], axis=0)
        result = QRResult(
            rid=req.rid, R=R, x=x, bucket=slot.bucket,
            panels=slot.panels_needed,
            ticks_resident=self.tick_count - slot.admitted_tick + 1,
            latency_s=time.perf_counter() - self._submit_t.pop(req.rid),
            events=slot.events)
        self.results[req.rid] = result
        return result

    # -- the express static-batch path ------------------------------------

    def drain_batched(self) -> Dict[str, QRResult]:
        """Offline bulk mode: group the current queue by bucket and run
        each group through ``caqr_factorize_batched`` (one vmapped sweep
        per bucket — the batched bucket dispatch), bypassing the slot
        machinery. No mid-flight admission or failure handling; results
        match the continuous path to float32 rounding
        (``tests/test_serve.py``)."""
        by_bucket: Dict[Tuple[int, int], List[QRRequest]] = {}
        queue, self.queue = self.queue, []
        for req in queue:
            nrhs = 0 if req.rhs is None else req.rhs.shape[1]
            bucket = self.select_bucket(
                req.A.shape[0], req.A.shape[1] + nrhs)
            by_bucket.setdefault(bucket, []).append(req)
        out: Dict[str, QRResult] = {}
        for (m_loc, n_b), reqs in by_bucket.items():
            stack = jnp.stack([
                block_row_layout(
                    jnp.asarray(r.A if r.rhs is None else np.concatenate(
                        [r.A, r.rhs], axis=1)),
                    self.P, m_loc, n_b)
                for r in reqs])
            res = caqr_factorize_batched(
                stack, self.comm, self.b, collect_bundles=True)
            geom = sweep_geometry(self.P, m_loc, n_b, self.b)
            for i, req in enumerate(reqs):
                m_req, n_req = req.shape
                k_req = req.k
                # full-sweep R; rows past the tenant's frontier are below
                # its triangle, so the slice equals the early-retired one
                R_full = np.asarray(res.R[i, 0])
                R = R_full[:k_req, :n_req]
                x = None
                if req.rhs is not None:
                    nrhs = req.rhs.shape[1]
                    Qtb = R_full[:k_req, n_req:n_req + nrhs]
                    x1 = jax.scipy.linalg.solve_triangular(
                        jnp.asarray(R[:, :k_req]), jnp.asarray(Qtb),
                        lower=False)
                    x = np.asarray(x1)
                    if n_req > k_req:
                        x = np.concatenate(
                            [x, np.zeros((n_req - k_req, nrhs), x.dtype)],
                            axis=0)
                result = QRResult(
                    rid=req.rid, R=R, x=x, bucket=(m_loc, n_b),
                    panels=geom.n_panels, ticks_resident=1,
                    latency_s=time.perf_counter()
                    - self._submit_t.pop(req.rid),
                    events=[])
                self.results[req.rid] = result
                out[req.rid] = result
        return out
