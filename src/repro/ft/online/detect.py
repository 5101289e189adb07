"""Runtime failure detection for the online sweep (DESIGN.md §9).

No trace-time schedule: the orchestrator polls a detector at every segment
boundary and deaths are *discovered*, not scripted. Detection is the one
place the simulation meets the paper's §II model — FT-MPI surfaces a death
to survivors at their next collective involving the failed rank; here the
mask-based death representation (``comm.poison`` NaN-floods everything the
lane holds) makes the same information observable in-band: a designated
*sentinel slot* per lane goes NaN.

Detectors (the ``OnlineDetector`` protocol):

* ``NaNSentinelDetector`` — probes sentinel slots of the lane-sharded state
  between segments (element ``[0, 0]`` of each lane's block-row slice, plus
  the in-flight R/C' heads): ONE compiled program per poll and O(P)
  scalars transferred; a ``deep`` mode scans every float leaf for
  hardening/debugging. Latency bound: a death is reported at the first
  boundary after it happens — one segment. ``poll`` is the split form
  ``collect(probe(...))``: ``probe`` dispatches the program and returns a
  handle, ``collect`` materializes it, so a caller may dispatch device work
  between the two.
* ``FailStopDetector`` — injectable test double: the harness ``declare``-s a
  death and the detector reports it after ``report_delay`` polls (0 = the
  very next boundary; 1 = one segment late, the false-negative case).
* ``DelayedDetector`` — wraps any detector and suppresses each lane's first
  ``miss`` positive reports: models a detector false-negative on an
  otherwise-real probe (used by the one-segment-late regression test).

Fault injectors (the *cause*, distinct from detection): boundary hooks the
orchestrator runs before each poll, poisoning state exactly like a
scheduled death does — ``ScriptedKiller`` (die at a chosen sweep point) and
``WallClockKiller`` (die at the first boundary past a wall-clock deadline,
the genuinely unscripted demo). Both leave discovery entirely to the
detector.
"""
from __future__ import annotations

import functools
import operator
import time
from typing import Any, Dict, Iterable, List, Optional, Protocol, \
    Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft.failures import prev_sweep_point
from repro.ft.online.state import SweepState, state_lane_axes


class OnlineDetector(Protocol):
    """Runtime failure detector: polled by the orchestrator at every
    segment boundary; returns the lanes it believes died since the last
    poll (never lanes it already reported — the orchestrator rebuilds them
    immediately, so a repeat report would re-kill a healthy respawn)."""

    def poll(self, comm, state: SweepState) -> List[int]:  # pragma: no cover
        ...

    def revive(self, lane: int) -> None:  # pragma: no cover
        """Optional: the orchestrator announces a completed REBUILD so the
        detector re-arms for ``lane`` immediately — without it, a
        stateful detector needs one clean poll before it can see the same
        lane die again, and back-to-back deaths at consecutive boundaries
        would go unreported."""


# The probed fields: the block row plus the in-flight per-lane artifacts,
# each with its lane axis first.
_PROBED_FIELDS = ("A", "window", "R_leaf", "R_carry", "C_prime")


def _probed(state: SweepState) -> Tuple:
    """The probed fields present at the state's cursor, in a fixed order."""
    return tuple(x for x in (getattr(state, f) for f in _PROBED_FIELDS)
                 if x is not None)


@jax.jit
def _sentinel_probe(fields: Tuple) -> jax.Array:
    """One float per lane: the sum of the head element of each lane's slice
    of every probed field (NaN iff any head is NaN), as ONE compiled program
    returning a length-P device array.

    The heads are read in place: a flattening reshape would copy the whole
    field on TPU's tiled layout. The program is keyed on the fields' shapes
    alone, not on the whole state, so the sweep's cursors share far fewer
    specializations than they have points."""
    heads = [x[(slice(None),) + (0,) * (x.ndim - 1)] for x in fields]
    return functools.reduce(operator.add, heads)


def _deep_nan_lanes(comm, state: SweepState) -> Set[int]:
    """Full scan: any-NaN per lane over every float leaf (lane axis from
    ``state_lane_axes``)."""
    P = comm.axis_size()
    hit: Set[int] = set()
    axes = state_lane_axes(state)
    for x, ax in zip(jax.tree_util.tree_leaves(state),
                     jax.tree_util.tree_leaves(axes)):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            continue
        per_lane = jnp.any(jnp.isnan(jnp.moveaxis(x, ax, 0).reshape(P, -1)),
                           axis=1)
        hit.update(int(i) for i in np.flatnonzero(np.asarray(per_lane)))
    return hit


class NaNSentinelDetector:
    """Sentinel-slot NaN probe over the lane-sharded state.

    The mask-based death model NaN-floods everything a dead lane holds, so
    probing one designated slot per live artifact detects any fail-stop
    death at the next boundary. ``deep=True`` scans every float leaf
    instead (O(state) work — debugging / belt-and-braces). Reports each
    lane once per death: after the orchestrator rebuilds it the sentinels
    are finite again and the lane re-arms.

    Caveat (documented, inherent to in-band detection): a workload whose
    *data* legitimately contains NaN would false-positive; the CAQR sweep
    on finite input never produces NaN in a live lane.
    """

    def __init__(self, deep: bool = False):
        self.deep = deep
        self._reported: Set[int] = set()

    def poll(self, comm, state: SweepState) -> List[int]:
        """The lanes newly dead at this boundary: one probe dispatch and one
        transfer of P floats (``deep``: the full scan)."""
        return self.collect(comm, self.probe(comm, state))

    # -- non-blocking probe (the async orchestrator's poll) -----------------

    def probe(self, comm, state: SweepState) -> Any:
        """Dispatch the sentinel reduction WITHOUT materializing it and
        return an opaque handle for :meth:`collect`. Under jax's async
        dispatch the reduction runs while the host does other work — the
        blocking transfer is deferred to ``collect``. ``deep`` mode has no
        compiled form; its handle just defers the full scan."""
        if self.deep:
            return ("deep", state)
        return ("sent", _sentinel_probe(_probed(state)))

    def collect(self, comm, handle: Any) -> List[int]:
        """Materialize a :meth:`probe` handle into the newly-dead list —
        the blocking half of the split poll. Same report-once semantics as
        ``poll``: a lane is returned at most once per death and re-arms
        after ``revive`` (or automatically once its sentinels are finite)."""
        kind, payload = handle
        if kind == "deep":
            hit = _deep_nan_lanes(comm, payload)
        else:
            hit = {int(i) for i in np.flatnonzero(np.isnan(np.asarray(payload)))}
        newly = sorted(hit - self._reported)
        self._reported = hit
        return newly

    def revive(self, lane: int) -> None:
        self._reported.discard(lane)

    def reset(self) -> None:
        """Re-arm every sentinel. The elastic orchestrator calls this
        after a world transition: lane numbering changed, so per-lane
        report state from the old world is meaningless (the probe itself
        is shape-agnostic and works on the new layout unchanged)."""
        self._reported.clear()


class FailStopDetector:
    """Injectable fail-stop oracle for tests: the harness declares deaths,
    the detector surfaces each one ``report_delay`` polls later (0 = next
    boundary — the fail-fast model; 1 = one segment late — the
    false-negative latency case)."""

    def __init__(self, report_delay: int = 0):
        self.report_delay = report_delay
        self._pending: Dict[int, int] = {}  # lane -> polls still to wait

    def declare(self, lane: int) -> None:
        self._pending.setdefault(lane, self.report_delay)

    def poll(self, comm, state: SweepState) -> List[int]:
        ready = sorted(l for l, d in self._pending.items() if d <= 0)
        for l in list(self._pending):
            if l in ready:
                del self._pending[l]
            else:
                self._pending[l] -= 1
        return ready

    def revive(self, lane: int) -> None:
        pass  # reports are one-shot; a new death needs a new declare()


class DelayedDetector:
    """Suppress each lane's first ``miss`` positive reports from ``inner``
    — a detector false-negative model over a real probe. The suppressed
    death surfaces at a later boundary (the NaN sentinels are still NaN),
    so the one-segment-late recovery path is exercised end to end."""

    def __init__(self, inner: OnlineDetector, miss: int = 1):
        self.inner = inner
        self.miss = miss
        self._suppressed: Dict[int, int] = {}

    def poll(self, comm, state: SweepState) -> List[int]:
        out = []
        for lane in self.inner.poll(comm, state):
            seen = self._suppressed.get(lane, 0)
            if seen < self.miss:
                self._suppressed[lane] = seen + 1
                # re-arm the inner detector so it re-reports next poll
                rearm = getattr(self.inner, "_reported", None)
                if rearm is not None:
                    rearm.discard(lane)
            else:
                self._suppressed.pop(lane, None)
                out.append(lane)
        return out

    def revive(self, lane: int) -> None:
        self._suppressed.pop(lane, None)
        revive = getattr(self.inner, "revive", None)
        if revive is not None:
            revive(lane)


# -- fault injectors (boundary hooks; the cause, not the detection) ----------


def _just_completed(state: SweepState) -> Optional[Tuple[int, str, int]]:
    return prev_sweep_point(state.cursor, state.geom.n_panels,
                            state.geom.levels)


class ScriptedKiller:
    """Boundary hook: poison ``lanes`` when the just-completed sweep point
    matches a key of ``events`` — the runtime enactment of what a
    ``FailureSchedule`` scripts at trace time (each event fires once).
    Discovery is left entirely to the detector."""

    def __init__(self, events: Dict[Tuple[int, str, int], Iterable[int]]):
        self.events = {k: list(v) for k, v in events.items()}
        self._fired: Set[Tuple[Tuple[int, str, int], int]] = set()

    def __call__(self, comm, state: SweepState) -> SweepState:
        from repro.ft.driver import obliterate_state

        point = _just_completed(state)
        for lane in self.events.get(point, []):
            if (point, lane) not in self._fired:
                self._fired.add((point, lane))
                state = obliterate_state(comm, state, lane)
        return state


class WallClockKiller:
    """Boundary hook: poison ``lane`` at the first segment boundary more
    than ``after_s`` wall-clock seconds after the hook's first invocation —
    a death whose sweep position is chosen by the clock, not the trace
    (``examples/online_recovery.py``). Records where it struck in
    ``.struck_at``."""

    def __init__(self, after_s: float, lane: int, clock=time.monotonic):
        self.after_s = after_s
        self.lane = lane
        self.clock = clock  # injectable for deterministic tests (fake clock)
        self._t0: Optional[float] = None
        self.struck_at: Optional[Tuple[int, str, int]] = None

    def __call__(self, comm, state: SweepState) -> SweepState:
        from repro.ft.driver import obliterate_state

        now = self.clock()
        if self._t0 is None:
            self._t0 = now
        if self.struck_at is None and now - self._t0 >= self.after_s \
                and state.cursor is not None:
            self.struck_at = _just_completed(state)
            if self.struck_at is not None:  # not before the first point
                state = obliterate_state(comm, state, self.lane)
        return state
