"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced to plain intervals, in nanoseconds on the profiler's
common clock:

- ``ops``: every device operation, ``(name, start, end, device)``, from
  the ``XLA Ops`` line of each ``/device:*`` plane;
- ``spans``: the benchmark's own host spans (``jax.profiler.TraceAnnotation``
  names that start with ``bench.``), ``(name, start, end)``.

Everything below works on those lists alone, so a test can feed it a
small recorded trace (``testdata/``) with no accelerator and no profiler.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"

Op = Tuple[str, int, int, int]      # name, start_ns, end_ns, device index
Span = Tuple[str, int, int]         # name, start_ns, end_ns


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]

    @property
    def devices(self) -> List[int]:
        return sorted({d for *_, d in self.ops})

    def window(self) -> Tuple[int, int]:
        """The benchmark's traced window: its ``bench.window`` span."""
        for name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        raise ValueError("the trace holds no bench.window span")

    def to_json(self) -> dict:
        return {"ops": [list(o) for o in self.ops],
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=[tuple(o) for o in d["ops"]],
                   spans=[tuple(s) for s in d["spans"]])


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """A device event's operation name. A TPU trace names each event by its
    whole HLO instruction (``%vmap_jit_wy_apply__.2 = f32[...]
    custom-call(...)``); keep the instruction's own name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> Trace:
    """Read a profiler ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    device_index = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((op_name(ev.name), s,
                                s + int(ev.duration_ns), device_index))
            device_index += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    ops.sort(key=lambda o: o[1])
    spans.sort(key=lambda sp: sp[1])
    return Trace(ops=ops, spans=spans)


def load(path: str) -> Trace:
    """An ``.xplane.pb``, or a ``Trace.to_json`` file (optionally gzipped)."""
    if path.endswith(".xplane.pb"):
        return load_xplane(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return Trace.from_json(json.load(f))


# -- interval arithmetic -----------------------------------------------------


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: Trace, lo: int, hi: int, device: Optional[int] = None
            ) -> int:
    """Nanoseconds of [lo, hi) in which some operation ran on ``device``
    (every device pooled when ``None``)."""
    ivs = [(s, e) for _, s, e, d in trace.ops
           if device is None or d == device]
    return sum(e - s for s, e in union(clip(ivs, lo, hi)))


def busy_s_per_device(trace: Trace) -> float:
    """Busy seconds inside the window, averaged over the devices traced."""
    lo, hi = trace.window()
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(busy_ns(trace, lo, hi, d) for d in devs) / len(devs) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, averaged over devices; None with no device op."""
    lo, hi = trace.window()
    if not trace.devices or hi <= lo:
        return None
    return 1.0 - busy_s_per_device(trace) * 1e9 / (hi - lo)


def kernel_seconds(trace: Trace, patterns: Sequence[str]) -> float:
    """Summed device time, inside the window, of the operations whose name
    matches any of ``patterns`` (regular expressions, ``re.search``)."""
    lo, hi = trace.window()
    rx = [re.compile(p) for p in patterns]
    total = 0
    for name, s, e, _ in trace.ops:
        if any(r.search(name) for r in rx):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                total += e - s
    return total / 1e9


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operation names with the most device time in the window,
    as ``[name, seconds]``."""
    lo, hi = trace.window()
    per: Dict[str, int] = {}
    for name, s, e, _ in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            per[name] = per.get(name, 0) + e - s
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def gaps(trace: Trace, device: int) -> List[Tuple[int, int]]:
    """The idle intervals of ``device`` inside the window."""
    lo, hi = trace.window()
    busy = union(clip([(s, e) for _, s, e, d in trace.ops if d == device],
                      lo, hi))
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def enclosing_span(spans: Sequence[Span], starts: Sequence[int],
                   t: int) -> str:
    """Name of the innermost (latest-starting) span that contains ``t``."""
    i = bisect.bisect_right(starts, t)
    best = None
    for name, s, e in reversed(spans[:i]):
        if s <= t < e and name != WINDOW_SPAN:
            best = name
            break
    return best or "(no span)"


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Idle time of the first device traced, summed by the host span that
    encloses each gap's midpoint, as ``[span name, seconds]``, most first."""
    if not trace.devices:
        return []
    spans = sorted(trace.spans, key=lambda sp: sp[1])
    starts = [s for _, s, _ in spans]
    per: Dict[str, int] = {}
    for s, e in gaps(trace, trace.devices[0]):
        name = enclosing_span(spans, starts, (s + e) // 2)
        per[name] = per.get(name, 0) + e - s
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]

