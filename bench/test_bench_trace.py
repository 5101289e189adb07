"""Trace reduction: idle share, kernel time by name pattern and idle gaps by
host span, on a hand-made trace with known answers and on a small trace
recorded on a TPU v5e: 0.6 s of the traced window of back-to-back
32768 x 4096 factorizations on 8 lanes (the same sweep and kernels as the
benchmark's cells, at another shape), cut from its ``.xplane.pb`` by
``load_xplane`` (``testdata/factor_trace.json.gz``).
"""
from __future__ import annotations

import importlib.util
import pathlib
import re

import pytest

from bench import trace_reduce
from bench.trace_reduce import Trace

TESTDATA = pathlib.Path(__file__).resolve().parent / "testdata"
MS = 1_000_000


def hand_made() -> Trace:
    # window 0-100 ms; device busy 10-30 (two overlapping ops), 50-60,
    # and 90-120 (clipped to 100): 20 + 10 + 10 = 40 ms busy
    ops = [("wy_apply.1", 10 * MS, 25 * MS, 0),
           ("fusion.2", 20 * MS, 30 * MS, 0),
           ("panel_qr.3", 50 * MS, 60 * MS, 0),
           ("wy_apply.1", 90 * MS, 120 * MS, 0)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.tick", 0, 40 * MS),
             ("bench.submit", 5 * MS, 8 * MS),
             ("bench.tick", 40 * MS, 100 * MS)]
    return Trace(ops=ops, spans=spans)


def test_idle_share_is_one_minus_union_over_window():
    tr = hand_made()
    assert trace_reduce.busy_s_per_device(tr) == pytest.approx(0.040)
    assert trace_reduce.idle_share(tr) == pytest.approx(0.60)


def test_kernel_time_by_pattern_is_clipped_to_the_window():
    tr = hand_made()
    assert trace_reduce.kernel_seconds(tr, [r"wy_apply"]) == \
        pytest.approx(0.015 + 0.010)
    assert trace_reduce.kernel_seconds(tr, [r"panel_qr", r"fusion"]) == \
        pytest.approx(0.020)
    assert trace_reduce.kernel_seconds(tr, [r"nothing"]) == 0.0


def test_idle_gaps_go_to_the_innermost_enclosing_span():
    tr = hand_made()
    # gaps: 0-10 (mid 5 -> bench.submit), 30-50 (mid 40 -> second tick),
    # 60-90 (mid 75 -> second tick)
    gaps = dict(trace_reduce.idle_gaps(tr))
    assert gaps == pytest.approx({"bench.submit": 0.010,
                                  "bench.tick": 0.050})
    top = trace_reduce.top_ops(tr)
    assert top[0] == ["wy_apply.1", pytest.approx(0.025)]


def test_union_and_json_round_trip():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    tr = hand_made()
    assert Trace.from_json(tr.to_json()) == tr


def test_no_device_ops_reads_nothing():
    tr = Trace(ops=[], spans=[("bench.window", 0, 10)])
    assert trace_reduce.idle_share(tr) is None
    assert trace_reduce.idle_gaps(tr) == []


def recorded() -> Trace:
    return trace_reduce.load(str(TESTDATA / "factor_trace.json.gz"))


def brute_busy_ns(tr: Trace, step: int = 1000) -> int:
    """Busy time by sampling the window every microsecond."""
    lo, hi = tr.window()
    starts = sorted((s, e) for _, s, e, _ in tr.ops)
    busy, i, live = 0, 0, []
    for t in range(lo, hi, step):
        while i < len(starts) and starts[i][0] <= t:
            live.append(starts[i][1])
            i += 1
        live = [e for e in live if e > t]
        busy += step if live else 0
    return busy


def test_recorded_idle_share_matches_a_sampled_count():
    tr = recorded()
    lo, hi = tr.window()
    busy = trace_reduce.busy_ns(tr, lo, hi)
    assert busy == pytest.approx(brute_busy_ns(tr), rel=1e-2)
    assert trace_reduce.idle_share(tr) == pytest.approx(
        1 - busy / (hi - lo))
    assert 0.5 < trace_reduce.idle_share(tr) < 0.95


@pytest.mark.parametrize("metric", ["trailing_roofline", "panel_roofline"])
def test_roofline_patterns_match_the_recorded_kernels(metric):
    """Each pattern of a roofline metric finds its Pallas kernel in a real
    TPU trace, and no pattern finds anything else."""
    spec = importlib.util.spec_from_file_location(
        metric, TESTDATA.parent / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    names = {n for n, *_ in recorded().ops}
    for pattern in mod.PATTERNS:
        hits = {n for n in names if re.search(pattern, n)}
        assert hits, pattern
        assert all(n.startswith("vmap_jit_") for n in hits), hits


def test_recorded_gaps_fall_under_the_segment_spans():
    gaps = dict(trace_reduce.idle_gaps(recorded()))
    assert set(gaps) <= {"bench.segment.leaf", "bench.segment.tsqr",
                         "bench.segment.trailing", "bench.finalize",
                         "bench.factorize", "(no span)"}
    assert max(gaps, key=gaps.get) == "bench.segment.trailing"
