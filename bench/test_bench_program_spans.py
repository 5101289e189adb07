"""The program's own spans (``ftqr.*``): the orchestrator records them on
the profiler's clock, and ``program_spans`` splits the device's idle time
among them, with known answers on a hand-made trace and nothing to read on
a trace without them."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from bench import program_spans, trace_reduce
from bench.trace_reduce import Trace

BENCH = pathlib.Path(__file__).resolve().parent
MS = 1_000_000


def hand_made():
    """``(trace, program spans)``. Window 0-100 ms; device 0 busy 10-30,
    50-60, 64-66, 70-72 and 90-100 (the last op clipped): idle 0-10, 30-50,
    60-64, 66-70 and 72-90, 56 ms."""
    ops = [("a", 10 * MS, 30 * MS, 0), ("b", 50 * MS, 60 * MS, 0),
           ("heal_op.1", 64 * MS, 66 * MS, 0),
           ("heal_op.2", 70 * MS, 72 * MS, 0),
           ("c", 90 * MS, 120 * MS, 0),
           ("before", -15 * MS, -14 * MS, 0)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.segment.leaf", 0, 45 * MS)]
    program = [
        # a heal before the window: neither its idle nor its ops count
        ("ftqr.heal", -20 * MS, -10 * MS, {"lanes": 1}),
        ("ftqr.sweep", 5 * MS, 95 * MS, {"sweep": 0}),
        ("ftqr.dispatch", 5 * MS, 12 * MS, {"panel": 0}),      # idle 5
        # the gap 30-50 straddles the poll and the next dispatch: 10 each
        ("ftqr.poll", 28 * MS, 40 * MS, {"boundary": 1}),
        ("ftqr.dispatch", 40 * MS, 55 * MS, {"panel": 0}),     # idle 10
        # nested in the sweep; idle 62-64, 66-70, 72-80 = 14; two ops
        ("ftqr.heal", 62 * MS, 80 * MS,
         {"lanes": 3, "panel": 0, "phase": "tsqr", "level": 1}),
    ]
    return Trace(ops=ops, spans=spans), program


def test_idle_overlap_splits_a_gap_by_intersection():
    tr, prog = hand_made()

    def ov(names):
        return program_spans.idle_overlap_s(tr, prog, names)

    assert ov(["ftqr.dispatch"]) == pytest.approx(0.015)
    assert ov(["ftqr.poll"]) == pytest.approx(0.010)
    assert ov(["ftqr.heal"]) == pytest.approx(0.014)
    assert ov(["ftqr.sweep"]) == pytest.approx(0.051)
    assert ov(["ftqr.poll", "ftqr.dispatch"]) == pytest.approx(0.025)
    # the midpoint rule of ``idle_gaps`` (mid 40 ms) gives the whole
    # straddling gap to the dispatch and none of it to the poll
    mid = Trace(ops=tr.ops, spans=[("bench.window", 0, 100 * MS)] + [
        (n, s, e) for n, s, e, _ in prog
        if n in ("ftqr.poll", "ftqr.dispatch")])
    assert "ftqr.poll" not in dict(trace_reduce.idle_gaps(mid))


def test_idle_overlap_inside_restricts_to_the_enclosing_spans():
    tr, prog = hand_made()
    ov = program_spans.idle_overlap_s
    assert ov(tr, prog, ["ftqr.heal"], inside=["ftqr.sweep"]) == \
        pytest.approx(0.014)
    assert ov(tr, prog, ["ftqr.dispatch"], inside=["ftqr.poll"]) == 0.0
    assert ov(tr, prog, ["ftqr.nothing"]) == 0.0
    assert ov(Trace(ops=[], spans=tr.spans), prog, ["ftqr.poll"]) is None


def test_idle_overlap_averages_over_devices():
    tr, prog = hand_made()
    busy = Trace(ops=tr.ops + [("all", 0, 100 * MS, 1)], spans=tr.spans)
    assert program_spans.idle_overlap_s(busy, prog, ["ftqr.poll"]) == \
        pytest.approx(0.005)


def test_ops_started_in_counts_per_span_in_the_window():
    tr, prog = hand_made()
    assert program_spans.ops_started_in(tr, prog, "ftqr.heal") == [2]
    assert program_spans.ops_started_in(tr, prog, "ftqr.dispatch") == [1, 1]
    assert program_spans.ops_started_in(
        Trace(ops=[], spans=tr.spans), prog, "ftqr.heal") is None


@pytest.mark.parametrize("name,value", [
    ("idle_share.dispatch", 15.0), ("idle_share.poll", 10.0),
    ("idle_share.loop", 12.0), ("idle_share.heal", 14.0),
    ("idle_share.outside", 5.0), ("heal_ops", 2)])
def test_partition_known_answers(name, value):
    assert program_spans.partition(*hand_made())[name] == \
        pytest.approx(value)


def test_partition_adds_up_to_the_idle_share():
    """Dispatch, poll, loop, heal and the idle outside ``ftqr.sweep`` add up
    to ``trace_reduce.idle_share``, which the spans leave as it was."""
    tr, prog = hand_made()
    split = program_spans.partition(tr, prog)
    assert split["idle_share.factor"] == pytest.approx(56.0)
    assert 100.0 * trace_reduce.idle_share(tr) == pytest.approx(56.0)
    assert sum(split[f"idle_share.{k}"] for k in (
        "dispatch", "poll", "loop", "heal", "outside")) == \
        pytest.approx(56.0)
    assert split["spans"] == {"ftqr.sweep": 1, "ftqr.dispatch": 2,
                              "ftqr.poll": 1, "ftqr.heal": 1}


def test_partition_reads_nothing_without_program_spans():
    """A trace of a program that records no ``ftqr.*`` span (the recorded
    one, as every trace from before the spans) gives no partition."""
    tr = trace_reduce.load(str(BENCH / "testdata" / "factor_trace.json.gz"))
    assert program_spans.partition(tr, []) is None
    _, prog = hand_made()
    assert program_spans.partition(Trace(ops=[], spans=tr.spans),
                                   prog) is None


# -- the orchestrator records the spans --------------------------------------


def _factorize_traced(tmp_path, async_segments):
    import jax

    from repro.core.comm import SimComm
    from repro.ft.failures import sweep_point
    from repro.ft.online.detect import ScriptedKiller
    from repro.ft.online.orchestrator import SweepOrchestrator

    kills = {sweep_point(0, "tsqr", 1): [1],
             sweep_point(2, "trailing", 0): [2]}
    A = np.random.default_rng(3).standard_normal((4, 6, 10)).astype(
        np.float32)
    orchs = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for _ in range(2):
            orch = SweepOrchestrator(
                A, SimComm(4), 4, fault_hooks=[ScriptedKiller(kills)],
                async_segments=async_segments)
            orch.run()
            orchs.append(orch)
    prog = program_spans.load_xplane(
        trace_reduce.find_xplane(str(tmp_path)))
    return orchs, prog, kills


@pytest.mark.parametrize("async_segments", [False, True],
                         ids=["sync", "async"])
def test_orchestrator_records_one_span_per_step(tmp_path, async_segments):
    orchs, prog, kills = _factorize_traced(tmp_path, async_segments)
    sweeps = [p for p in prog if p[0] == "ftqr.sweep"]
    assert len(sweeps) == len(orchs)
    assert len({p[3]["sweep"] for p in sweeps}) == len(orchs)
    inner = [p for p in prog if p[0] != "ftqr.sweep"]
    for orch, (_, s0, e0, args) in zip(orchs, sweeps):
        assert (args["lanes"], args["m_loc"], args["n"]) == (4, 6, 10)
        mine = [p for p in inner if s0 <= p[1] and p[2] <= e0]
        names = [p[0] for p in mine]
        assert names.count("ftqr.poll") == orch.boundaries
        assert names.count("ftqr.dispatch") == orch.segments_run
        heals = sorted(((a["panel"], a["phase"], a["level"]),
                        [int(x) for x in str(a["lanes"]).split()])
                       for n, _, _, a in mine if n == "ftqr.heal")
        assert heals == sorted((p, lanes) for p, lanes in kills.items())
        assert sorted(tuple(e.point) for e in orch.events) == \
            sorted(kills)
    # every program span nests in one sweep
    assert all(any(s0 <= p[1] and p[2] <= e0 for _, s0, e0, _ in sweeps)
               for p in inner)
