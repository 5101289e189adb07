#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One run is one process: set up (load or compile every program the cell
uses, warm every shape its traffic sends), measure for ``--seconds``, then
check what the timed path produced against a plain reference. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics of ``BENCHMARK.json``
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: every number
compared, beside its limit. The same comparisons close standard error.
A ``--trace 1`` run measures a window of its own, the cell's
``trace_seconds`` long at most, under the profiler.

The run exits non-zero with no result line when the default device is not
a TPU, when there are fewer devices than the cell asks for, or when the
device is missing from ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace_reduce  # noqa: E402

class NoAccelerator(RuntimeError):
    pass


# -- files found by name -----------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_entry(spec: dict, workload: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {workload!r}")


def cell_files(workload: str):
    """``(entry in BENCHMARK.json, workload file, config, traffic mix)``."""
    entry = cell_entry(benchmark_spec(), workload)
    cell_file = load_json(BENCH / "workloads" / f"{workload}.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "mixes" / f"{entry['traffic']}.json")
    return entry, cell_file, config, traffic


def load_driver(cell_file: dict) -> types.ModuleType:
    name = cell_file["driver"]
    return load_module(BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")


def cell_metrics(spec: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end ones, or with a trace
    its per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


# -- the device --------------------------------------------------------------


def device_guard(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"the default device is {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def device_peaks(kind: str, allow_cpu: bool) -> Optional[dict]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if allow_cpu:
        return None
    raise NoAccelerator(f"device kind {kind!r} is not in bench/peaks.json")


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def lower_precision() -> str:
    """The control: every matmul of the program one step below the
    configuration's float32 at HIGHEST. XLA's dots take HIGH (three bf16
    passes); the Pallas TPU kernels have no HIGH, so theirs take DEFAULT
    (one bf16 pass), the next step down. The kernel modules read the
    precision when they are imported, so this runs before they are."""
    import jax

    from repro.core import householder

    assert "repro.kernels" not in sys.modules, "kernels already imported"
    householder.MATMUL_PRECISION = jax.lax.Precision.DEFAULT
    import repro.kernels.panel_qr  # noqa: F401
    import repro.kernels.stacked_qr  # noqa: F401
    import repro.kernels.wy_apply  # noqa: F401
    import repro.kernels.fused_sweep  # noqa: F401
    householder.MATMUL_PRECISION = jax.lax.Precision.HIGH
    return "xla HIGH, pallas DEFAULT"


def trace_options():
    """Device operations and the benchmark's own spans; no Python function
    events (they would bury the spans and multiply the trace's size)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class CompileCounter:
    """Counts XLA executables built (compiled or loaded from the persistent
    cache) through JAX's monitoring events; nothing built inside the
    measured window is the steady state."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.event:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


# -- one run -----------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        allow_cpu: bool = False, overrides: Optional[dict] = None,
        trace_dir: Optional[str] = None) -> Tuple[dict, List[tuple]]:
    """Run one cell; return ``(result, checks)``.

    ``overrides`` (tests only) replaces keys of the cell's config and
    traffic, to run the same path at a size a CPU holds; ``allow_cpu``
    skips the look for a TPU."""
    spec = benchmark_spec()
    entry, cell_file, config, traffic = cell_files(workload)
    limits = dict(cell_file["limits"])
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
        limits = {**limits, **overrides.get("limits", {})}

    devices = device_guard(entry["chips"], allow_cpu)
    dev = devices[0]
    peaks = device_peaks(dev.device_kind, allow_cpu)

    import jax

    from repro.kernels import backend
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print("[device] " + json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()), "chips_used": len(devices),
        "engines": backend.engine_report(), "compile_cache": cache_dir}),
        flush=True)

    driver = load_driver(cell_file)
    # a traced run measures a window of its own, a few steps long: the
    # trace of a whole run would be hundreds of MB
    window_s = min(seconds, cell_file["trace_seconds"]) if trace else seconds
    cell = driver.Cell(config, traffic, limits, seed, tracing=trace)
    cell.setup(window_s)
    setup_s = time.perf_counter() - T_START
    compiled_in_setup = counter.count

    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="trace_")
        jax.profiler.start_trace(tdir, profiler_options=trace_options())
    before = counter.count
    cell.window(window_s)
    compiled_in_window = counter.count - before
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    window_names = counter.names[before:before + compiled_in_window]
    print("[window] " + json.dumps({
        "setup_s": setup_s, "programs_built_in_setup": compiled_in_setup,
        "programs_built_in_window": compiled_in_window,
        "built_in_window": window_names[:20],
        **cell.window_report()}), flush=True)

    metrics: Dict[str, dict] = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        t_read = time.perf_counter()
        tr = trace_reduce.load_xplane(trace_reduce.find_xplane(tdir))
        print(f"[trace] read in {time.perf_counter() - t_read:.1f} s: "
              f"{len(tr.ops)} device ops, {len(tr.spans)} spans", flush=True)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = tr.window()
        device["busy_s"] = trace_reduce.busy_s_per_device(tr)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_gaps(tr)}
        ctx = types.SimpleNamespace(
            trace=tr, telemetry=cell.telemetry(), config=config,
            traffic=traffic, peaks=peaks, workload=workload)
        for m in cell_metrics(spec, workload, trace=True):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = cell.end_to_end(peak)
        e2e["setup_s"] = setup_s
        for m in cell_metrics(spec, workload, trace=False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    cell.release()
    attempted, failed, checks = cell.check()
    checks = checks + [("programs_built_in_window", compiled_in_window, 0)]
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result, checks


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result, checks = run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoAccelerator as e:
        print(f"run_cell: {e}; no result", file=sys.stderr)
        return 2
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if value <= limit else 'FAIL'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
