"""Benchmark harness: one function per paper claim. Prints
``name,us_per_call,derived`` CSV plus the sweep-cost table, writes the
machine-readable ``BENCH_core.json`` at the repo root (the perf trajectory
artifact — one snapshot per PR), then the roofline table if dry-run
artifacts exist.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--out PATH]
  --quick: kernel smoke + reduced sweep-cost only (CI smoke; still writes
           BENCH_core.json, flagged quick=true).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_core.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(_DEFAULT_OUT))
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    # previous record = the regression baseline for the online-path gate
    baseline = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                baseline = json.load(f)
        except (ValueError, OSError):
            baseline = {}

    from benchmarks import bench_core, roofline

    rows = []
    print("name,us_per_call,derived")
    for bench in (bench_core.QUICK if args.quick else bench_core.ALL):
        for row in bench():
            rows.append(row)
            print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")

    print()
    print("# kernel roofline (analytic arithmetic intensity, v5e projection)")
    roofline.print_kernel_rows(rows)

    sweep = bench_core.bench_sweep_cost(quick=args.quick)
    print()
    print("# sweep cost per panel (windowed vs full-width trailing update)")
    print("k,us_windowed,us_full,flops_windowed,flops_full")
    for p in sweep["per_panel"]:
        print(f"{p['k']},{p['us_windowed']:.1f},{p['us_full']:.1f},"
              f"{p['flops_windowed']:.3e},{p['flops_full']:.3e}")
    t = sweep["totals"]
    print(f"# sweep totals: windowed {t['us_windowed_sweep']:.0f}us, "
          f"full {t['us_full_sweep']:.0f}us, scan {t['us_scan_sweep']:.0f}us, "
          f"trailing-flop ratio {t['trailing_flop_ratio']:.2f}x")

    from benchmarks import bench_recovery

    recovery = bench_recovery.suite(quick=args.quick)
    ff = recovery["failure_free"]
    print()
    print("# recovery: failure-free overhead + REBUILD latency")
    print(f"# bundle maintenance: {ff['bundle_overhead']:.2f}x "
          f"({ff['us_sweep_no_bundles']:.0f}us -> "
          f"{ff['us_sweep_with_bundles']:.0f}us); "
          f"driver harness: {ff['driver_overhead']:.2f}x")
    print("point,us_rebuild,fetches,sources")
    for row in recovery["latency"]["by_level"] + recovery["latency"]["by_panel"]:
        pt = "-".join(str(x) for x in row["point"])
        print(f"{pt},{row['us_rebuild']:.0f},{row['fetches']},{row['sources']}")

    general = bench_core.bench_general_shapes(quick=args.quick)
    print()
    print("# general shapes: ragged (zero-padded) vs aligned sweep, same padded compute")
    print(f"# aligned {tuple(general['aligned']['shape'])}: "
          f"{general['aligned']['us']:.0f}us; "
          f"ragged {tuple(general['ragged']['shape'])} -> padded "
          f"{tuple(general['ragged']['padded_shape'])}: "
          f"{general['ragged']['us']:.0f}us; "
          f"overhead {general['overhead']:.2f}x")

    from benchmarks import bench_spmd

    print()
    print("# SPMD path (shard_map over a forced host-device mesh) vs SimComm")
    if jax.default_backend() != "cpu":
        # the section measures in a child process with forced host
        # devices; this process already holds the accelerator, and a chip
        # belongs to one process at a time
        spmd = {"skipped": f"needs a forced-host-device child process; "
                           f"this process holds the {jax.default_backend()}"}
        print(f"# skipped: {spmd['skipped']}")
    else:
        spmd = bench_spmd.suite(quick=args.quick)
        print(f"# P={spmd['P']} m_loc={spmd['m_loc']} n={spmd['n']} "
              f"b={spmd['b']}: "
              f"SimComm {spmd['us_simcomm_sweep']:.0f}us/sweep (eager), "
              f"shard_map {spmd['us_spmd_sweep']:.0f}us/sweep "
              f"(+{spmd['s_spmd_compile']:.1f}s compile); "
              f"1-kill REBUILD adds {spmd['us_spmd_rebuild_delta']:.0f}us/sweep")

    from benchmarks import bench_online

    online = bench_online.suite(quick=args.quick)
    st = online["stepped"]
    print()
    print("# online path: host-orchestrated stepped sweep vs monolithic")
    print(f"# P={st['config']['P']} m_loc={st['config']['m_loc']} "
          f"n={st['config']['n']} b={st['config']['b']}: "
          f"monolithic jit {st['us_monolithic_jit']:.0f}us, "
          f"eager driver {st['us_driver_eager']:.0f}us")
    print("segment,points,us_sweep")
    for name, row in st["by_segment"].items():
        print(f"{name},{row['segment_points']},{row['us']:.0f}")
    det = online["detection"]
    print(f"# stepped(1) overhead {st['overhead_vs_driver']:.2f}x vs driver, "
          f"{st['overhead_vs_jit']:.2f}x vs jit; detect-to-recovered "
          f"{det['us_detect_to_recovered']:.0f}us "
          f"(poll {det['us_poll_avg']:.0f}us/boundary, "
          f"{det['fetches']} fetches)")

    from benchmarks import bench_elastic

    elastic = bench_elastic.suite(quick=args.quick)
    sh, sp = elastic["shrink"], elastic["speculation"]
    print()
    print("# elastic path: SHRINK continuation vs REBUILD, straggler race")
    print(f"# P={sh['config']['P']} m_loc={sh['config']['m_loc']} "
          f"n={sh['config']['n']} b={sh['config']['b']}: "
          f"REBUILD {sh['us_rebuild_mid_kill']:.0f}us, "
          f"SHRINK {sh['us_shrink_mid_kill']:.0f}us "
          f"({sh['shrink_vs_rebuild']:.2f}x); "
          f"P-1 world {sh['p_minus_1_vs_free']:.2f}x vs failure-free")
    print(f"# speculation: {sp['speculations']} races, "
          f"{sp['us_per_speculation']:.0f}us each, "
          f"{sp['speculative_vs_blocking']:.2f}x vs blocking "
          f"(straggler excess {sp['config']['excess_us_per_boundary']:.0f}"
          f"us/boundary)")

    from benchmarks import bench_serve

    serve = bench_serve.suite(quick=args.quick)
    tr, kl = serve["traffic"], serve["kill"]
    print()
    print("# serve path: continuous sweep batching (QR-as-a-service)")
    print(f"# {serve['config']['requests']} ragged requests, "
          f"{tr['resident_peak']} resident, "
          f"{tr['compiled_programs']} compiled segments: "
          f"{tr['req_per_s']:.1f} req/s "
          f"(p50 {tr['p50_ms']:.0f}ms p99 {tr['p99_ms']:.0f}ms); "
          f"mid-batch kill: {kl['req_per_s']:.1f} req/s, "
          f"{kl['tenant_rebuilds']} tenant REBUILDs, "
          f"{kl['kill_vs_free']:.2f}x; "
          f"continuous vs batched {serve['continuous_vs_batched']:.2f}x")

    from benchmarks import bench_coding

    coding = bench_coding.suite(quick=args.quick)
    ov = coding["overhead"]
    print()
    print("# coded checksum lanes: overhead-vs-f + joint-decode latency")
    print("P,f,us_sweep,overhead_vs_xor")
    for P_, world in ov["by_world"].items():
        for f_, row in world["by_f"].items():
            print(f"{P_},{f_},{row['us']:.0f},{row['overhead_vs_xor']:.2f}")
    dec = coding["decode"]
    print(f"# f=2 overhead {ov['overhead_f2_vs_xor']:.2f}x vs XOR floor; "
          f"buddy-pair joint decode {dec['us_detect_to_recovered']:.0f}us "
          f"({dec['reads']} reads)")

    from benchmarks import bench_train

    train = bench_train.suite(quick=args.quick)
    bd, stp = train["boundary"], train["step"]
    print()
    print("# train path: optimizer-internal FT-QR inside the training step")
    print(f"# boundary ({bd['config']['boundaries']} per sweep): "
          f"sync {bd['us_sync_per_boundary']:.0f}us, "
          f"async {bd['us_async_per_boundary']:.0f}us "
          f"({bd['async_vs_sync']:.2f}x)")
    print(f"# step: free {stp['us_step_free']/1e3:.0f}ms, killed "
          f"{stp['us_step_killed']/1e3:.0f}ms "
          f"(REBUILD adds {stp['us_rebuild_delta']/1e3:.0f}ms, "
          f"{stp['kill_vs_free']:.2f}x), bitwise-identical losses")

    # gate BEFORE recording: a regressed measurement must not become the
    # next run's baseline (the gate would otherwise fail exactly once),
    # and a passing one is recorded with the damped-baseline floor so a
    # lucky-fast outlier cannot set a bar ordinary runs miss by noise
    ok, msg = bench_online.check_regression(online, baseline.get("online"))
    elastic_ok, elastic_msg = bench_elastic.check_regression(
        elastic, baseline.get("elastic"))
    serve_ok, serve_msg = bench_serve.check_regression(
        serve, baseline.get("serve"))
    coding_ok, coding_msg = bench_coding.check_regression(
        coding, baseline.get("coding"))
    train_ok, train_msg = bench_train.check_regression(
        train, baseline.get("train"))
    # kernels-beat-oracle gate: intra-run (compiled rows vs their oracles),
    # no baseline needed — but the verdict is recorded alongside the rows
    kernel_ok, kernel_msg = bench_core.check_kernel_regression(rows)
    record = {"schema": 1, "quick": args.quick, "rows": rows,
              "kernel_gate": {"ok": kernel_ok, "msg": kernel_msg},
              "sweep_cost": sweep, "recovery": recovery,
              "general_shapes": general, "spmd": spmd,
              "online": bench_online.baseline_to_record(
                  online, baseline.get("online")),
              "elastic": bench_elastic.baseline_to_record(
                  elastic, baseline.get("elastic")),
              "serve": bench_serve.baseline_to_record(
                  serve, baseline.get("serve")),
              "coding": bench_coding.baseline_to_record(
                  coding, baseline.get("coding")),
              "train": bench_train.baseline_to_record(
                  train, baseline.get("train"))}
    if not ok:
        record["online"] = baseline.get("online")   # keep the old baseline
        record["online_rejected"] = online          # the failing numbers
    if not elastic_ok:
        record["elastic"] = baseline.get("elastic")
        record["elastic_rejected"] = elastic
    if not serve_ok:
        record["serve"] = baseline.get("serve")
        record["serve_rejected"] = serve
    if not coding_ok:
        record["coding"] = baseline.get("coding")
        record["coding_rejected"] = coding
    if not train_ok:
        record["train"] = baseline.get("train")
        record["train_rejected"] = train
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"# wrote {args.out}")
    print(f"# online regression gate: {msg}")
    print(f"# elastic regression gate: {elastic_msg}")
    print(f"# serve regression gate: {serve_msg}")
    print(f"# coding regression gate: {coding_msg}")
    print(f"# train regression gate: {train_msg}")
    print(f"# kernel gate: {kernel_msg}")
    if not ok or not kernel_ok or not elastic_ok or not serve_ok \
            or not coding_ok or not train_ok:
        raise SystemExit(2)

    if not args.quick:
        rl = roofline.load_all()
        if rl:
            print()
            print("# roofline (from dry-run artifacts; see EXPERIMENTS.md)")
            roofline.main()


if __name__ == "__main__":
    main()
