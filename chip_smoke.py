#!/usr/bin/env python
"""Bring-up smoke: the FT-CAQR main paths on a TPU, at production width.

Run from the repository root of a machine with a TPU:

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # the shard_map path on four chips (f)

a. device and kernel report: platform, device kind and count, the engine
   of each kernel op (``repro.kernels.backend``), the matmul precision and
   the compile-cache directory.
b. ``ft_caqr_sweep_online`` — the orchestrator path the QR service and the
   training runtime use — on ``SimComm(8)`` lanes of one chip at
   ``configs.paper_qr.PRODUCTION`` widths (n = 4096, b = 128, f32), with m
   cut to 32768 rows (``SWEEP_ROWS``: at 65536 rows the state plus the
   stacked outputs of the final assembly hold 14.9 GiB of the chip's 16).
   R is checked against the host's float64 Gram matrix:
   ``||R^T R - A^T A||_F / ||A||_F^2 <= 1e-4``. ``jnp.linalg.qr`` of the
   same matrix is timed for orientation.
c. the same sweep with lane 3 killed at ``sweep_point(16, "trailing", 0)``:
   the healed R must equal phase b's bit for bit, and the REBUILD must read
   each artifact from one survivor.
d. ``QRService`` through ``repro.launch.serve_qr.run``: 8 requests up to
   8192 x 1024 at b = 128, 30% least squares, a lane killed mid-batch;
   every result is checked with ``serve_qr.verify``.
e. the last line: ``{"ok": true, "device": {...}}``.
f. (``--chips 4`` only, nothing else runs) ``ft_caqr_sweep_online_spmd`` on
   ``make_lane_mesh(4)`` at 65536 x 4096, b = 128, failure-free and with a
   lane killed whose XOR buddies sit on other chips: the Gram bound, the
   healed R bitwise against the failure-free one, and the compiled program
   of a butterfly point spread over four devices with a
   ``collective-permute``.

Compile seconds are the cold run's wall time less a warm rerun's. The
matrix is Gaussian, drawn on the device from ``--seed``. The script exits
non-zero, with no result line, when the default device is not a TPU or
any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GRAM_BOUND = 1e-4
LANES = 8          # SimComm lanes of the one-chip sweep (m_loc = m / 8)
SWEEP_ROWS = 32768  # m of phases b-c (PRODUCTION.m_rows = 65536, cut for HBM)
KILL_LANE = 3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    """One phase's line: its fields plus the device's peak memory so far."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        fields["peak_hbm_gib"] = stats["peak_bytes_in_use"] / 2**30
    print(f"[{phase}] " + json.dumps(fields), flush=True)


def gaussian(seed: int, m: int, n: int):
    import jax
    import jax.numpy as jnp

    A = jax.random.normal(jax.random.key(seed), (m, n), jnp.float32)
    return jax.block_until_ready(A)


def host_gram(A):
    """(A^T A, ||A||_F^2) in float64 on the host — numpy, independent of
    the code under test."""
    import numpy as np

    A64 = np.asarray(A, np.float64)
    G = A64.T @ A64
    return G, float(np.trace(G))


def gram_residual(R, gram) -> float:
    import numpy as np

    G, norm2 = gram
    R64 = np.asarray(R, np.float64)
    return float(np.linalg.norm(R64.T @ R64 - G) / norm2)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def kernel_report() -> dict:
    """Phase a: the device and the route of every kernel op."""
    import jax

    from repro.core.householder import MATMUL_PRECISION
    from repro.kernels import backend

    dev = jax.devices()[0]
    engines = backend.engine_report()
    check(backend.dispatch_enabled(),
          "the core -> kernel dispatch is off on this device")
    for op, engine in engines.items():
        want = (backend.ENGINE_PALLAS if op in backend.PALLAS_ON_TPU
                else backend.ENGINE_XLA)
        check(engine == want, f"{op} runs {engine}, the policy says {want}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "engines": engines,
            "matmul_precision": str(MATMUL_PRECISION)}


def sweep_phases(seed: int, m: int, n: int, b: int, lanes: int,
                 kill_lane: int) -> None:
    """Phases b and c: the failure-free and the one-kill online sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.comm import SimComm
    from repro.ft.failures import sweep_point
    from repro.ft.online.detect import ScriptedKiller
    from repro.ft.online.orchestrator import ft_caqr_sweep_online

    A = gaussian(seed, m, n)
    gram = host_gram(A)
    comm = SimComm(lanes)
    A_lanes = A.reshape(lanes, m // lanes, n)

    def sweep(hooks=()):
        res = ft_caqr_sweep_online(A_lanes, comm, b, fault_hooks=list(hooks))
        R = np.asarray(res.R[0])   # lanes hold replicated copies of R
        return R, res.events

    (R, events), cold = timed(sweep)
    check(not events, f"failure-free sweep logged REBUILDs: {events}")
    (R_warm, _), warm = timed(sweep)
    check(np.array_equal(R, R_warm), "two failure-free sweeps differ")
    res_b = gram_residual(R, gram)
    check(res_b <= GRAM_BOUND,
          f"Gram residual {res_b:.3e} exceeds {GRAM_BOUND:.0e}")

    qr = jax.jit(lambda a: jnp.linalg.qr(a, mode="r"))
    compiled, qr_compile = timed(lambda: qr.lower(A).compile())
    R_ref, qr_run = timed(lambda: jax.block_until_ready(compiled(A)))
    report("b", path="ft_caqr_sweep_online", comm=f"SimComm({lanes})",
           shape=[m, n], b=b, compile_s=cold - warm, run_s=warm,
           gram_residual=res_b, bound=GRAM_BOUND,
           jnp_linalg_qr={"compile_s": qr_compile, "run_s": qr_run,
                          "gram_residual": gram_residual(R_ref, gram)})
    del R_ref, compiled

    n_panels = -(-min(m, n) // b)
    point = sweep_point(n_panels // 2, "trailing", 0)

    buddies = {kill_lane ^ (1 << s) for s in range(lanes.bit_length() - 1)}

    def killed():
        (R_c, events), wall = timed(
            lambda: sweep([ScriptedKiller({point: [kill_lane]})]))
        check(len(events) == 1, f"expected one REBUILD, got {events}")
        ev = events[0]
        check(ev.lane == kill_lane and tuple(ev.point) == point,
              f"REBUILD of lane {ev.lane} at {ev.point}, expected "
              f"{kill_lane} at {point}")
        check(set(ev.reads.values()) <= buddies,
              f"REBUILD read from {ev.sources}, not only the XOR buddies "
              f"{sorted(buddies)}")
        check(np.array_equal(R_c, R),
              "healed R differs from the failure-free R")
        return ev, wall

    _, cold = killed()
    ev, warm = killed()
    report("c", kill_lane=kill_lane, point=list(point),
           compile_s=cold - warm, run_s=warm, rebuild_s=ev.elapsed_s,
           artifacts_read=len(ev.reads), sources=ev.sources,
           bitwise_equal=True)


def serve_phase(seed: int, lanes: int, b: int, max_m: int, max_n: int,
                requests: int) -> None:
    """Phase d: the QR service through the ``serve_qr`` entry point's code
    (``run`` verifies every result against numpy and raises on a
    mismatch)."""
    from repro.launch import serve_qr

    args = serve_qr.parse_args([
        "--lanes", str(lanes), "--panel-width", str(b),
        "--requests", str(requests), "--max-m", str(max_m),
        "--max-n", str(max_n), "--lstsq-frac", "0.3",
        "--kill-lane", "2", "--kill-tick", "2", "--seed", str(seed)])
    cold_summary, cold = timed(lambda: serve_qr.run(args))
    summary, warm = timed(lambda: serve_qr.run(args))
    for s in (cold_summary, summary):
        check(s["requests"] == requests,
              f"{s['requests']} of {requests} requests retired")
        check(s["rebuilds"] >= 1, "the lane kill healed no tenant")
    report("d", path="repro.launch.serve_qr.run", lanes=lanes, b=b,
           max_shape=[max_m, max_n], compile_s=cold - warm, run_s=warm,
           verified=requests, **{k: summary[k] for k in (
               "lstsq", "rebuilds", "ticks", "p50_ms", "p99_ms",
               "compiled_segments")})


def mesh_phase(seed: int, m: int, n: int, b: int, chips: int,
               kill_lane: int) -> None:
    """Phase f: the production shard_map path over ``chips`` devices."""
    import jax
    import numpy as np

    from repro.core.comm import SimComm
    from repro.dist import compat
    from repro.ft.failures import sweep_point
    from repro.ft.online.detect import ScriptedKiller
    from repro.ft.online.state import initial_sweep_state
    from repro.launch.spmd_qr import (
        ft_caqr_sweep_online_spmd,
        make_lane_mesh,
        make_spmd_sweep_step,
    )

    mesh = make_lane_mesh(chips)
    A = gaussian(seed, m, n)
    gram = host_gram(A)
    n_panels = -(-min(m, n) // b)
    point = sweep_point(n_panels // 2, "trailing", 0)

    def sweep(hooks=()):
        res = ft_caqr_sweep_online_spmd(A, b, mesh=mesh,
                                        fault_hooks=list(hooks))
        return np.asarray(res.R[0]), res.events

    (R, events), cold = timed(sweep)
    check(not events, f"failure-free sweep logged REBUILDs: {events}")
    (R_warm, _), warm = timed(sweep)
    check(np.array_equal(R, R_warm), "two failure-free sweeps differ")
    res_f = gram_residual(R, gram)
    check(res_f <= GRAM_BOUND,
          f"Gram residual {res_f:.3e} exceeds {GRAM_BOUND:.0e}")
    (R_k, events), killed_s = timed(
        lambda: sweep([ScriptedKiller({point: [kill_lane]})]))
    check(len(events) == 1 and events[0].lane == kill_lane,
          f"expected one REBUILD of lane {kill_lane}, got {events}")
    check(np.array_equal(R_k, R), "healed R differs from the failure-free R")

    # the butterfly point's compiled program: four partitions, and the
    # pair exchange as a collective-permute
    step = make_spmd_sweep_step(mesh)
    state = initial_sweep_state(SimComm(chips), A.reshape(chips, -1, n), b)
    state = step(state)   # now at (0, tsqr, 0)
    with compat.set_mesh(mesh):
        text = step.program(state).lower(state).compile().as_text()
    devices = {d.id for d in state.A.sharding.device_set}
    check(len(devices) == chips, f"state sits on devices {devices}")
    check(f"num_partitions={chips}" in text,
          f"butterfly program is not partitioned {chips} ways")
    check("collective-permute" in text,
          "butterfly program has no collective-permute")
    report("f", path="ft_caqr_sweep_online_spmd", mesh=chips,
           shape=[m, n], b=b, compile_s=cold - warm, run_s=warm,
           gram_residual=res_f, bound=GRAM_BOUND, kill_lane=kill_lane,
           point=list(point), killed_run_s=killed_s,
           rebuild_sources=events[0].sources, bitwise_equal=True,
           devices=sorted(devices), collective_permute=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    os.environ.pop("REPRO_AUTOTUNE_CACHE", None)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default device is {dev.platform}, not a "
              f"TPU", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.configs.paper_qr import PRODUCTION
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    n, b = PRODUCTION.n_cols, PRODUCTION.panel
    try:
        if args.chips == 4:
            mesh_phase(args.seed, PRODUCTION.m_rows, n, b, 4, kill_lane=1)
        else:
            report("a", compile_cache=cache, **kernel_report())
            print(f"[b] m cut: {PRODUCTION.m_rows} -> {SWEEP_ROWS} rows "
                  f"(n={n}, b={b} kept); at {PRODUCTION.m_rows} rows the "
                  f"sweep state and the stacked outputs of its final "
                  f"assembly hold 14.9 GiB of the 16 GiB HBM", flush=True)
            sweep_phases(args.seed, SWEEP_ROWS, n, b, LANES, KILL_LANE)
            serve_phase(args.seed, LANES, b, 8192, 1024, requests=8)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
