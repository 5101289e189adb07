"""QR-service driver: ``python -m repro.launch.serve_qr``.

Generates a synthetic burst of ragged factorization / least-squares
requests, streams them through the continuous-batching ``QRService``
(``repro.serve.qr_service``), optionally kills a lane mid-batch, and
reports sustained throughput + latency percentiles. Every retired R is
checked against ``numpy.linalg.qr`` of the tenant's own matrix (sign-fixed
columns), and lstsq solutions against ``numpy.linalg.lstsq`` — so the run
is a correctness smoke as well as a traffic demo (``tools/ci.sh`` runs it
with ``--kill-lane`` as the serve smoke tier).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict

import numpy as np

from repro.core import SimComm
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.qr_service import QRService


def make_requests(rng: np.random.Generator, count: int, b: int,
                  max_m: int, max_n: int, lstsq_frac: float):
    """Ragged synthetic traffic: shapes uniform in [b, max];
    ``round(lstsq_frac * count)`` of the requests, at random positions,
    carry a right-hand side (the lstsq tenants). An lstsq tenant is drawn
    overdetermined by 2x where the bounds allow (``m >= min(2n, max_m)``),
    which keeps a Gaussian problem well-conditioned: its solution is then
    checked to a fixed absolute tolerance."""
    lstsq = set(rng.choice(count, round(lstsq_frac * count), replace=False)
                .tolist())
    reqs = []
    for i in range(count):
        n = int(rng.integers(b, max_n + 1))
        lo = b
        if i in lstsq:
            n = min(n, max_m)
            lo = max(b, min(2 * n, max_m))
        m = int(rng.integers(lo, max_m + 1))
        A = rng.standard_normal((m, n)).astype(np.float32)
        rhs = None
        if i in lstsq:
            rhs = rng.standard_normal((m, 2)).astype(np.float32)
        reqs.append((A, rhs))
    return reqs


def verify(res, A, rhs) -> None:
    k, n = min(A.shape), A.shape[1]
    Q_ref, R_ref = np.linalg.qr(A.astype(np.float64), mode="reduced")
    # QR is unique up to column signs of Q / row signs of R
    s = np.sign(np.diag(R_ref[:k, :k]))
    s[s == 0] = 1.0
    R_ref = s[:, None] * R_ref[:k, :n]
    s_got = np.sign(np.diag(res.R[:k, :k]))
    s_got[s_got == 0] = 1.0
    R_got = s_got[:, None] * res.R
    assert np.allclose(R_got, R_ref, atol=1e-3), (
        f"{res.rid}: R mismatch, max err "
        f"{np.abs(R_got - R_ref).max():.2e}")
    if rhs is not None:
        x_ref, *_ = np.linalg.lstsq(
            A.astype(np.float64), rhs.astype(np.float64), rcond=None)
        assert np.allclose(res.x, x_ref, atol=1e-2), (
            f"{res.rid}: lstsq mismatch, max err "
            f"{np.abs(res.x - x_ref).max():.2e}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--panel-width", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-m", type=int, default=24)
    ap.add_argument("--max-n", type=int, default=12)
    ap.add_argument("--lstsq-frac", type=float, default=0.3)
    ap.add_argument("--arrive-every", type=int, default=1,
                    help="submit one request per this many ticks (0 = all "
                         "up front)")
    ap.add_argument("--kill-lane", type=int, default=-1,
                    help="kill this lane mid-batch (-1 = failure-free)")
    ap.add_argument("--kill-tick", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> Dict[str, float]:
    """Serve the synthetic burst ``args`` describes, verify every retired
    result against numpy (raises on a mismatch), and return the summary
    ``main`` prints."""
    rng = np.random.default_rng(args.seed)
    comm = SimComm(args.lanes)
    b = args.panel_width
    m_loc = -(-args.max_m // args.lanes)
    m_loc += (-m_loc) % b
    bucket = (m_loc, args.max_n + 2)   # +2: room for the lstsq rhs columns
    svc = QRService(comm, panel_width=b, buckets=[bucket],
                    max_slots=args.slots)
    reqs = make_requests(rng, args.requests, b, args.max_m, args.max_n,
                         args.lstsq_frac)

    pending = list(reqs)
    by_rid = {}
    t0 = time.perf_counter()
    killed = False
    while pending or svc.queue or svc.resident:
        if args.arrive_every == 0:
            while pending:
                A, rhs = pending.pop(0)
                by_rid[svc.submit(A, rhs)] = (A, rhs)
        elif pending and svc.tick_count % args.arrive_every == 0:
            A, rhs = pending.pop(0)
            by_rid[svc.submit(A, rhs)] = (A, rhs)
        if (args.kill_lane >= 0 and not killed
                and svc.tick_count == args.kill_tick):
            svc.kill_lane(args.kill_lane)
            killed = True
        svc.tick()
    wall = time.perf_counter() - t0

    lat = np.array(sorted(r.latency_s for r in svc.results.values()))
    for rid, (A, rhs) in by_rid.items():
        verify(svc.results[rid], A, rhs)
    return {
        "requests": len(svc.results),
        "lstsq": sum(rhs is not None for _, rhs in by_rid.values()),
        "wall_s": wall,
        "ticks": svc.tick_count,
        "p50_ms": lat[len(lat) // 2] * 1e3,
        "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3,
        "rebuilds": sum(len(r.events) for r in svc.results.values()),
        "compiled_segments": svc.compiled_programs,
    }


def main() -> None:
    enable_compile_cache()
    s = run(parse_args())
    print(f"served {s['requests']} requests in {s['wall_s']:.2f}s "
          f"({s['requests'] / s['wall_s']:.1f} req/s) over {s['ticks']} "
          f"ticks; p50 {s['p50_ms']:.1f}ms p99 {s['p99_ms']:.1f}ms; "
          f"{s['rebuilds']} tenant REBUILDs; "
          f"{s['compiled_segments']} resident compiled segments")
    print("all results verified against numpy QR/lstsq")


if __name__ == "__main__":
    main()
