#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process, for
setting its correctness limits: the program's readings (the lower end) and,
with ``--control``, the readings of the program one matmul precision step
lower (the upper end). Each seed runs the cell's own set-up, a window of
``--seconds`` and the check, exactly as ``run_cell.py`` does; set-up is
paid once for all seeds.

    python3 bench/readings.py --workload tsqr_tall.free --seeds 1,2,3 \\
        --seconds 0 [--control]

One JSON line per seed (the compared numbers, then under ``window`` the
seed's end-to-end readings, with no set-up time), then one with the largest
reading of each compared number.
"""
from __future__ import annotations

import argparse
import json
import sys

from run_cell import (NoAccelerator, cell_files, device_guard, load_driver,
                      lower_precision)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    entry, cell_file, config, traffic = cell_files(args.workload)
    try:
        device_guard(entry["chips"], allow_cpu=False)
    except NoAccelerator as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    if args.control:
        lower_precision()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = load_driver(cell_file)
    worst = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = driver.Cell(config, traffic, cell_file["limits"], seed)
        cell.setup(args.seconds)
        cell.window(args.seconds)
        e2e = cell.end_to_end(None)
        cell.release()
        attempted, failed, checks = cell.check()
        row = {name: value for name, value, _ in checks}
        print(json.dumps({"seed": seed, "control": args.control,
                          "attempted": attempted, "failed": failed, **row,
                          "window": e2e}),
              flush=True)
        for name, value in row.items():
            worst[name] = max(worst.get(name, value), value)
    print(json.dumps({"largest": worst, "control": args.control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
