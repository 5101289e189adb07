"""Training driver: ``python -m repro.launch.train --arch <id> [options]``.

Single-host entrypoint (the dry-run proves the production-mesh lowering;
this driver runs real steps on whatever devices exist). Smoke-scale by
default; pass --full to use the published config (requires a real pod).
"""
from __future__ import annotations

import argparse

from repro.configs import ARCHS, get_config, get_smoke
from repro.data.pipeline import DataConfig
from repro.ft.failures import FailureSchedule
from repro.ft.semantics import Semantics
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=sorted(ARCHS))
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (needs a pod)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "caqr_muon"])
    ap.add_argument("--semantics", default="rebuild",
                    choices=[s.value for s in Semantics])
    ap.add_argument("--fail", default="",
                    help="failure schedule, e.g. '17:2,30:1' (step:lane)")
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    tcfg = TrainConfig(
        steps=args.steps, lr=args.lr, n_lanes=args.lanes,
        optimizer=args.optimizer, semantics=Semantics(args.semantics),
        ckpt_every=50 if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir or "/tmp/repro_ckpt",
    )
    schedule = None
    if args.fail:
        events = {}
        for part in args.fail.split(","):
            s, l = part.split(":")
            events.setdefault(int(s), []).append(int(l))
        schedule = FailureSchedule(events=events)
    Trainer(cfg, tcfg, dcfg).run(schedule)


if __name__ == "__main__":
    main()
