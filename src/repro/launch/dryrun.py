import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import — jax locks the device
count at first init. Everything else in the framework sees the real device
count; only this entrypoint forces 512 host devices so the production
meshes (16x16 and 2x16x16) can be built.

Per cell:
  * build the production mesh and the sharding-rule table;
  * lower the cell's step (train_step for train shapes, serve_step for
    decode shapes, prefill for prefill shapes) against ShapeDtypeStruct
    inputs with explicit in_shardings;
  * compile; record memory_analysis(), cost_analysis(), and the collective
    operand bytes parsed from the post-SPMD HLO;
  * write a JSON artifact to experiments/dryrun/ for §Roofline.

CLI:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both]    # subprocess per cell
  python -m repro.launch.dryrun --arch caqr            # the paper's own workload
"""
import argparse
import json
import re
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config, get_shape
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.dist import compat
from repro.dist import params_sharding as psh
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh, make_qr_mesh
from repro.models import api
from repro.models import transformer as tf

# Per-arch dry-run knobs: optimizer chosen so the training state fits
# 16 GiB/chip (adafactor's factored second moment is what lets the 1T-param
# kimi cell fit; see DESIGN.md §7 and EXPERIMENTS.md §Dry-run). Activations
# are bounded by sequence-parallel residual sharding + per-layer remat, so
# no gradient accumulation is needed.
TRAIN_KNOBS: Dict[str, Dict[str, Any]] = {
    "kimi-k2-1t-a32b": dict(opt="adafactor", remat_group=4),
    "nemotron-4-340b": dict(opt="adafactor", remat_group=4),
    "mixtral-8x22b": dict(opt="adafactor", remat_group=4),
    "mamba2-2.7b": dict(opt="adamw", remat_group=8),
    "recurrentgemma-9b": dict(opt="adamw", remat_group=2),
    "gemma2-2b": dict(opt="adamw", remat_group=2),
}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _collective_bytes(hlo: str, n_per_group_default: int) -> Dict[str, Any]:
    """Sum ring-model wire bytes per collective kind from post-SPMD HLO."""
    dt_bytes = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    totals = {k: 0.0 for k in kinds}
    counts = {k: 0 for k in kinds}
    op_re = re.compile(
        r"=\s+(?:\()?((?:[a-z0-9]+)\[[0-9,]*\][^)]*?)\)?\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start|-done)?\(", )
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    group_re = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
    group_re2 = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

    for line in hlo.splitlines():
        m = op_re.search(line)
        if not m or "-done(" in line:
            continue
        kind = m.group(2)
        # group size
        gm = group_re.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gm2 = group_re2.search(line)
            gsize = len(gm2.group(1).split(",")) if gm2 else n_per_group_default
        # sum all result shapes on the line (tuples possible)
        nbytes = 0.0
        for sm in shape_re.finditer(m.group(1)):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in dt_bytes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * dt_bytes[dt]
        if gsize <= 1:
            continue
        ring = (gsize - 1) / gsize
        factor = {"all-gather": ring, "reduce-scatter": ring,
                  "all-to-all": ring, "collective-permute": 1.0,
                  "all-reduce": 2.0 * ring}[kind]
        totals[kind] += nbytes * factor
        counts[kind] += 1
    totals["total_bytes"] = float(sum(totals[k] for k in kinds))
    totals["counts"] = counts
    return totals


def _abstract_opt_state(opt, params_abs):
    return jax.eval_shape(opt.init, params_abs)


def _model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D for training; 2*N*D per
    generated token for decode."""
    n_params = 0
    n_active = 0
    for leaf in jax.tree_util.tree_leaves(api.param_specs(cfg)):
        n = int(np.prod(leaf.shape))
        n_params += n
    if cfg.moe is not None:
        # active = non-expert params + top_k/E of expert params
        expert = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(api.param_specs(cfg))[0]:
            name = str(path)
            if any(w in name for w in ("w_gate", "w_in", "w_out")) and len(leaf.shape) >= 4:
                expert += int(np.prod(leaf.shape))
        n_active = n_params - expert + expert * cfg.moe.top_k / cfg.moe.n_experts
    else:
        n_active = n_params
    tokens = shape.global_batch * (1 if shape.is_decode else shape.seq_len)
    # fwd+bwd for training; fwd only for prefill and per-token decode
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens, n_params, n_active


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, multi_pod: bool,
               rule_overrides: Optional[Dict[str, Any]] = None,
               fsdp_override: Optional[Any] = "unset"):
    """Returns (fn, args_abs, in_shardings, out_shardings, rules)."""
    fsdp = ("pod", "data") if multi_pod else "data"
    if fsdp_override != "unset":
        fsdp = fsdp_override
    rules = shd.multi_pod_rules() if multi_pod else shd.single_pod_rules()
    if rule_overrides:
        rules.update(rule_overrides)
    batch_axes = rules["batch"]

    if shape.kind == "train":
        rules = dict(rules)
        if not (rule_overrides and "seq_shard" in rule_overrides):
            # sequence parallelism on the residual stream (default on) —
            # EXCEPT for recurrent mixers (Mamba2 SSD / RG-LRU): their
            # chunk/associative scans run over the sequence dim, and a
            # sharded scan dim forces the partitioner into per-iteration
            # all-gathers (observed 200 GiB/device blowup).
            kinds = {cfg.mixer_at(i) for i in range(cfg.n_layers)}
            rules["seq_shard"] = None if kinds & {"M", "R"} else "model"
        knobs = TRAIN_KNOBS.get(cfg.name, dict(opt="adamw"))
        if knobs["opt"] == "adafactor":
            from repro.optim.adafactor import adafactor
            opt = adafactor()
        else:
            from repro.optim.adamw import adamw
            opt = adamw()
        from repro.optim.schedule import constant
        from repro.train.step import TrainState, make_train_step
        step = make_train_step(cfg, opt, constant(1e-3))

        params_abs = api.param_specs(cfg)
        opt_abs = _abstract_opt_state(opt, params_abs)
        batch_abs = api.train_input_specs(cfg, shape)
        state_abs = TrainState(params_abs, opt_abs,
                               jax.ShapeDtypeStruct((), jnp.int32))
        p_sh = psh.tree_shardings(params_abs, mesh, fsdp)
        o_sh = psh.tree_shardings(opt_abs, mesh, fsdp)
        b_sh = psh.batch_shardings(batch_abs, mesh, batch_axes)
        state_sh = TrainState(p_sh, o_sh, NamedSharding(mesh, P()))
        return step, (state_abs, batch_abs), (state_sh, b_sh), (state_sh, None), rules

    if shape.kind == "prefill":
        fn = api.make_prefill(cfg)
        params_abs = api.param_specs(cfg)
        batch_abs = api.train_input_specs(cfg, shape)
        batch_abs.pop("labels")
        p_sh = psh.tree_shardings(params_abs, mesh, fsdp)
        b_sh = psh.batch_shardings(batch_abs, mesh, batch_axes)
        return fn, (params_abs, batch_abs), (p_sh, b_sh), None, rules

    # decode
    rules = dict(rules)
    if rule_overrides and "kv_seq_shard" in rule_overrides:
        pass  # caller controls the cache sharding
    elif shape.name == "long_500k":
        rules = shd.long_decode_overrides(rules)
        batch_axes = rules["batch"]
    else:
        # decode_32k: flash-decode over the model axis — the cache seq dim
        # shards 16-way (kv heads often cannot), cutting cache HBM 16x; the
        # partitioner inserts the tiny per-layer softmax all-reduces.
        rules["kv_seq_shard"] = "model"
    serve = api.make_serve_step(cfg)
    params_abs = api.param_specs(cfg)
    specs = api.decode_input_specs(cfg, shape)
    p_sh = psh.tree_shardings(params_abs, mesh, fsdp)
    tok_sh = psh.batch_shardings(
        {"token": specs["token"]}, mesh, batch_axes)["token"]
    cache_sh = psh.cache_shardings(
        specs["caches"], mesh, batch_axes, rules["kv_seq_shard"])
    args = [params_abs, specs["token"], specs["pos"], specs["caches"]]
    shardings = [p_sh, tok_sh, NamedSharding(mesh, P()), cache_sh]
    if cfg.encoder is not None:
        args.append(specs["enc_out"])
        shardings.append(psh.batch_shardings(
            {"e": specs["enc_out"]}, mesh, batch_axes)["e"])
    return serve, tuple(args), tuple(shardings), (None, cache_sh), rules


def _compile_variant(cfg, shape, mesh, multi_pod, rule_overrides=None,
                     fsdp_override="unset"):
    fn, args, in_sh, out_sh, rules = build_cell(
        cfg, shape, mesh, multi_pod, rule_overrides, fsdp_override)
    # donation: the train step donates its TrainState; the serve step donates
    # its caches — in-place update semantics, as a real engine runs.
    if shape.kind == "train":
        donate = (0,)
    elif shape.kind == "decode":
        donate = (3,)
    else:
        donate = ()
    t0 = time.time()
    with compat.set_mesh(mesh), shd.use_rules(rules):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled, time.time() - t0


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides: Optional[Dict[str, Any]] = None,
             rule_overrides: Optional[Dict[str, Any]] = None,
             fsdp_override: Any = "unset",
             tag: str = "") -> Dict:
    import dataclasses

    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = api.supports_shape(cfg, shape)
    if not ok:
        print(f"SKIP {arch} x {shape_name}: {why}")
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    # --- memory compile: production form (scanned layers, scan-scheduled ---
    # attention). XLA:CPU's buffer assignment over a fully unrolled graph
    # does not reuse buffers the way the TPU pipeliner does, and it emulates
    # bf16 dots in f32; the scanned module's memory analysis is the faithful
    # one.
    overrides = dict(overrides or {})
    if shape.kind == "train":
        rg = TRAIN_KNOBS.get(arch, {}).get("remat_group", 1)
        overrides.setdefault("remat_group", rg)
    cfg_mem = dataclasses.replace(cfg, attn_schedule="scan", **overrides)
    compiled_mem, t_mem = _compile_variant(
        cfg_mem, shape, mesh, multi_pod, rule_overrides, fsdp_override)
    ma = compiled_mem.memory_analysis()

    # --- cost compiles: two-point depth extrapolation -----------------------
    # XLA's cost_analysis counts while bodies once, and fully unrolling a
    # 96-layer stack does not compile in reasonable time on one CPU core.
    # The layer stack is periodic, so cost(L) = fixed + per_layer * L is
    # exact for flops and an excellent model for bytes/collectives: compile
    # (unrolled) at L1 = period and L2 = 2*period and extrapolate to the
    # full depth. Validated against a full unroll on tinyllama (<2% error).
    n_tokens = shape.global_batch * shape.seq_len
    loss_chunk = cfg.loss_chunk
    if shape.kind == "train":
        for cand in (n_tokens // 8, n_tokens // 16, n_tokens // 4, n_tokens):
            if cand and n_tokens % cand == 0:
                loss_chunk = cand
                break
    period = cfg.pattern_period
    L1, L2, L_full = period, 2 * period, cfg.n_layers
    t0 = time.time()
    if multi_pod:
        # The multi-pod pass proves the 'pod' axis shards (the production-
        # form lower+compile above succeeded); the roofline/cost table is
        # single-pod only, so the cost compiles are skipped here.
        ca = {"flops": 0.0, "bytes accessed": 0.0}
        coll = {"total_bytes": 0.0,
                "skipped": "cost analysis is single-pod only"}
        hlo_len = 0
    else:
        def cost_point(n_layers):
            cfg_c = dataclasses.replace(
                cfg, n_layers=n_layers, scan_unroll=True, loss_chunk=loss_chunk,
                attn_schedule="tri", **(overrides or {}))
            compiled_c, _ = _compile_variant(
                cfg_c, shape, mesh, multi_pod, rule_overrides, fsdp_override)
            ca = compiled_c.cost_analysis() or {}
            try:
                hlo = compiled_c.as_text()
                coll = _collective_bytes(hlo, 16)
                hlo_len = len(hlo)
            except Exception as e:  # pragma: no cover
                coll = {"total_bytes": 0.0, "error": str(e)}
                hlo_len = 0
            return ca, coll, hlo_len

        ca1, coll1, _ = cost_point(L1)
        ca2, coll2, hlo_len = cost_point(L2)

        def extrap(v1, v2):
            per_layer = (v2 - v1) / (L2 - L1)
            return v1 + per_layer * (L_full - L1)

        ca = {
            "flops": extrap(float(ca1.get("flops", 0.0)), float(ca2.get("flops", 0.0))),
            "bytes accessed": extrap(float(ca1.get("bytes accessed", 0.0)),
                                     float(ca2.get("bytes accessed", 0.0))),
        }
        kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                 "collective-permute")
        coll = {k: extrap(float(coll1.get(k, 0.0)), float(coll2.get(k, 0.0)))
                for k in kinds}
        coll["total_bytes"] = float(sum(coll[k] for k in kinds))
        coll["counts_L2"] = coll2.get("counts", {})
        coll["extrapolated_from_layers"] = [L1, L2]
    t_cost = time.time() - t0
    t_lower, t_compile = t_mem, t_cost
    mf, n_params, n_active = _model_flops(cfg, shape)

    # Analytic activation-memory estimate (TPU projection): XLA:CPU's buffer
    # assignment over scanned+rematted graphs does not model the TPU
    # pipeliner's reuse (and counts bf16 emulation in f32), so alongside the
    # CPU temp number we record: args + outputs + scan-carry stashes
    # (n_groups/remat_group x sharded residual) + a 2x working-set factor.
    if shape.kind == "train":
        mesh_axes = dict(mesh.shape)
        batch_div = mesh_axes.get("data", 1) * mesh_axes.get("pod", 1)
        seq_div = 1
        kinds = {cfg.mixer_at(i) for i in range(cfg.n_layers)}
        if not (kinds & {"M", "R"}):
            seq_div = mesh_axes.get("model", 1)
        rg = overrides.get("remat_group", 1)
        period = cfg.pattern_period
        n_groups = cfg.n_layers // period
        n_stash = max(n_groups // max(rg, 1), 1) + cfg.n_layers % period
        stash = (shape.global_batch // batch_div) * (shape.seq_len // seq_div) \
            * cfg.d_model * 2 * n_stash
        analytic = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                       - ma.alias_size_in_bytes + 3 * stash)
    else:
        analytic = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                       - ma.alias_size_in_bytes + 2 * 2**30)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": "ok",
        "n_chips": int(np.prod(list(mesh.shape.values()))),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes_est": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
            "peak_bytes_analytic": analytic,
        },
        "cost": {
            "flops_per_device": float(ca.get("flops", 0.0)),
            "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "model_flops_global": float(mf),
        "n_params": int(n_params),
        "n_active_params": int(n_active),
        "hlo_chars": hlo_len,
        "t_compile_mem_s": round(t_lower, 1),
        "t_compile_cost_s": round(t_compile, 1),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    rec["tag"] = tag
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    peak_gb = rec["memory"]["peak_bytes_analytic"] / 2**30
    print(f"OK {arch} x {shape_name} x {mesh_kind}: "
          f"peak/device ~{peak_gb:.2f} GiB (analytic; "
          f"cpu-assign {rec['memory']['peak_bytes_est']/2**30:.1f}), "
          f"flops/device {rec['cost']['flops_per_device']:.3e}, "
          f"coll {coll.get('total_bytes', 0)/2**30:.3f} GiB "
          f"(compile mem {t_lower:.0f}s + cost {t_compile:.0f}s)")
    return rec


def run_caqr_cell(mesh_kind: str, out_dir: str, m_rows: int = 65536,
                  n_cols: int = 4096, panel: int = 128, tag: str = "") -> Dict:
    """The paper's own workload: FT-CAQR of a general matrix on the full
    pod (one lane per chip)."""
    from repro.core import AxisComm
    from repro.core.caqr import caqr_factorize

    multi_pod = mesh_kind == "multi"
    mesh = make_qr_mesh(multi_pod=multi_pod)
    lanes = 512 if multi_pod else 256

    def qr_fn(a):
        res = caqr_factorize(a, AxisComm("qr"), panel)
        return res.R

    spec = P("qr", None)
    fn = jax.jit(
        compat.shard_map(qr_fn, mesh, in_specs=spec, out_specs=P())
    )
    A = jax.ShapeDtypeStruct((m_rows, n_cols), jnp.float32)
    t0 = time.time()
    with compat.set_mesh(mesh):
        lowered = fn.lower(A)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = _collective_bytes(hlo, lanes)
    # CAQR model flops: 2 m n^2 - (2/3) n^3
    mf = 2 * m_rows * n_cols**2 - (2 / 3) * n_cols**3
    rec = {
        "arch": "caqr", "shape": f"qr_{m_rows}x{n_cols}_b{panel}",
        "mesh": mesh_kind, "status": "ok", "n_chips": lanes,
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes_est": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes),
        },
        "cost": {
            "flops_per_device": float(ca.get("flops", 0.0)),
            "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "model_flops_global": float(mf),
        "t_total_s": round(time.time() - t0, 1),
    }
    os.makedirs(out_dir, exist_ok=True)
    rec["tag"] = tag
    suffix = f"__{tag}" if tag else ""
    with open(os.path.join(out_dir, f"caqr__{mesh_kind}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"OK caqr x {mesh_kind}: flops/dev {rec['cost']['flops_per_device']:.3e} "
          f"coll {coll['total_bytes']/2**30:.3f} GiB ({rec['t_total_s']}s)")
    return rec


def all_cells():
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if api.supports_shape(cfg, shape)[0]:
                cells.append((arch, shape.name))
    return cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.list:
        for a, s in all_cells():
            print(f"{a} x {s}")
        print("caqr x qr_65536x4096")
        return

    if args.all:
        failures = []
        for a, s in all_cells():
            for mk in meshes:
                fname = os.path.join(args.out, f"{a}__{s}__{mk}.json")
                if os.path.exists(fname):
                    print(f"cached {a} x {s} x {mk}")
                    continue
                r = subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun",
                     "--arch", a, "--shape", s, "--mesh", mk, "--out", args.out],
                    env={**os.environ, "PYTHONPATH": "src"},
                )
                if r.returncode != 0:
                    failures.append((a, s, mk))
        for mk in meshes:
            if not os.path.exists(os.path.join(args.out, f"caqr__{mk}.json")):
                subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun",
                     "--arch", "caqr", "--mesh", mk, "--out", args.out],
                    env={**os.environ, "PYTHONPATH": "src"},
                )
        if failures:
            print("FAILED CELLS:", failures)
            sys.exit(1)
        print("ALL CELLS OK")
        return

    assert args.arch
    for mk in meshes:
        if args.arch == "caqr":
            run_caqr_cell(mk, args.out)
        else:
            assert args.shape
            run_cell(args.arch, args.shape, mk, args.out)


if __name__ == "__main__":
    main()
