import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the appropriate program is lowered with production shardings:
    train_4k     -> train_step   (fwd+bwd+optimizer, grad accumulation)
    prefill_32k  -> prefill      (writes KV cache, last-token logits)
    decode_32k   -> decode_step  (1 new token against a seq_len cache)
    long_500k    -> decode_step  (SSM/hybrid archs only)

and compiled for the single-pod (16,16) and multi-pod (2,16,16) meshes.
``compiled.memory_analysis()`` proves the per-device footprint fits;
``cost_analysis()`` + the HLO collective parse feed §Roofline.

Results append to reports/dryrun/<cell>.json; existing cells are skipped
(resume-friendly: the full sweep runs cell-by-cell in subprocesses).

Usage:
    python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--out reports/dryrun]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import (
    SHAPES,
    ModelConfig,
    ShapeConfig,
    TrainConfig,
    arch_shapes,
    get_arch,
)
from repro.configs import ASSIGNED
from repro.launch import specs as S
from repro.launch.hlo_cost import analyze_hlo, cpu_bf16_upcast_bytes
from repro.launch.mesh import V5E_HBM_BYTES, make_production_mesh
from repro.launch.roofline import Roofline, parse_collectives
from repro.models import encdec, transformer
from repro.models.counting import count_active_params, count_params
from repro.models.sharding import use_activation_mesh
from repro.train.steps import make_train_step

# Per-arch fit presets: optimizer + grad-accumulation + sequence-parallel.
# 340B needs Adafactor (4B/param state vs 12) and seq-parallel remat saves;
# the big-activation cells bound per-micro tokens via microbatches.
FIT_PRESETS: Dict[str, Dict[str, Any]] = {
    "nemotron-4-340b": dict(optimizer="adafactor", microbatches=16, seq_parallel=True),
    "jamba-v0.1-52b": dict(optimizer="adafactor", microbatches=16, seq_parallel=False),
    "internvl2-26b": dict(optimizer="adafactor", microbatches=16, seq_parallel=False),
    "granite-3-8b": dict(optimizer="adamw", microbatches=8, seq_parallel=False),
    "granite-8b": dict(optimizer="adamw", microbatches=4, seq_parallel=False),
    "minicpm3-4b": dict(optimizer="adamw", microbatches=8, seq_parallel=False),
    "qwen2-moe-a2.7b": dict(optimizer="adamw", microbatches=8, seq_parallel=False),
    "granite-moe-3b-a800m": dict(optimizer="adamw", microbatches=4, seq_parallel=False),
    "rwkv6-7b": dict(optimizer="adamw", microbatches=4, seq_parallel=False),
    "whisper-large-v3": dict(optimizer="adamw", microbatches=8, seq_parallel=False),
}


def make_programs(cfg: ModelConfig, tcfg: TrainConfig):
    if cfg.family == "encdec":
        return {
            "train": make_train_step(cfg, tcfg),
            "prefill": lambda p, b, c: encdec.prefill(p, b, cfg, c),
            "decode": lambda p, c, t, pos: encdec.decode_step(p, c, t, pos, cfg),
        }
    return {
        "train": make_train_step(cfg, tcfg),
        "prefill": lambda p, b, c: transformer.prefill(p, b, cfg, c),
        "decode": lambda p, c, t, pos: transformer.decode_step(p, c, t, pos, cfg),
    }


def lower_cell(
    arch: str,
    shape: ShapeConfig,
    mesh_kind: str,
    *,
    overrides: Optional[Dict[str, Any]] = None,
):
    """Lower + compile one cell; returns the result record."""
    cfg = get_arch(arch)
    preset = dict(FIT_PRESETS.get(arch, {}))
    preset.update(overrides or {})
    seq_parallel = preset.pop("seq_parallel", False)
    remat = preset.pop("remat", None)
    scan_layers = preset.pop("scan_layers", None)
    moe_dispatch = preset.pop("moe_dispatch", None)
    moe_group_size = preset.pop("moe_group_size", None)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if scan_layers is not None:
        cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    if cfg.moe is not None and (moe_dispatch or moe_group_size):
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(
                cfg.moe,
                dispatch=moe_dispatch or cfg.moe.dispatch,
                group_size=moe_group_size or cfg.moe.group_size,
            ),
        )
    tcfg = TrainConfig(**{k: v for k, v in preset.items() if k in
                          {f.name for f in dataclasses.fields(TrainConfig)}})
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if shape.kind == "train":
        # per-microbatch batch must stay shardable over the DP extent:
        # B_micro < dp would silently replicate every activation (measured
        # 5-30x memory blowup on the multi-pod mesh; see EXPERIMENTS §Perf).
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        mb_max = max(shape.global_batch // dp, 1)
        if tcfg.microbatches > mb_max:
            tcfg = dataclasses.replace(tcfg, microbatches=mb_max)
    programs = make_programs(cfg, tcfg)

    t0 = time.time()
    with use_activation_mesh(mesh, seq_parallel=seq_parallel):
        if shape.kind == "train":
            fn = jax.jit(programs["train"], donate_argnums=(0,))
            state = S.state_specs(cfg, tcfg, mesh)
            batch = S.input_specs(cfg, shape, mesh)
            lowered = fn.lower(state, batch)
        elif shape.kind == "prefill":
            fn = jax.jit(programs["prefill"], donate_argnums=(2,))
            params = S.param_specs_only(cfg, mesh)
            batch = S.input_specs(cfg, shape, mesh)
            cache = S.cache_specs(cfg, shape, mesh)
            lowered = fn.lower(params, batch, cache)
        else:  # decode
            fn = jax.jit(programs["decode"], donate_argnums=(1,))
            params = S.param_specs_only(cfg, mesh)
            cache = S.cache_specs(cfg, shape, mesh)
            toks = S.input_specs(cfg, shape, mesh)["tokens"]
            pos = jnp.int32(shape.seq_len - 1)
            lowered = fn.lower(params, cache, toks, pos)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    # cost_analysis() counts while bodies ONCE; with scan-over-layers +
    # grad-accum scans that undercounts by the product of trip counts.
    # analyze_hlo re-derives per-device FLOPs/traffic/wire with trip-count
    # multipliers from the optimized HLO (see launch/hlo_cost.py).
    mc = analyze_hlo(hlo)
    upcast = cpu_bf16_upcast_bytes(hlo)

    n_dev = mesh.size
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        model_flops = 6.0 * count_active_params(cfg) * tokens
    elif shape.kind == "prefill":
        model_flops = 2.0 * count_active_params(cfg) * tokens
    else:  # decode: one token per sequence
        model_flops = 2.0 * count_active_params(cfg) * shape.global_batch

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    roof = Roofline(
        flops_per_device=mc.flops,
        hbm_bytes_per_device=mc.traffic_bytes,
        wire_bytes_per_device=mc.wire_bytes,
        model_flops_total=model_flops,
        num_devices=n_dev,
    )
    bytes_per_device = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    # clamp: arguments/outputs are live regardless; upcast bytes are a sum
    # over converts, not all simultaneously live, so this is a lower bound
    # and the true TPU peak lies in [projected, peak].
    live_floor = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
    )
    projected = max(bytes_per_device - upcast, live_floor)
    record = {
        "arch": arch,
        "shape": shape.name,
        "mesh": mesh_kind,
        "num_devices": n_dev,
        "params_total": count_params(cfg),
        "params_active": count_active_params(cfg),
        "preset": {**FIT_PRESETS.get(arch, {}), **(overrides or {})},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_live_bytes_per_device": bytes_per_device,
            "fits_16GiB": bool(bytes_per_device < V5E_HBM_BYTES),
            # XLA:CPU materializes f32 copies of bf16 matmul/conv operands
            # (no native bf16 on the host backend); those buffers do not
            # exist on the TPU target.  Projection: peak minus the measured
            # f32-upcast bytes that exceed what bf16 originals would need.
            "cpu_bf16_upcast_bytes": upcast,
            "peak_projected_tpu_bytes": projected,
            "fits_16GiB_tpu_projected": bool(projected < V5E_HBM_BYTES),
        },
        # xla_cost = raw cost_analysis() (while bodies counted once; kept for
        # reference).  hlo_cost = trip-count-corrected totals used by roofline.
        "xla_cost": {"flops_per_device": flops, "bytes_per_device": bytes_acc},
        "cost": {
            "flops_per_device": mc.flops,
            "bytes_per_device": mc.traffic_bytes,
        },
        "collectives": {
            k: {
                "count": mc.coll_count.get(k, 0),
                "wire_bytes": mc.wire_by_kind.get(k, 0.0),
            }
            for k in sorted(mc.wire_by_kind)
        },
        "collectives_unrolled_once": coll.summary(),
        "collective_wire_bytes_per_device": mc.wire_bytes,
        "model_flops_total": model_flops,
        "roofline": roof.row(),
    }
    return record


def cell_list(mesh_kinds):
    cells = []
    for arch in ASSIGNED:
        cfg = get_arch(arch)
        for shape in arch_shapes(cfg):
            for mk in mesh_kinds:
                cells.append((arch, shape.name, mk))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    ap.add_argument("--override", default="", help="k=v[,k=v] preset overrides")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = (
            v == "true" if v in ("true", "false") else int(v) if v.isdigit() else v
        )

    os.makedirs(args.out, exist_ok=True)
    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = cell_list(mesh_kinds)
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape, mk) for mk in mesh_kinds]

    failures = 0
    for arch, shape_name, mk in cells:
        tag = f"_{args.tag}" if args.tag else ""
        path = os.path.join(args.out, f"{arch}_{shape_name}_{mk}{tag}.json")
        if os.path.exists(path) and not args.force:
            print(f"[skip] {path}", flush=True)
            continue
        print(f"[cell] {arch} x {shape_name} x {mk} ...", flush=True)
        try:
            rec = lower_cell(arch, SHAPES[shape_name], mk, overrides=overrides)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            print(
                f"  ok: compile {rec['compile_s']}s, "
                f"mem/dev {rec['memory']['peak_live_bytes_per_device']/2**30:.2f} GiB, "
                f"dominant={r['dominant']}, mfu_bound={r['roofline_mfu']:.3f}",
                flush=True,
            )
        except Exception as e:
            failures += 1
            print(f"  FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
