"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, without allocating any model-scale array:
  * proof the sharding config compiles (SPMD partitioning succeeds),
  * ``memory_analysis()`` per-device bytes (fits-in-HBM proof),
  * ``cost_analysis()`` raw numbers plus loop-corrected FLOPs / HBM bytes
    / per-link collective wire bytes from the HLO analyzer,
  * the tier ledger for framework-managed (host) state when the planner
    offloads optimizer moments (llama4-class models).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]

``main`` gives the CPU backend 512 virtual devices through ``XLA_FLAGS``
before JAX starts; importing this module leaves the flags alone.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.caption import CaptionConfig
from repro.launch import hlo_analysis, shardings as shmod, steps as steps_mod
from repro.launch.mesh import (chips as mesh_chips, make_production_mesh,
                               mesh_context)
from repro.launch.shapes import SHAPES, ShapeSpec, applicable
from repro.models.registry import ARCH_IDS, get as get_arch
from repro.optim import adamw

HBM_PER_CHIP = 16 * 1024**3
# Offload optimizer state when (moments+master) would eat >35% of HBM.
OFFLOAD_BYTES_FRAC = 0.35


def should_offload_opt(cfg: ArchConfig, n_chips: int) -> bool:
    opt_bytes = cfg.param_count() * 12  # fp32 mu+nu+master
    return opt_bytes / n_chips > OFFLOAD_BYTES_FRAC * HBM_PER_CHIP


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.batch * shape.seq
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.batch * shape.seq
    return 2.0 * n_active * shape.batch  # decode: one token per sequence


def lower_cell(arch_id: str, shape: ShapeSpec, mesh, *, n_micro: int = 0,
               fsdp=None, seq_shard=None, zero1: bool = False,
               wkv_chunked: bool = True, flash: bool = True):
    """Build + lower + compile one cell; returns (record, compiled)."""
    arch = get_arch(arch_id)
    cfg = arch.cfg
    scfg = shmod.ShardingConfig.for_arch(cfg)
    if fsdp is not None:
        scfg = dataclasses.replace(scfg, fsdp=fsdp)
    if zero1:
        scfg = dataclasses.replace(scfg, fsdp=False, zero1=True)
    specs = steps_mod.input_specs(arch, shape, mesh, scfg)
    n_dp = mesh_chips(mesh) // mesh.shape["model"]
    act_policy = shmod.activation_policy(
        mesh, seq_sharded=(shape.kind == "prefill" and shape.batch < n_dp
                           if seq_shard is None else seq_shard))
    if wkv_chunked:
        act_policy["_wkv_chunked"] = True
    if not flash:
        act_policy.pop("_flash", None)
    record_extra = {"zero1": zero1, "wkv_chunked": wkv_chunked, "flash": flash}

    if n_micro <= 0 and shape.kind == "train":
        # default: per-device microbatch of 1 sequence
        n_micro = max(1, shape.batch // n_dp)
    offload = shape.kind == "train" and should_offload_opt(cfg, mesh_chips(mesh))
    opt_cfg = adamw.AdamWConfig()
    record = {
        "arch": arch_id, "shape": shape.name, "kind": shape.kind,
        "mesh": dict(zip(mesh.axis_names, [int(mesh.shape[a]) for a in mesh.axis_names])),
        "chips": mesh_chips(mesh), "fsdp": scfg.fsdp,
        "n_micro": n_micro if shape.kind == "train" else 0,
        "offload_opt": offload,
        "model_flops_total": model_flops(cfg, shape),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        **record_extra,
    }

    t0 = time.time()
    with mesh_context(mesh):
        if shape.kind == "train":
            if offload:
                # ZeRO-offload structure: the device program is ONE
                # microbatch fwd+bwd emitting bf16 param-sharded grads; the
                # host daemon (TieredAdamW + BulkMover) accumulates in fp32
                # and pages moments/master. Per optimizer step the program
                # runs n_micro times (roofline aggregates accordingly).
                fn = steps_mod.make_micro_grad_step(arch, act_policy=act_policy)
                micro_batch = jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(
                        (l.shape[0] // n_micro,) + l.shape[1:], l.dtype,
                        sharding=l.sharding),
                    specs.batch)
                lowered = jax.jit(
                    fn, donate_argnums=(),
                    out_shardings=(specs.param_sh, None)).lower(
                    specs.params, micro_batch)
                record["offload_micro_step"] = True
                # host-side tier ledger: moments + master live on host DRAM
                opt_bytes = cfg.param_count() * 12
                per_host = opt_bytes / (mesh_chips(mesh) / 8)  # 8 chips/host
                record["offload_host_bytes_per_host"] = per_host
                record["offload_traffic_bytes_per_step_per_chip"] = (
                    cfg.param_count() * (12 + 12 + 2) / mesh_chips(mesh))
                # Caption migration cost: during convergence the controller
                # re-tiers one hill-climb step's worth of state every
                # (epoch_steps x probe_epochs) app steps; amortized over
                # steps this is repartition traffic the roofline must see
                # (benchmarks/roofline.py folds it into the tier term).
                ccfg = CaptionConfig()
                record["migration_bytes_per_step_per_chip"] = (
                    opt_bytes * ccfg.step
                    / (ccfg.epoch_steps * ccfg.probe_epochs)
                    / mesh_chips(mesh))
            else:
                fn = steps_mod.make_train_step(
                    arch, opt_cfg, n_micro=n_micro, act_policy=act_policy,
                    mesh=mesh, grad_shardings=specs.param_sh)
                lowered = jax.jit(
                    fn, donate_argnums=(0, 1),
                    out_shardings=(specs.param_sh, specs.opt_sh, None)).lower(
                    specs.params, specs.opt_state, specs.batch)
        elif shape.kind == "prefill":
            fn = steps_mod.make_prefill_step(arch, act_policy=act_policy)
            lowered = jax.jit(fn).lower(specs.params, specs.batch)
        else:
            fn = steps_mod.make_serve_step(arch, act_policy=act_policy)
            lowered = jax.jit(
                fn, donate_argnums=(1,),
                out_shardings=(None, specs.cache_sh)).lower(
                specs.params, specs.cache, specs.tokens)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    if ma is not None:
        record["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device": int(ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes),
        }
        record["fits_hbm"] = record["memory"]["peak_per_device"] <= HBM_PER_CHIP
    ca = compiled.cost_analysis() or {}
    record["cost_analysis"] = {
        "flops": float(ca.get("flops", -1)),
        "bytes_accessed": float(ca.get("bytes accessed", -1)),
    }
    t2 = time.time()
    hc = hlo_analysis.analyze(compiled.as_text(), n_devices=mesh_chips(mesh))
    record["analyze_s"] = round(time.time() - t2, 2)
    record["hlo"] = {
        "flops_per_device": hc.flops,
        "hbm_bytes_per_device": hc.hbm_bytes,
        "collective_counts": hc.collective_counts(),
        "ici_bytes_per_device": hc.collective_bytes("ici"),
        "dcn_bytes_per_device": hc.collective_bytes("dcn"),
    }
    return record, compiled


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, out_dir: str,
             **kw) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_arch(arch_id).cfg
    ok, why = applicable(cfg, shape)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch_id}__{shape_name}__{mesh_tag}"
    if not ok:
        record = {"arch": arch_id, "shape": shape_name, "skipped": why,
                  "mesh": mesh_tag}
        print(f"SKIP {name}: {why}")
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        try:
            record, compiled = lower_cell(arch_id, shape, mesh, **kw)
            mem = record.get("memory", {})
            print(f"OK   {name}: compile={record['compile_s']}s "
                  f"peak={mem.get('peak_per_device', 0)/2**30:.2f}GiB "
                  f"fits={record.get('fits_hbm')} "
                  f"flops/dev={record['hlo']['flops_per_device']:.3e} "
                  f"colls={record['hlo']['collective_counts']}")
        except Exception as e:
            record = {"arch": arch_id, "shape": shape_name, "mesh": mesh_tag,
                      "error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-2000:]}
            print(f"FAIL {name}: {type(e).__name__}: {str(e)[:200]}")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(record, f, indent=1, default=float)
    return record


def main():
    # Must precede the first JAX backend use of the process.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--n-micro", type=int, default=0)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--no-wkv-chunked", action="store_true")
    ap.add_argument("--no-flash", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch_id in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                rec = run_cell(arch_id, shape_name, multi_pod, args.out,
                               n_micro=args.n_micro, zero1=args.zero1,
                               wkv_chunked=not args.no_wkv_chunked,
                               flash=not args.no_flash)
                failures += "error" in rec
    print(f"\ndone; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
