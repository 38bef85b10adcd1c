"""Training driver: data pipeline -> jitted train step -> checkpoints.

Runs for real on any backend (CPU for the examples/tests: tiny configs;
TPU pods with the production mesh).  Composes every substrate: the
deterministic pipeline, AdamW (optionally tiered/offloaded via the
planner), async checkpointing, fault-tolerant resume, straggler
mitigation, and telemetry.

Usage (CPU example — a ~100M model for a few hundred steps):
  python -m repro.launch.train --arch starcoder2-3b --tiny --steps 200
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import tiers as tiers_mod
from repro.core.arbiter import CaptionArbiter, budgeted_config
from repro.core.caption import CaptionConfig, CaptionController
from repro.core.classifier import AccessProfile
from repro.core.telemetry import EpochWindow
from repro.core.warmstart import WarmStartMemo
from repro.core.planner import BufferReq, plan as plan_placement
from repro.core.policy import BufferClass
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get as get_arch
from repro.optim import adamw, offload, schedules
from repro.runtime.straggler import StragglerMitigator


def build(arch_id: str, *, tiny: bool, batch: int, seq: int, lr: float,
          total_steps: int, offload_fraction: float | None = None,
          devices: str = "tpu-v5e", slow_budget: float = 0.0):
    arch = get_arch(arch_id)
    if tiny:
        arch = arch.tiny()
    cfg = arch.cfg
    opt_cfg = adamw.AdamWConfig(
        lr=lr, schedule=schedules.warmup_cosine(min(100, total_steps // 10),
                                                total_steps))
    params = arch.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    # Paper integration: plan optimizer-state placement against the target
    # topology; if the plan spills, use the tiered optimizer.  With an
    # arbiter budget, the plan is reconciled with it UP FRONT (arbiter-
    # aware seeding) instead of letting the runtime clip from a bad start.
    topo = tiers_mod.topology_from_spec(devices)
    opt_bytes = n_params * 12
    req = BufferReq(
        "opt_state", BufferClass.OPT_STATE, opt_bytes,
        AccessProfile(opt_bytes, opt_bytes, dependent_chain=1,
                      parallelism=1024, granularity=4 << 20,
                      compute_seconds=0.1),
    )
    placement = None
    slow_weights = None
    if offload_fraction is None:
        placement = plan_placement(
            [req], topo, compute_seconds=0.1,
            reserve_fast_bytes=int(2 * n_params + 4 * n_params),
            write_budget_bw=slow_budget if slow_budget > 0 else None)
        offload_fraction = placement.slow_fraction("opt_state")
        dfr = placement.decisions["opt_state"].device_fractions
        if topo.n_slow > 1 and dfr:
            slow_weights = [dfr.get(n, 0.0) for n in topo.slow_names]
    if offload_fraction > 0:
        opt = offload.TieredAdamW(
            opt_cfg, slow_fraction=offload_fraction,
            slow_weights=slow_weights,
            slow_device_names=topo.slow_names if topo.n_slow > 1 else None)
        opt_state = opt.init(params)
    else:
        opt = None
        opt_state = adamw.init_state(params)
    return arch, opt_cfg, opt, params, opt_state, n_params, placement, topo


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--offload-fraction", type=float, default=None)
    ap.add_argument("--devices", default="tpu-v5e",
                    help="tier topology: a preset (tpu-v5e, paper, paper3) "
                         "or a '+'-joined device list, fast tier first "
                         "(e.g. ddr5-l8+cxl-a+cxl-b)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--caption", action="store_true",
                    help="dynamic re-tiering of opt-state between steps")
    ap.add_argument("--caption-epoch-steps", type=int, default=8)
    ap.add_argument("--slow-budget", type=float, default=0.0,
                    help="aggregate slow-tier write budget in bytes/s for "
                         "the CaptionArbiter (0 = slow tier's nt-store bw)")
    ap.add_argument("--memo-path", default=None,
                    help="JSON warm-start memo: a recurring workload seeds "
                         "Caption at its remembered converged weights")
    ap.add_argument("--duels", type=int, default=0,
                    help="paired probe duels per Caption candidate point "
                         "(noise-robust probing); 0 = single-sample")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch, opt_cfg, opt, params, opt_state, n_params, placement, topo = build(
        args.arch, tiny=args.tiny, batch=args.batch, seq=args.seq,
        lr=args.lr, total_steps=args.steps,
        offload_fraction=args.offload_fraction, devices=args.devices,
        slow_budget=args.slow_budget)
    cfg, mod = arch.cfg, arch.module
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"tiered_opt={'on' if opt else 'off'}")

    caption = None
    caption_window = None
    arbiter = None
    memo = None
    if args.caption and opt is not None:
        ccfg = CaptionConfig(epoch_steps=args.caption_epoch_steps,
                             duel_count=args.duels)
        if placement is not None:
            caption = CaptionController.from_plan(
                placement, "opt_state", topo, ccfg)
        else:
            caption = CaptionController(
                topo, ccfg, initial_fraction=opt.slow_fraction)
        # One arbiter spans every tiered buffer in this process; training
        # currently registers opt_state (a colocated serving engine or
        # tiered weights would register under the same budget).  An
        # explicit budget keeps per-device ceilings on multi-device
        # topologies (scaled to sum to it) instead of disabling them.
        arbiter = CaptionArbiter(topo, budgeted_config(topo, args.slow_budget))
        arbiter.register("opt_state", caption)
        caption_window = EpochWindow(opt.telemetry)
        if args.memo_path:
            memo = WarmStartMemo.load(args.memo_path)
            caption.attach_memo(memo)

    data = TokenPipeline(DataConfig(
        vocab=cfg.vocab_padded, batch=args.batch, seq=args.seq, seed=17))

    def make_batch(raw: dict) -> dict:
        b = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.family == "vlm":
            b["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.vision.n_prefix_tokens, cfg.d_model))
        if cfg.family == "audio":
            rng = np.random.default_rng(0)
            b["frames"] = jnp.asarray(rng.normal(
                size=(args.batch, cfg.encoder.n_ctx, cfg.d_model)), jnp.float32
            ).astype(jax.tree_util.tree_leaves(params)[0].dtype)
        return b

    loss_grad = jax.jit(jax.value_and_grad(
        lambda p, b: mod.loss(cfg, p, b, remat=True)))
    fused_step = None
    if opt is None:
        @jax.jit
        def fused_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: mod.loss(cfg, p, batch, remat=True))(params)
            params, opt_state, metrics = adamw.apply(
                opt_cfg, params, grads, opt_state)
            return params, opt_state, dict(metrics, loss=loss)

    ckpt = Checkpointer(args.ckpt_dir, keep=2)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        start, (params, opt_state_r), meta = ckpt.restore((params, opt_state))
        opt_state = opt_state_r
        print(f"resumed from step {start}")

    strag = StragglerMitigator()
    losses = []
    t0 = time.perf_counter()
    for step, raw in zip(range(start, args.steps), data.iter_from(start)):
        batch = make_batch(raw)
        if opt is None:
            def run():
                return fused_step(params, opt_state, batch)
            params, opt_state, metrics = strag.run(run)
        else:
            loss, grads = loss_grad(params, batch)
            params, opt_state, m2 = opt.step(params, grads, opt_state)
            metrics = dict(m2, loss=loss)
            if caption is not None and (step + 1) % caption.cfg.epoch_steps == 0:
                # Caption epoch: modeled step time on the target tiers is
                # the throughput signal; the window supplies write share
                # (paged state streams both ways) and writer concurrency
                # from the optimizer's actual route counters.
                slow_b = opt.traffic_per_step_bytes(opt_state)
                agg_nt_bw = sum(t.nt_store_bw for t in topo.slows)
                slow_s = slow_b / agg_nt_bw if agg_nt_bw else 0.0
                modeled = max(0.1, slow_s)  # compute floor from the plan
                fast_resident = (12 * n_params * (1 - caption.fraction)
                                 + 6 * n_params)  # opt state + params/grads
                decision = arbiter.observe_window(
                    "opt_state", caption_window, 1.0 / modeled,
                    mover=opt.mover,
                    fast_pressure=min(
                        1.0, fast_resident / topo.fast.capacity_bytes),
                    slow_name=(None if opt.mover is not None
                               else (topo.slow_names if topo.n_slow > 1
                                     else "host")))
                if decision.changed:
                    if topo.n_slow > 1 and len(decision.weights) > 1:
                        opt_state = opt.repartition_weights(
                            params, opt_state, decision.weights)
                        caption.actuated_weights(
                            opt.achieved_weights(params, opt_state))
                    else:
                        opt_state = opt.repartition(
                            params, opt_state, decision.fraction)
                        caption.actuated(sum(
                            opt.achieved_weights(params, opt_state)))
                    print(f"caption: slow_fraction -> "
                          f"{decision.fraction:.2f} ({decision.reason})")
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            dt = (time.perf_counter() - t0) / args.log_every
            print(f"step {step+1:5d} loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms/step")
            t0 = time.perf_counter()
        if (step + 1) % args.ckpt_every == 0 and opt is None:
            ckpt.save(step + 1, (params, opt_state), metadata={"arch": cfg.name})
    ckpt.wait()
    strag.close()
    if memo is not None:
        memo.save(args.memo_path)
        print(f"warmstart: entries={len(memo)} hits={memo.hits} "
              f"misses={memo.misses} -> {args.memo_path}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"redispatched={strag.stats.redispatched}")
    return losses


if __name__ == "__main__":
    main()
