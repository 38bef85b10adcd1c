"""Serving driver: batched engine over the tiered KV cache.

Usage (CPU demo):
  python -m repro.launch.serve --arch qwen2.5-32b --tiny --requests 16 \
      --slow-fraction 0.5

``--trace-dir DIR`` records the served run in a profiler trace under
``DIR`` (open it in TensorBoard or Perfetto): the engine's ``engine.*``
host spans beside the device's operations.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.core.arbiter import CaptionArbiter, budgeted_config
from repro.core.caption import CaptionConfig, CaptionController
from repro.core.ledger import TierLedger
from repro.core.mover import BulkMover
from repro.core.policy import MemPolicy
from repro.core.tiers import topology_from_spec
from repro.core.warmstart import WarmStartMemo
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get as get_arch
from repro.serving.engine import ServingEngine, kv_access_profile

#: where the engine copies KV-cache device arrays to the host.
HOST_READ_SITES = ("promote", "cow", "retile", "page_map")


def main(argv=None, params=None):
    """Serve ``--requests`` random prompts; returns the finished requests.

    ``params`` are the weights to serve; by default they are random,
    drawn from ``PRNGKey(0)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--slow-fraction", type=float, default=0.0)
    ap.add_argument("--devices", default="tpu-v5e",
                    help="tier topology: a preset (tpu-v5e, paper, paper3) "
                         "or a '+'-joined device list, fast tier first "
                         "(e.g. ddr5-l8+cxl-a+cxl-b)")
    ap.add_argument("--page-t", type=int, default=16)
    ap.add_argument("--caption", action="store_true",
                    help="dynamic re-tiering of KV pages between decode steps")
    ap.add_argument("--caption-epoch-steps", type=int, default=8)
    ap.add_argument("--slow-budget", type=float, default=0.0,
                    help="aggregate slow-tier write budget in bytes/s for "
                         "the CaptionArbiter (0 = slow tier's nt-store bw)")
    ap.add_argument("--latency-every", type=int, default=0,
                    help="every Nth request is latency-SLO class (pins its "
                         "KV pages fast); 0 = all batch-class")
    ap.add_argument("--prefix-pages", type=int, default=0,
                    help="shared-prefix page pool size; repeated prompt "
                         "prefixes attach by reference instead of replaying")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of prompt prefix shared across requests "
                         "(0 = fully independent prompts)")
    ap.add_argument("--admission", choices=("none", "cost"), default="none",
                    help="'cost': defer batch-class admissions the perf "
                         "model predicts would pressure latency pins")
    ap.add_argument("--async-mover", action="store_true",
                    help="issue Caption migrations unfenced so they overlap "
                         "decode compute (drained at epoch boundaries)")
    ap.add_argument("--memo-path", default=None,
                    help="JSON warm-start memo: converged Caption weights "
                         "are filed under a workload fingerprint and a "
                         "recurring workload seeds at its remembered "
                         "optimum, skipping the walk")
    ap.add_argument("--duels", type=int, default=0,
                    help="paired probe duels per Caption candidate point "
                         "(noise-robust probing); 0 = single-sample")
    ap.add_argument("--trace-dir", default=None,
                    help="record the served run in a profiler trace "
                         "under this directory")
    ap.add_argument("--ledger-report", action="store_true",
                    help="register the serving pools (KV + shared-prefix "
                         "pages) in a TierLedger and print the per-tier "
                         "capacity report after the run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = get_arch(args.arch)
    if args.tiny:
        arch = arch.tiny()
    cfg = arch.cfg
    if cfg.family not in ("dense", "vlm", "moe"):
        raise SystemExit("tiered serving demo targets uniform-attention archs")
    if params is None:
        params = arch.init(jax.random.PRNGKey(0))
    topology = topology_from_spec(args.devices)
    if topology.n_slow > 1:
        # Seed the per-device split bandwidth-proportionally (Fig. 10's
        # best static ratio); Caption tunes the vector from there.
        bw = topology.bandwidth_weights()
        policy = MemPolicy.from_tier_fractions(
            topology.fast.name, topology.slow_names,
            [args.slow_fraction * w for w in bw])
    else:
        policy = MemPolicy.from_slow_fraction("fast", "slow",
                                              args.slow_fraction)
    caption = None
    arbiter = None
    memo = None
    if args.caption:
        # §6.1 seeding: classify the KV cache's access profile against
        # the active slow pool — a latency-bound shape is fast-pinned
        # automatically (from_profile zeroes the prior and the floor).
        profile = kv_access_profile(cfg, args.max_batch, args.max_len,
                                    page_t=args.page_t)
        caption = CaptionController.from_profile(
            profile, topology,
            CaptionConfig(epoch_steps=args.caption_epoch_steps,
                          duel_count=args.duels),
            initial_fraction=args.slow_fraction)
        if args.memo_path:
            memo = WarmStartMemo.load(args.memo_path)
            caption.attach_memo(memo)
        # One arbiter owns the slow-tier write budget; the engine registers
        # its KV controller under it (more buffers would share the pool).
        arbiter = CaptionArbiter(topology,
                                 budgeted_config(topology, args.slow_budget))
    mover = (BulkMover(topology, asynchronous=True)
             if args.async_mover else None)
    ledger = TierLedger(topology) if args.ledger_report else None
    engine = ServingEngine(
        cfg, params, max_batch=args.max_batch, max_len=args.max_len,
        policy=policy, topology=topology, page_t=args.page_t,
        caption=caption, arbiter=arbiter, mover=mover,
        prefix_pages=args.prefix_pages, admission=args.admission,
        overlap=args.async_mover, ledger=ledger)
    rng = np.random.default_rng(0)
    shared = (rng.integers(0, cfg.vocab_padded,
                           size=args.shared_prefix).tolist()
              if args.shared_prefix else [])
    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = shared + rng.integers(0, cfg.vocab_padded, size=4).tolist()
        slo = ("latency" if args.latency_every
               and i % args.latency_every == 0 else "batch")
        engine.submit(prompt, max_new_tokens=args.new_tokens, slo=slo)
    counters0 = dict(engine.telemetry.counters)
    traced = (jax.profiler.trace(args.trace_dir) if args.trace_dir
              else contextlib.nullcontext())
    with traced:
        done = engine.run_until_drained()
    wall = time.perf_counter() - t0
    if mover is not None:
        mover.close()
    counters = {k: v - counters0.get(k, 0.0)
                for k, v in engine.telemetry.counters.items()}
    lats = sorted(r.latency for r in done)
    queue = sorted(r.admitted_at - r.submitted_at for r in done)
    ttft = sorted(r.first_token_at - r.submitted_at for r in done)
    p99 = lats[int(len(lats) * 0.99) - 1] if len(lats) > 1 else lats[0]
    print(f"completed={len(done)} wall={wall:.2f}s "
          f"p50={lats[len(lats)//2]*1e3:.1f}ms p99={p99*1e3:.1f}ms "
          f"queue_p50={queue[len(queue)//2]*1e3:.1f}ms "
          f"ttft_p50={ttft[len(ttft)//2]*1e3:.1f}ms "
          f"slow_frac={engine.cache.slow_fraction():.2f}")
    print("host reads: " + " ".join(
        f"{site}={int(counters.get(f'host_read_bytes|{site}', 0))}B"
        f"/{int(counters.get(f'host_reads|{site}', 0))}"
        for site in HOST_READ_SITES)
        + f" promoted_pages={int(counters.get('prefix.promoted_pages', 0))}"
        + "".join(f" prefill_{name}={int(counters.get(f'prefill.{name}', 0))}"
                  for name in ("calls", "tokens", "padded_tokens")))
    if topology.n_slow > 1:
        fr = engine.cache.device_fractions()
        print("devices: " + " ".join(f"{k}={v:.2f}" for k, v in fr.items()))
    if caption is not None:
        traj = " -> ".join(f"{f:.2f}" for _, f in engine.caption_trace[-8:])
        print(f"caption: phase={caption.phase.value} "
              f"epochs={int(counters.get('caption.epochs', 0))} "
              f"actuations={int(counters.get('caption.actuations', 0))} "
              f"trajectory {traj}")
    if memo is not None:
        memo.save(args.memo_path)
        print(f"warmstart: entries={len(memo)} hits={memo.hits} "
              f"misses={memo.misses} drift_misses={memo.drift_misses} "
              f"-> {args.memo_path}")
    if arbiter is not None:
        print(f"arbiter: budget={arbiter.cfg.slow_bw_budget:.3g} B/s "
              f"demand={arbiter.aggregate_demand_bw():.3g} B/s "
              f"grants={ {k: f'{v:.3g}' for k, v in arbiter.grants().items()} }")
    if args.prefix_pages:
        idx = engine.prefix_index
        print(f"prefix: hits={idx.hits} misses={idx.misses} "
              f"pages={idx.allocated_pages()} cow={idx.cow_copies} "
              f"evictions={idx.evictions} "
              f"prefill_avoided={engine.prefill_tokens_avoided}"
              f"/{engine.prefill_tokens_total}")
    if args.admission != "none":
        print(f"admission: deferrals={engine.admission_deferrals}")
    if args.async_mover:
        print(f"overlap: stall={engine.migration_stall_s*1e3:.1f}ms "
              f"hidden={engine.migration_hidden_s*1e3:.3f}ms "
              f"exposed={engine.migration_exposed_s*1e3:.3f}ms")
    if ledger is not None:
        engine.register_pools()
        print("ledger (framework-managed serving pools):")
        print(ledger.report())
    return done


if __name__ == "__main__":
    main()
