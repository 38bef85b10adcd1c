"""Drive the system's two main paths once on one TPU chip, and check them.

1. ``serve``: starcoder2-3b at its published width (30 layers, d_model
   3072, GQA 24/2, about 3.2 B parameters in bf16; random weights from a
   seed) answers 4 requests through ``repro.launch.serve`` with Caption
   re-tiering the KV pages between decode steps, once with no slow pages
   and once with half of them slow.  Checks: every request answered in
   full, the greedy tokens are identical under both placements, and the
   first decode step's logits lie within ``LOGIT_TOL`` of the model's
   own ``forward`` run in float32 at ``highest`` matmul precision.
2. ``embedding``: the paper's DLRM workload.  A 2 GiB integer-valued
   table (2^22 x 128 float32) becomes an ``InterleavedTensor`` on the
   ``memory_kind`` backend, half of its pages in HBM and half in pinned
   host memory.  ``bag_reduce`` through the compiled Pallas
   ``embedding_reduce`` kernel must equal a plain ``jnp`` gather-and-sum
   exactly, before and after a donated ``repartition_weights`` that a
   ``BulkMover`` ships through the Pallas ``stream_copy`` kernel.

Each phase prints one JSON line: its name, wall and compile seconds and
its checks.  The last line is the device summary.  Off a TPU the script
exits non-zero before doing any work; any failed check exits non-zero.

Usage: python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "starcoder2-3b"
SERVE_ARGS = ["--requests", "4", "--max-batch", "4", "--max-len", "64",
              "--new-tokens", "16", "--page-t", "16", "--shared-prefix", "28",
              "--caption", "--caption-epoch-steps", "4"]
SLOW_FRACTIONS = ("0", "0.5")
#: prompt of the logits check: three KV pages of 16 tokens, some slow.
LOGIT_PROMPT = 40
#: bf16 serving path against the float32 forward: max |difference| over
#: max |reference logit|.  bf16 keeps 8 significant bits (a relative
#: step of 2^-8 = 0.4%), and 30 layers of bf16 activations compound it.
LOGIT_TOL = 0.05

EMB_ROWS, EMB_DIM, PAGE_ROWS = 1 << 22, 128, 256
BAGS, BAG = 512, 32
#: slow share after the repartition: 2048 more pages move to the host.
SLOW_AFTER = 0.625


class CompileClock:
    """Seconds XLA spent compiling, or loading compiled programs from the
    persistent cache, and the programs loaded from it, since this clock
    was made."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self.EVENT:
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1


def reference_logits(arch, params, prompt):
    """Last-position logits of the model's own ``forward`` in float32.

    The bf16 layer weights are promoted to float32 where they meet the
    float32 activations, so no full float32 copy of them is held."""
    import jax
    import jax.numpy as jnp

    ref_params = dict(params)
    for name in ("embed", "lm_head"):
        if name in ref_params:
            ref_params[name] = ref_params[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: arch.module.forward(
            arch.cfg, p, t, last_only=True))(
                ref_params, jnp.asarray([prompt], jnp.int32))
    return np.asarray(logits[0, -1], np.float32)


def serve_phase(arch_id: str = ARCH, tiny: bool = False):
    import jax

    from repro.core.policy import MemPolicy
    from repro.launch import serve
    from repro.models.registry import get as get_arch
    from repro.serving.engine import ServingEngine

    arch = get_arch(arch_id)
    if tiny:
        arch = arch.tiny()
    cfg = arch.cfg
    params = jax.block_until_ready(arch.init(jax.random.PRNGKey(0)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    argv = ["--arch", arch_id] + (["--tiny"] if tiny else []) + SERVE_ARGS
    answers = {}
    for frac in SLOW_FRACTIONS:
        done = serve.main(argv + ["--slow-fraction", frac], params=params)
        answers[frac] = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    n_req = int(SERVE_ARGS[SERVE_ARGS.index("--requests") + 1])
    n_new = int(SERVE_ARGS[SERVE_ARGS.index("--new-tokens") + 1])

    # First decode step of one request on a half-slow KV placement.
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, size=LOGIT_PROMPT).tolist()
    engine = ServingEngine(
        cfg, params, max_batch=1, max_len=64, page_t=16,
        policy=MemPolicy.from_slow_fraction("fast", "slow", 0.5))
    engine.submit(prompt, max_new_tokens=1)
    engine.step()
    got = np.asarray(engine.last_logits[0], np.float32)
    ref = reference_logits(arch, params, prompt)
    max_diff = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    checks = {
        "all_answered": all(len(a) == n_req and all(len(t) == n_new for t in a)
                            for a in answers.values()),
        "tokens_equal_across_placements": len(set(
            json.dumps(a) for a in answers.values())) == 1,
        "logits_finite": bool(np.isfinite(got).all()),
        "logits_within_tol": max_diff <= LOGIT_TOL * scale,
    }
    info = {
        "arch": cfg.name, "params": int(n_params), "n_layers": cfg.n_layers,
        "d_model": cfg.d_model, "param_dtype": cfg.param_dtype,
        "requests": n_req, "new_tokens": n_new,
        "tokens_per_placement": sum(len(t) for t in answers[SLOW_FRACTIONS[0]]),
        "logits_max_abs_diff": max_diff, "logits_ref_max_abs": scale,
        "logits_tol": LOGIT_TOL * scale,
        "slow_pages_in_logits_check": float(engine.cache.slow_fraction()),
        "argmax_equal": int(got.argmax()) == int(ref.argmax()),
        "tokens_differing": sum(
            a != b for x, y in zip(*answers.values()) for a, b in zip(x, y)),
    }
    return checks, info


def embedding_phase(rows: int = EMB_ROWS, backend: str = "memory_kind"):
    import jax
    import jax.numpy as jnp

    from repro.core.donation import FULL_SHARD_COPIES
    from repro.core.interleave import InterleavedTensor
    from repro.core.mover import BulkMover, stream_executor
    from repro.core.policy import MemPolicy
    from repro.core.tiers import tpu_v5e_topology
    from repro.kernels.common import interpret_default
    from repro.kernels.embedding_reduce import kernel as er_kernel
    from repro.kernels.embedding_reduce import ops as er_ops

    k_table, k_idx, k_w = jax.random.split(jax.random.PRNGKey(2), 3)
    table = jax.jit(lambda k: jax.random.randint(
        k, (rows, EMB_DIM), -8, 9).astype(jnp.float32))(k_table)
    idx = jax.random.randint(k_idx, (BAGS, BAG), 0, rows)
    w = jax.random.randint(k_w, (BAGS, BAG), 1, 4).astype(jnp.float32)
    # Plain reference over the whole table: elementwise products of small
    # integers summed in float32 are exact, whatever the order.
    ref = np.asarray(jax.jit(lambda t, i, w: (
        jnp.take(t, i, axis=0) * w[..., None]).sum(1))(table, idx, w))

    n_pages = rows // PAGE_ROWS
    it = InterleavedTensor.from_array(
        table, MemPolicy.from_slow_fraction("hbm", "host", 0.5), PAGE_ROWS,
        headroom=n_pages // 8, backend=backend)
    kinds = [p.sharding.memory_kind for p in it.parts]
    before = np.asarray(it.bag_reduce(idx, w, reduce_fn=er_ops.embedding_reduce))
    lowered = jax.jit(er_kernel.embedding_reduce,
                      static_argnames="interpret").lower(
        it.parts[0], idx, w, interpret=interpret_default()).as_text()

    FULL_SHARD_COPIES.reset()
    with BulkMover(tpu_v5e_topology(), asynchronous=False,
                   execute=stream_executor()) as mover:
        moved = it.repartition_weights((SLOW_AFTER,), mover=mover,
                                       fast_tier="hbm", slow_tier="host",
                                       donate=True)
        descriptors, moved_bytes = (mover.descriptors_submitted,
                                    mover.bytes_submitted)
    copies = FULL_SHARD_COPIES.reset()
    parent_deleted = [p.is_deleted() for p in it.parts]
    del it
    kinds_after = [p.sharding.memory_kind for p in moved.parts]
    after = np.asarray(moved.bag_reduce(idx, w,
                                        reduce_fn=er_ops.embedding_reduce))
    page_bytes = PAGE_ROWS * EMB_DIM * 4
    want_kinds = (["device", "pinned_host"] if backend == "memory_kind"
                  else kinds)
    checks = {
        "memory_kinds": kinds == want_kinds and kinds_after == want_kinds,
        "kernel_compiled": not interpret_default()
                           and "tpu_custom_call" in lowered,
        "bag_reduce_exact": bool(np.array_equal(before, ref)),
        "slow_share_moved": abs(moved.slow_fraction() - SLOW_AFTER) < 1e-9,
        "moved_bytes": moved_bytes == int(round((SLOW_AFTER - 0.5) * n_pages))
                       * page_bytes,
        "bag_reduce_exact_after_move": bool(np.array_equal(after, ref)),
    }
    info = {
        "table_bytes": rows * EMB_DIM * 4, "bags": BAGS, "bag_size": BAG,
        "memory_kinds": kinds, "memory_kinds_after": kinds_after,
        "slow_fraction": [0.5, moved.slow_fraction()],
        "mover_descriptors": descriptors, "moved_bytes": moved_bytes,
        "full_shard_copies": copies, "donated_parent_deleted": parent_deleted,
        "max_abs_diff": [float(np.abs(before - ref).max()),
                         float(np.abs(after - ref).max())],
    }
    return checks, info


def run_phase(name, fn, clock) -> bool:
    t0 = time.perf_counter()
    c0, h0 = clock.seconds, clock.cache_hits
    checks, info = fn()
    ok = all(checks.values())
    print(json.dumps({"phase": name, "ok": ok,
                      "wall_s": time.perf_counter() - t0,
                      "compile_s": clock.seconds - c0,
                      "compile_cache_hits": clock.cache_hits - h0,
                      "checks": checks, **info}), flush=True)
    return ok


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    print(json.dumps({"compile_cache": cache_dir}), flush=True)
    ok = run_phase("serve", serve_phase, clock)
    ok = run_phase("embedding", embedding_phase, clock) and ok
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
