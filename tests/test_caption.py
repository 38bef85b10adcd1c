"""Caption control loop tests: telemetry epoch windows, hill-climbing
convergence against the planner's analytic optimum, the §6 guardrails,
and the delta-page repartition paths (InterleavedTensor, TieredKVCache,
TieredAdamW) — including the numerical no-op property."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.testing import given, settings, st  # hypothesis, with fallback

from repro.core.caption import (CaptionConfig, CaptionController,
                                EpochMetrics)
from repro.core.classifier import AccessProfile, Boundedness
from repro.core.interleave import InterleavedTensor, minimal_delta_assignment
from repro.core.mover import BulkMover
from repro.core.planner import BufferReq, plan
from repro.core.policy import BufferClass, MemPolicy
from repro.core.telemetry import EpochWindow, Telemetry
from repro.core.tiers import TierTopology, tpu_v5e_topology

# The benchmark modules ARE the modeled workloads under test: import the
# SNC topology and DLRM throughput model from them so the test and the
# Fig. 9/11 benchmarks can never drift apart.
from benchmarks.fig8_dlrm import throughput as _fig8_throughput
from benchmarks.fig11_caption import snc_topology as _snc_topology


def _dlrm_throughput(topo, f_slow: float, threads: int = 32) -> float:
    return _fig8_throughput(topo.fast, topo.slow, f_slow, threads)


# -- telemetry epoch windows ---------------------------------------------------
def test_epoch_window_deltas_and_ewma():
    tel = Telemetry()
    win = EpochWindow(tel, ewma_alpha=0.5)
    tel.record_move("fast", "slow", 1000, 1.0)
    win.gauge("writer_concurrency", 3)
    s0 = win.tick(seconds=1.0)
    assert s0.route_bytes["fast->slow"] == 1000
    assert s0.route_bw["fast->slow"] == pytest.approx(1000.0)
    assert s0.gauges["writer_concurrency"] == 3
    # second epoch sees only the delta, EWMA smooths across windows
    tel.record_move("fast", "slow", 3000, 1.0)
    s1 = win.tick(seconds=1.0)
    assert s1.route_bytes["fast->slow"] == 3000
    assert s1.route_bw_ewma["fast->slow"] == pytest.approx(2000.0)
    assert s1.gauges == {}  # gauges do not leak across epochs
    assert s1.bytes_into("slow") == 3000 and s1.bytes_from("slow") == 0


# -- controller convergence ----------------------------------------------------
def test_caption_converges_to_planner_optimum():
    """On a synthetic bandwidth-bound workload the closed loop lands within
    tolerance of the planner's analytic optimum (the Fig. 9/11 regime)."""
    topo = _snc_topology()
    # analytic optimum from the static planner (x* balance equation)
    reads = 55e9 * 1.3
    p = plan([BufferReq("emb", BufferClass.EMBEDDING, 8 << 30,
                        AccessProfile(reads, 0, 1, 1024, 256, 1.0))],
             TierTopology(fast=dataclasses.replace(topo.fast,
                                                   capacity_bytes=96 << 30),
                          slow=topo.slow),
             compute_seconds=1.0)
    f_planner = p.slow_fraction("emb")

    ctl = CaptionController(
        topo, CaptionConfig(probe_epochs=2, step=0.05, min_step=0.01,
                            hysteresis=0.01))
    for _ in range(64):
        t = _dlrm_throughput(topo, ctl.fraction)
        ctl.observe(EpochMetrics(throughput=t))
    assert ctl.converged
    # converges into the planner's neighborhood AND beats membind-fast
    assert abs(ctl.fraction - f_planner) <= 0.12, (ctl.fraction, f_planner)
    assert (_dlrm_throughput(topo, ctl.fraction)
            >= _dlrm_throughput(topo, 0.0))
    # ... and within 5 points of the empirically best static split
    grid = np.linspace(0, 0.5, 101)
    best = float(grid[np.argmax([_dlrm_throughput(topo, float(f))
                                 for f in grid])])
    assert abs(ctl.fraction - best) <= 0.05, (ctl.fraction, best)


def test_caption_never_grows_latency_bound():
    """Guideline 5: a latency-bound profile only ever walks toward fast."""
    topo = tpu_v5e_topology()
    ctl = CaptionController(topo, CaptionConfig(probe_epochs=1),
                            initial_fraction=0.4,
                            boundedness=Boundedness.LATENCY_BOUND)
    fracs = [ctl.fraction]
    for i in range(40):
        # even when a (noisy) sample claims slow is better, growth is pinned
        t = 1.0 + 0.5 * ctl.fraction + (0.1 if i % 3 else -0.1)
        ctl.observe(EpochMetrics(throughput=t))
        fracs.append(ctl.fraction)
    assert all(b <= a + 1e-12 for a, b in zip(fracs, fracs[1:]))


def test_caption_writer_limit_and_pressure_guardrails():
    topo = tpu_v5e_topology()
    ctl = CaptionController(topo, CaptionConfig(probe_epochs=1, step=0.1))
    for _ in range(6):
        d = ctl.observe(EpochMetrics(throughput=1.0, writer_concurrency=8))
    assert ctl.fraction == 0.0  # growth frozen above the writer limit
    # high fast pressure freezes shrink steps
    ctl2 = CaptionController(topo, CaptionConfig(probe_epochs=1, step=0.1),
                             initial_fraction=0.5,
                             boundedness=Boundedness.LATENCY_BOUND)
    for _ in range(6):
        ctl2.observe(EpochMetrics(throughput=1.0, fast_pressure=0.99))
    assert ctl2.fraction == pytest.approx(0.5)


def test_caption_respects_capacity_floor_from_plan():
    """from_plan seeds fraction/floor/boundedness; the controller can never
    tune below the capacity spill minimum."""
    topo = tpu_v5e_topology()
    reqs = [BufferReq("opt", BufferClass.OPT_STATE, 30 << 30,
                      AccessProfile(30e9, 30e9, 1, 1024, 2 << 20, 0.05))]
    p = plan(reqs, topo, compute_seconds=0.05)
    d = p.decisions["opt"]
    assert d.min_slow_fraction > 0.4  # 30 GiB demand vs 16 GiB HBM
    ctl = CaptionController.from_plan(p, "opt", topo,
                                      CaptionConfig(probe_epochs=1))
    assert ctl.fraction == pytest.approx(d.slow_fraction)
    for _ in range(50):
        # throughput always "prefers" less slow; floor must still hold
        ctl.observe(EpochMetrics(throughput=1.0 / (1.0 + ctl.fraction)))
    assert ctl.fraction >= d.min_slow_fraction - 1e-9


# -- repartition: numerical no-op + delta-only traffic -------------------------
@given(st.integers(1, 7), st.integers(1, 7), st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_repartition_is_numerical_noop(wf, ws, seed):
    """reduce(before) == reduce(after) for any policy change (property)."""
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(8, 100))
    x = jnp.asarray(rng.normal(size=(rows, 4)), jnp.float32)
    it = InterleavedTensor.from_array(
        x, MemPolicy.weighted(("fast", "slow"), (wf, ws)), page_rows=8)
    idx = jnp.asarray(rng.integers(0, rows, size=(3, 5)))
    w = jnp.asarray(rng.uniform(size=(3, 5)), jnp.float32)
    before = it.bag_reduce(idx, w)
    target = float(rng.uniform(0, 1))
    it2 = it.repartition_fraction(target, telemetry=Telemetry())
    assert np.allclose(np.asarray(it2.to_array()), np.asarray(x))
    assert np.allclose(np.asarray(it2.bag_reduce(idx, w)),
                       np.asarray(before), atol=1e-5)


def test_repartition_moves_only_delta_pages():
    x = jnp.arange(64.0 * 4).reshape(64, 4)
    it = InterleavedTensor.from_array(x, MemPolicy.membind("fast"),
                                      page_rows=4)  # 16 pages
    tel = Telemetry()
    topo = tpu_v5e_topology()
    with BulkMover(topo, asynchronous=True, batch_size=4,
                   telemetry=tel) as mover:
        it2 = it.repartition_fraction(0.25, mover=mover, fast_tier="hbm",
                                      slow_tier="host")
        it3 = it2.repartition_fraction(0.5, mover=mover, fast_tier="hbm",
                                       slow_tier="host")
    page_bytes = 4 * it.row_bytes
    assert tel.route("hbm", "host").bytes_moved == 8 * page_bytes  # 4 + 4
    assert tel.route("host", "hbm").bytes_moved == 0
    assert it3.slow_fraction() == pytest.approx(0.5)
    assert np.allclose(np.asarray(it3.to_array()), np.asarray(x))


def test_minimal_delta_assignment_properties():
    cur = np.array([0, 1, 0, 0, 1, 0, 0, 0], np.int8)
    out = minimal_delta_assignment(cur, 0.5)
    assert int(out.sum()) == 4
    assert int((out != cur).sum()) == 2  # exactly the delta
    back = minimal_delta_assignment(out, 0.0)
    assert int(back.sum()) == 0


# -- serving: engine rebalances mid-decode, tokens unchanged -------------------
def test_engine_caption_rebalances_same_tokens(key):
    from repro.models import registry
    from repro.serving.engine import ServingEngine
    arch = registry.get("internvl2-2b").tiny()
    params = arch.module.init(arch.cfg, key)

    def run(caption):
        eng = ServingEngine(arch.cfg, params, max_batch=2, max_len=32,
                            policy=MemPolicy.membind("fast"),
                            topology=_snc_topology(), page_t=8,
                            caption=caption, telemetry=Telemetry())
        for _ in range(3):
            eng.submit([5, 6, 7], max_new_tokens=6)
        done = eng.run_until_drained()
        return eng, sorted((r.rid, tuple(r.generated)) for r in done)

    ctl = CaptionController(
        _snc_topology(), CaptionConfig(epoch_steps=2, probe_epochs=1))
    eng_dyn, toks_dyn = run(ctl)
    _, toks_static = run(None)
    assert toks_dyn == toks_static  # re-tiering never changes outputs
    assert len(eng_dyn.caption_trace) >= 2  # the loop actually ran


def test_engine_caption_mover_uses_topology_tier_names(key):
    """The engine's mover path must address the mover's REAL tier names
    (hbm/host on v5e), and migrations must flow through it batched."""
    from repro.models import registry
    from repro.serving.engine import ServingEngine
    arch = registry.get("internvl2-2b").tiny()
    params = arch.module.init(arch.cfg, key)
    topo = tpu_v5e_topology()
    tel = Telemetry()
    with BulkMover(topo, asynchronous=True, batch_size=4,
                   telemetry=tel) as mover:
        ctl = CaptionController(
            topo, CaptionConfig(epoch_steps=2, probe_epochs=1, step=0.25))
        eng = ServingEngine(arch.cfg, params, max_batch=2, max_len=32,
                            policy=MemPolicy.membind("fast"), topology=topo,
                            page_t=4, caption=ctl, mover=mover, telemetry=tel)
        for _ in range(2):
            eng.submit([5, 6, 7], max_new_tokens=6)
        done = eng.run_until_drained()
    assert len(done) == 2
    assert any(f > 0 for _, f in eng.caption_trace)  # the loop moved pages
    r = tel.route("hbm", "host")
    assert r.bytes_moved > 0  # migrations metered under real tier names
    assert r.batches <= r.descriptors  # batched submission, not per-page


# -- optimizer: repartition preserves training trajectory ----------------------
def test_tiered_adamw_repartition_preserves_trajectory():
    """Re-tiering opt state mid-training must not change the math: training
    with a mid-run repartition matches the fused optimizer."""
    from repro.optim import adamw, offload, schedules
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (64, 16))
    y = x @ jax.random.normal(key, (16, 4))
    params0 = {"a": jnp.zeros((16 * 4,), jnp.float32),
               "b": jnp.zeros((16 * 4,), jnp.float32)}
    cfg = adamw.AdamWConfig(lr=1e-2, weight_decay=0.0,
                            schedule=schedules.constant())

    def loss(p):
        w = (p["a"] + p["b"]).reshape(16, 4)
        return jnp.mean((x @ w - y) ** 2)

    # fused reference
    pf, sf = params0, adamw.init_state(params0)
    for _ in range(8):
        pf, sf, _ = adamw.apply(cfg, pf, jax.grad(loss)(pf), sf)

    tel = Telemetry()
    opt = offload.TieredAdamW(cfg, slow_fraction=1.0, min_offload_bytes=64,
                              telemetry=tel)
    pt, st_ = params0, opt.init(params0)
    assert len(st_["slow"]) == 2
    for i in range(8):
        pt, st_, _ = opt.step(pt, jax.grad(loss)(pt), st_)
        if i == 3:  # mid-run: reclaim everything to the fast tier
            up_before = tel.route("host", "hbm").bytes_moved
            down_before = tel.route("hbm", "host").bytes_moved
            st_ = opt.repartition(pt, st_, 0.0)
            assert not st_["slow"]
            assert tel.route("host", "hbm").bytes_moved > up_before
            # delta only: reclaiming adds no device->host traffic (the
            # hbm->host bytes so far are step()'s own paging writebacks)
            assert tel.route("hbm", "host").bytes_moved == down_before
    for a, b in zip(jax.tree_util.tree_leaves(pf),
                    jax.tree_util.tree_leaves(pt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)


def test_tiered_adamw_repartition_partial_delta():
    """Moving 0 -> 0.5 offloads only the picked leaves; 0.5 -> 0.5 is free."""
    from repro.optim import adamw, offload, schedules
    params = {"a": jnp.ones((64,), jnp.float32),
              "b": jnp.ones((64,), jnp.float32)}
    cfg = adamw.AdamWConfig(lr=1e-2, schedule=schedules.constant())
    tel = Telemetry()
    opt = offload.TieredAdamW(cfg, slow_fraction=0.0, min_offload_bytes=64,
                              telemetry=tel)
    st_ = opt.init(params)
    assert not st_["slow"]
    st_ = opt.repartition(params, st_, 0.5)
    assert len(st_["slow"]) == 1
    down = tel.route("hbm", "host").bytes_moved
    assert down > 0
    st_ = opt.repartition(params, st_, 0.5)  # no transition -> no traffic
    assert tel.route("hbm", "host").bytes_moved == down


# -- KV cache repartition ------------------------------------------------------
def test_kv_cache_repartition_preserves_decode(key):
    """Decode logits are bit-identical under re-tiering mid-sequence:
    attention reads the KV in position order whatever the placement."""
    from repro.models import registry
    from repro.serving.kv_cache import TieredKVCache, tiered_decode_step
    arch = registry.get("internvl2-2b").tiny()
    cfg = arch.cfg
    params = arch.module.init(cfg, key)
    cache = TieredKVCache.create(cfg, 2, 32, MemPolicy.membind("fast"),
                                 page_t=8)
    toks = jnp.asarray([3, 9], jnp.int32)
    outs_a, outs_b = [], []
    cache_b = cache
    for t in range(6):
        la, cache = tiered_decode_step(cfg, params, cache, toks)
        lb, cache_b = tiered_decode_step(cfg, params, cache_b, toks)
        if t == 2:
            cache_b = cache_b.repartition_fraction(0.5, telemetry=Telemetry())
        outs_a.append(np.asarray(la))
        outs_b.append(np.asarray(lb))
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
