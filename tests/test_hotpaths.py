"""Hot-path tests (ISSUE 5): routed-access old-vs-new equivalence
(bit-exact), repartition descriptor coalescing + billed-byte invariance,
shape-stable capacity-padded shards, and jit trace-count assertions
across multi-epoch Caption walks."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.testing import given, settings, st  # hypothesis, with fallback

from repro.core.caption import CaptionConfig, CaptionController, EpochMetrics
from repro.core.interleave import (InterleavedTensor, contiguous_runs,
                                   device_page_map, minimal_delta_weights)
from repro.core.mover import BulkMover
from repro.core.policy import MemPolicy
from repro.core.telemetry import Telemetry
from repro.core.tiers import (TierTopology, paper_three_device_topology,
                              tpu_v5e_topology)
from repro.serving.kv_cache import _INT32_MAX, TieredKVCache, _kv_layout_rows


def _tensor(rng, rows=100, feat=4, page_rows=8, weights=(3, 1), headroom=0):
    x = jnp.asarray(rng.normal(size=(rows, feat)), jnp.float32)
    it = InterleavedTensor.from_array(
        x, MemPolicy.weighted(("fast", "slow"), weights), page_rows,
        headroom=headroom)
    return it, np.asarray(x)


# -- routed access: single-pass bucketed == masked N-pass (bit-exact) ---------
@given(st.integers(0, 500), st.integers(0, 20))
@settings(max_examples=25, deadline=None)
def test_gather_bucketed_equals_masked_bit_exact(seed, headroom):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(8, 120))
    it, x = _tensor(rng, rows=rows,
                    weights=(int(rng.integers(1, 6)), int(rng.integers(1, 6))),
                    headroom=headroom)
    if headroom:  # exercise the free-slot (non-rank) local layout too
        it = it.repartition_fraction(float(rng.uniform(0, 1)),
                                     telemetry=Telemetry())
    idx = rng.integers(0, rows, size=(2, 7))
    got = it._gather_rows_bucketed(idx)
    ref = it._gather_rows_masked(jnp.asarray(idx))
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    # the public entry with concrete indices routes through the bucketed
    # path and still equals the source array
    assert np.array_equal(np.asarray(it.gather_rows(jnp.asarray(idx))),
                          x[idx])


@given(st.integers(0, 500))
@settings(max_examples=25, deadline=None)
def test_scatter_bucketed_equals_masked(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(16, 120))
    it, x = _tensor(rng, rows=rows)
    # "set" with distinct indices (duplicate-set order is unspecified in
    # both formulations); "add" with duplicates must accumulate equally
    idx_set = rng.permutation(rows)[:8]
    vals = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    a = it._scatter_bucketed(idx_set, vals, "set")
    b = it._scatter_masked(jnp.asarray(idx_set), vals, "set")
    assert np.array_equal(np.asarray(a.to_array()), np.asarray(b.to_array()))
    idx_add = rng.integers(0, rows, size=8)
    c = it._scatter_bucketed(idx_add, vals, "add")
    d = it._scatter_masked(jnp.asarray(idx_add), vals, "add")
    np.testing.assert_allclose(np.asarray(c.to_array()),
                               np.asarray(d.to_array()), atol=1e-6)


def test_routed_access_traced_falls_back_to_masked():
    """Inside jit the masked formulation runs (static shapes) and agrees
    with the host path."""
    rng = np.random.default_rng(0)
    it, x = _tensor(rng)
    idx = jnp.asarray(rng.integers(0, 100, size=8))
    f = jax.jit(lambda t, i: t.gather_rows(i))
    assert np.array_equal(np.asarray(f(it, idx)),
                          np.asarray(it.gather_rows(idx)))


# -- vectorized bookkeeping == reference loops --------------------------------
@given(st.integers(0, 300))
@settings(max_examples=30, deadline=None)
def test_device_page_map_matches_reference_loop(seed):
    rng = np.random.default_rng(seed)
    n_devices = int(rng.integers(1, 5))
    assign = rng.integers(0, n_devices, size=int(rng.integers(1, 64)))
    dev, local, counts = device_page_map(assign.astype(np.int8), n_devices)
    # reference: the pre-change per-page counter walk
    ref_local = np.zeros(len(assign), np.int32)
    counters = [0] * n_devices
    for p, d in enumerate(assign):
        ref_local[p] = counters[d]
        counters[d] += 1
    assert np.array_equal(local, ref_local)
    assert counts == counters
    assert np.array_equal(dev, assign)


@given(st.integers(0, 300))
@settings(max_examples=30, deadline=None)
def test_kv_layout_rows_matches_reference_loop(seed):
    from repro.core.interleave import tier_page_map
    rng = np.random.default_rng(seed)
    B, P = int(rng.integers(1, 5)), int(rng.integers(1, 10))
    pt = int(rng.integers(1, 6))
    assign = rng.integers(0, 3, size=(B, P)).astype(np.int8)
    a01, local, Tf, Ts, pf, ps = _kv_layout_rows(assign, pt)
    # reference: the pre-change per-slot B x P python walk
    assign01 = np.minimum(assign, 1).astype(np.int8)
    rl = np.zeros((B, P), np.int32)
    n_slow = np.zeros(B, np.int64)
    for b in range(B):
        _, loc, counters = tier_page_map(assign01[b])
        rl[b] = loc
        n_slow[b] = counters[1]
    rTs = int(n_slow.max()) * pt
    rpf = np.full((B, P * pt), _INT32_MAX, np.int32)
    rps = (np.full((B, rTs), _INT32_MAX, np.int32) if rTs
           else np.zeros((B, 0), np.int32))
    for b in range(B):
        fpos, spos = [], []
        for p in range(P):
            (spos if assign01[b, p] else fpos).extend(
                range(p * pt, (p + 1) * pt))
        rpf[b, : len(fpos)] = fpos
        if rTs and spos:
            rps[b, : len(spos)] = spos
    assert np.array_equal(a01, assign01) and np.array_equal(local, rl)
    assert (Tf, Ts) == (P * pt, rTs)
    assert np.array_equal(pf, rpf) and np.array_equal(ps, rps)


# -- repartition: coalescing + billed-byte invariance -------------------------
def test_one_point_shift_issues_run_coalesced_descriptors():
    """The acceptance bar: a 1-point weight shift on a 4096-page tensor
    issues O(delta-runs) mover descriptors, not one per page, while the
    billed bytes stay exactly delta * page_bytes."""
    topo = paper_three_device_topology()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096 * 4, 8)), jnp.float32)
    it = InterleavedTensor.from_array(
        x, MemPolicy.from_slow_fraction("fast", "slow", 0.3), page_rows=4,
        headroom=512)
    page_bytes = 4 * it.row_bytes
    cur_slow = int(np.asarray(it.page_tier).sum())
    delta = abs(round(0.31 * it.n_pages) - cur_slow)
    tel = Telemetry()
    with BulkMover(topo, asynchronous=True, batch_size=16,
                   telemetry=tel) as mover:
        it2 = it.repartition_fraction(0.31, mover=mover,
                                      fast_tier=topo.fast.name,
                                      slow_tier=topo.slows[0].name)
        descs = mover.descriptors_submitted
        moved = mover.bytes_submitted
    assert delta >= 40  # a real 1-point shift on 4096 pages
    assert moved == delta * page_bytes
    assert descs < delta / 2, (descs, delta)  # coalesced runs
    assert np.array_equal(np.asarray(it2.to_array()), np.asarray(x))


def test_telemetry_path_billed_bytes_invariant():
    """Mover-less actuation bills identical bytes per route as the
    per-page accounting did (run records just aggregate)."""
    rng = np.random.default_rng(1)
    it, x = _tensor(rng, rows=512, page_rows=4)
    tel = Telemetry()
    before = int(np.asarray(it.page_tier).sum())
    it2 = it.repartition_fraction(0.5, telemetry=tel)
    after = int(np.asarray(it2.page_tier).sum())
    page_bytes = 4 * it.row_bytes
    total = sum(r.bytes_moved for r in tel.routes.values())
    assert total == abs(after - before) * page_bytes
    assert np.array_equal(np.asarray(it2.to_array()), x)


@given(st.integers(0, 300), st.integers(1, 32))
@settings(max_examples=25, deadline=None)
def test_minimal_delta_weights_run_pages_invariants(seed, run_pages):
    """For any run length: exact per-device counts, minimal move count,
    the no-op guarantee, and picks clustered into at most
    ceil(surplus/run) runs per surplus device."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 200))
    n_devices = int(rng.integers(2, 5))
    cur = rng.integers(0, n_devices, size=n).astype(np.int8)
    w = tuple(float(x) for x in rng.dirichlet(np.ones(n_devices))[1:])
    out = minimal_delta_weights(cur, w, n_devices, run_pages=run_pages)
    counts = np.bincount(cur, minlength=n_devices)
    if out is None:
        # no-op only when targets round to current counts
        again = minimal_delta_weights(cur, w, n_devices, run_pages=1)
        assert again is None
        return
    new_counts = np.bincount(out, minlength=n_devices)
    # page-count conservation + minimal moves
    assert new_counts.sum() == n
    moves = int((out != cur).sum())
    surplus = np.maximum(counts - new_counts, 0).sum()
    assert moves == surplus  # every move fills a real deficit
    # same targets as the page-at-a-time planner
    ref = minimal_delta_weights(cur, w, n_devices, run_pages=1)
    assert np.array_equal(np.bincount(ref, minlength=n_devices), new_counts)


def test_contiguous_runs():
    assert contiguous_runs(np.array([], np.int64)) == []
    assert contiguous_runs(np.array([3])) == [(0, 1)]
    assert contiguous_runs(np.array([1, 2, 3, 7, 8, 11])) == [
        (0, 3), (3, 2), (5, 1)]


# -- capacity-padded shards ---------------------------------------------------
def test_headroom_keeps_shapes_and_values_until_exhausted():
    rng = np.random.default_rng(2)
    it, x = _tensor(rng, rows=256, page_rows=8, weights=(1, 0), headroom=8)
    shapes = [p.shape for p in it.parts]
    cur = it
    for f in (0.1, 0.25, 0.05, 0.2):  # all fit 8 pages of headroom (32 pages)
        cur = cur.repartition_fraction(f, telemetry=Telemetry())
        assert [p.shape for p in cur.parts] == shapes
        assert np.allclose(np.asarray(cur.to_array()), x)
        dev = np.asarray(cur.page_device)
        local = np.asarray(cur.page_local)
        caps = cur.capacity_pages
        counts = cur.valid_page_counts()
        assert sum(counts) == cur.n_pages
        for i in range(cur.n_devices):  # locals valid + unique per device
            mine = np.sort(local[dev == i])
            assert counts[i] == mine.size <= caps[i]
            assert len(np.unique(mine)) == len(mine)
            assert mine.size == 0 or mine[-1] < caps[i]
    # exhaust the slow headroom: the shard grows (retrace by design)...
    grown = cur.repartition_fraction(0.9, telemetry=Telemetry())
    assert grown.parts[1].shape[0] > shapes[1][0]
    assert np.allclose(np.asarray(grown.to_array()), x)
    # ... and carries fresh headroom for the next walk
    assert grown.capacity_pages[1] >= round(0.9 * grown.n_pages) + 8


def test_headroom_zero_keeps_exact_legacy_shapes():
    rng = np.random.default_rng(3)
    it, x = _tensor(rng, rows=128, page_rows=4)
    it2 = it.repartition_fraction(0.4, telemetry=Telemetry())
    dev = np.asarray(it2.page_device)
    for i, part in enumerate(it2.parts):
        assert part.shape[0] == int((dev == i).sum()) * 4
    assert np.allclose(np.asarray(it2.to_array()), x)


# -- jit trace counts across Caption walks ------------------------------------
def test_interleave_walk_traces_once_across_epochs():
    """A jitted consumer over a capacity-padded tensor traces exactly
    once across >= 10 Caption probe epochs (the retrace-free acceptance
    bar)."""
    topo = tpu_v5e_topology()
    ctl = CaptionController(topo, CaptionConfig(probe_epochs=1, step=0.05),
                            initial_fraction=0.1)
    rng = np.random.default_rng(4)
    n_pages = 128
    x = jnp.asarray(rng.normal(size=(n_pages * 8, 4)), jnp.float32)
    it = InterleavedTensor.from_array(
        x, MemPolicy.from_slow_fraction("fast", "slow", 0.1), page_rows=8,
        headroom=ctl.headroom_pages(n_pages))
    traces = [0]

    def step(t, i):
        traces[0] += 1
        return t.bag_reduce(i)

    fn = jax.jit(step)
    idx = jnp.asarray(rng.integers(0, x.shape[0], size=(4, 8)))
    epochs = 0
    for _ in range(12):
        jax.block_until_ready(fn(it, idx))
        d = ctl.observe(EpochMetrics(throughput=1.0 + ctl.fraction))
        it = it.repartition_weights(d.weights, telemetry=Telemetry())
        ctl.actuated(it.slow_fraction())
        epochs += 1
    assert epochs >= 10
    assert traces[0] == 1, traces[0]
    assert np.allclose(np.asarray(it.to_array()), np.asarray(x))


def test_kv_decode_traces_once_across_walk(key):
    """The jitted decode step over a slow_headroom cache keeps its shapes
    (and its single trace) across repeated Caption repartitions."""
    from repro.models import registry
    from repro.serving.kv_cache import tiered_decode_step
    arch = registry.get("internvl2-2b").tiny()
    cfg = arch.cfg
    params = arch.module.init(cfg, key)
    pol = MemPolicy.from_tier_fractions("fast", ("cxl-a", "cxl-b"),
                                        (0.0, 0.0))
    cache = TieredKVCache.create(cfg, 2, 32, pol, page_t=4,
                                 slow_headroom=8)
    assert cache.k_slow.shape[2] == 8 * 4
    traces = [0]

    def decode(p, c, t):
        traces[0] += 1
        return tiered_decode_step(cfg, p, c, t)

    fn = jax.jit(decode)
    toks = jnp.asarray([3, 9], jnp.int32)
    fracs = [(0.125, 0.125), (0.25, 0.25), (0.125, 0.0), (0.25, 0.125),
             (0.0, 0.25), (0.375, 0.125), (0.125, 0.375), (0.25, 0.0),
             (0.0, 0.0), (0.375, 0.375)]
    for w in fracs:
        _, cache = fn(params, cache, toks)
        cache = cache.repartition_weights(w, telemetry=Telemetry())
    assert len(fracs) >= 10
    assert traces[0] == 1, traces[0]


def test_kv_decode_equivalence_with_headroom(key):
    """Headroom-padded caches decode bit-identically to exact-size caches
    under a mid-sequence retile."""
    from repro.models import registry
    from repro.serving.kv_cache import tiered_decode_step
    arch = registry.get("internvl2-2b").tiny()
    cfg = arch.cfg
    params = arch.module.init(cfg, key)
    pol = MemPolicy.from_slow_fraction("fast", "slow", 0.0)
    a = TieredKVCache.create(cfg, 2, 32, pol, page_t=4)
    b = TieredKVCache.create(cfg, 2, 32, pol, page_t=4, slow_headroom=4)
    toks = jnp.asarray([3, 9], jnp.int32)
    for t in range(6):
        la, a = tiered_decode_step(cfg, params, a, toks)
        lb, b = tiered_decode_step(cfg, params, b, toks)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        if t == 2:
            a = a.repartition_fraction(0.5, telemetry=Telemetry())
            b = b.repartition_fraction(0.5, telemetry=Telemetry())
            # the retile fits the held capacity: shape unchanged
            assert b.k_slow.shape[2] == 4 * 4
            assert a.k_slow.shape[2] == 4 * 4  # exact-size (legacy) grows


def test_kv_retile_coalesces_descriptors(key):
    from repro.models import registry
    arch = registry.get("internvl2-2b").tiny()
    cfg = arch.cfg
    topo = TierTopology(fast=paper_three_device_topology().fast,
                        slow=paper_three_device_topology().slows[0])
    pol = MemPolicy.from_slow_fraction("fast", "slow", 0.0)
    cache = TieredKVCache.create(cfg, 3, 64, pol, page_t=4,
                                 slow_headroom=8)
    tel = Telemetry()
    with BulkMover(topo, asynchronous=True, batch_size=16,
                   telemetry=tel) as mover:
        cache = cache.repartition_fraction(
            0.5, mover=mover, fast_tier=topo.fast.name,
            slow_tier=topo.slow.name)
        descs = mover.descriptors_submitted
    moved_pages = int(np.asarray(cache.page_tier).sum())  # 8/slot, 1 group
    assert moved_pages == 3 * 8
    # one slot-group, fast->slow, consecutive locals: ~1 run, not 24
    assert descs <= 2, descs


def test_engine_headroom_and_trace_counter(key):
    """The serving engine sizes the KV slow pool for the Caption walk and
    exposes the decode trace counter."""
    from repro.models import registry
    from repro.serving.engine import ServingEngine
    arch = registry.get("internvl2-2b").tiny()
    cfg = arch.cfg
    params = arch.module.init(cfg, key)
    topo = tpu_v5e_topology()
    ctl = CaptionController(topo, CaptionConfig(epoch_steps=2,
                                                probe_epochs=1, step=0.1),
                            initial_fraction=0.0)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=16,
                        topology=topo, page_t=4, caption=ctl)
    n_pages = 16 // 4
    assert eng.cache.slow_headroom == ctl.headroom_pages(n_pages)
    assert eng.cache.k_slow.shape[2] == ctl.headroom_pages(n_pages) * 4
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.submit([4, 5], max_new_tokens=6)
    eng.run_until_drained(max_steps=64)
    assert eng.decode_traces == 1, eng.decode_traces
