"""Donated in-place actuation, shard backends, and per-device KV pools
(ISSUE 7).

Covers the donation contract end to end: the three scatter
formulations (masked N-pass, bucketed numpy, donated jit) are bit-exact
on the same inputs — including duplicate indices under ``add`` and
aliased value buffers — the donated stable-path repartition performs
ZERO full receiving-shard copies and genuinely reuses the shard buffers
(``unsafe_buffer_pointer``), the mover bills post-cast bytes for
fused-cast descriptors, and the per-device KV pools keep storage equal
to the ``read_bytes_per_device`` accounting with drain/retile decode
bit-exactness on 3-device topologies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.donation import FULL_SHARD_COPIES, donated_update, pad_to_bucket
from repro.core.interleave import (BACKENDS, InterleavedTensor,
                                   resolve_backend, supports_memory_kinds)
from repro.core.mover import BulkMover, Descriptor, stream_executor
from repro.core.policy import MemPolicy
from repro.core.telemetry import Telemetry
from repro.core.tiers import TierTopology, paper_three_device_topology
from repro.testing import given, settings, st  # hypothesis, with fallback

FEAT = 4
PAGE_ROWS = 8


def _tensor(rng, rows=256, weights=(3, 1), headroom=4, backend="modeled"):
    x = jnp.asarray(rng.normal(size=(rows, FEAT)), jnp.float32)
    it = InterleavedTensor.from_array(
        x, MemPolicy.weighted(("fast", "slow"), weights), PAGE_ROWS,
        headroom=headroom, backend=backend)
    return it, np.asarray(x)


# -- scatter equivalence: donated == masked == bucketed -----------------------
@given(st.integers(0, 200), st.integers(1, 48))
@settings(max_examples=25, deadline=None)
def test_scatter_set_equivalence(seed, n_idx):
    """set with distinct rows: all three formulations bit-exact."""
    rng = np.random.default_rng(seed)
    it, x = _tensor(rng)
    idx = rng.choice(x.shape[0], size=min(n_idx, x.shape[0]), replace=False)
    vals = rng.normal(size=(idx.size, FEAT)).astype(np.float32)
    ref = x.copy()
    ref[idx] = vals
    masked = it._scatter_masked(jnp.asarray(idx), jnp.asarray(vals), "set")
    bucketed = it._scatter_bucketed(idx, jnp.asarray(vals), "set")
    donated = it.update_rows(idx, jnp.asarray(vals), donate=True)  # it dies
    assert np.array_equal(np.asarray(masked.to_array()), ref)
    assert np.array_equal(np.asarray(bucketed.to_array()), ref)
    assert np.array_equal(np.asarray(donated.to_array()), ref)


@given(st.integers(0, 200), st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_scatter_add_duplicates_equivalence(seed, n_idx):
    """add with DUPLICATE rows: duplicates must accumulate identically
    through the masked jax path, the numpy ufunc path, and the donated
    jit scatter."""
    rng = np.random.default_rng(seed)
    it, x = _tensor(rng)
    idx = rng.integers(0, x.shape[0], size=n_idx)  # duplicates likely
    vals = rng.normal(size=(idx.size, FEAT)).astype(np.float32)
    ref = x.copy()
    np.add.at(ref, idx, vals)
    masked = it._scatter_masked(jnp.asarray(idx), jnp.asarray(vals), "add")
    donated = it.add_rows(idx, jnp.asarray(vals), donate=True)  # it dies
    np.testing.assert_allclose(np.asarray(masked.to_array()), ref,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(donated.to_array()), ref,
                               atol=1e-5)
    # scatter-add accumulation order is formulation-dependent in float;
    # the two jax paths must still agree to rounding
    np.testing.assert_allclose(np.asarray(masked.to_array()),
                               np.asarray(donated.to_array()), atol=1e-6)


def test_scatter_aliased_values(key):
    """Values aliasing the shard's own storage (a read-modify-write
    through the gather) stay correct under donation: staging must be
    copied before the in-place write."""
    rng = np.random.default_rng(0)
    it, x = _tensor(rng)
    idx = np.arange(0, 64)
    # values gathered FROM the tensor itself (aliased source)
    vals = it.gather_rows(idx + 64)
    ref = x.copy()
    ref[idx] = x[idx + 64]
    out = it.update_rows(idx, vals, donate=True)
    assert np.array_equal(np.asarray(out.to_array()), ref)


def test_donated_update_under_jit_bucketing():
    """Varying delta sizes reuse a bounded set of jit traces (power-of-2
    buckets) and stay bit-exact."""
    rng = np.random.default_rng(1)
    part = jnp.asarray(rng.normal(size=(128, FEAT)), jnp.float32)
    ref = np.asarray(part).copy()
    for n in (1, 3, 5, 9, 17):
        rows = rng.choice(128, size=n, replace=False)
        vals = rng.normal(size=(n, FEAT)).astype(np.float32)
        ref[rows] = vals
        part = donated_update(part, rows, vals, "set")
    assert np.array_equal(np.asarray(part), ref)
    # bucket padding points one-past-the-end and is dropped
    rows_p, vals_p = pad_to_bucket(np.array([2, 5, 7]),
                                   np.ones((3, FEAT), np.float32), 128)
    assert rows_p.shape[0] == 4 and rows_p[-1] == 128


# -- donated repartition: zero copies, buffer reuse, bit-exact ----------------
def test_donated_repartition_zero_copies_and_aliasing():
    rng = np.random.default_rng(2)
    cur, x = _tensor(rng, rows=512, headroom=8)
    ptrs = [p.unsafe_buffer_pointer() for p in cur.parts]
    FULL_SHARD_COPIES.reset()
    # excursions stay within the headroom cap (slow starts at 16/64
    # pages, cap 24) so every step takes the donated stable path.  No
    # reference to any ancestor may survive the call (the donation
    # contract): a live ancestor pins its host mirror views, which
    # blocks the buffer alias.
    for f in (0.375, 0.25, 0.3125, 0.125, 0.25):
        cur = cur.repartition_fraction(f, telemetry=Telemetry(),
                                       donate=True)
    assert FULL_SHARD_COPIES.reset() == 0
    # the walk reused the original buffers in place throughout
    assert [p.unsafe_buffer_pointer() for p in cur.parts] == ptrs
    assert np.array_equal(np.asarray(cur.to_array()), x)


def test_donated_vs_cow_repartition_bit_exact():
    rng = np.random.default_rng(3)
    it, x = _tensor(rng, rows=512, headroom=8)
    cow = it.repartition_fraction(0.375, telemetry=Telemetry())
    FULL_SHARD_COPIES.reset()
    don = it.repartition_fraction(0.375, telemetry=Telemetry(),
                                  donate=True)  # it dies here
    assert FULL_SHARD_COPIES.reset() == 0
    for pc, pd in zip(cow.parts, don.parts):
        assert np.array_equal(np.asarray(pc), np.asarray(pd))
    assert np.array_equal(np.asarray(don.to_array()), x)


def test_donation_deletes_parent_buffers():
    rng = np.random.default_rng(4)
    it, _ = _tensor(rng)
    idx = np.arange(8)
    out = it.update_rows(idx, jnp.zeros((8, FEAT)), donate=True)
    # the receiving shard's parent buffer is genuinely gone
    assert any(p.is_deleted() for p in it.parts)
    assert not any(p.is_deleted() for p in out.parts)


# -- backends -----------------------------------------------------------------
def test_backend_resolution():
    assert resolve_backend("modeled") == "modeled"
    assert resolve_backend("staged") == "staged"
    # auto picks modeled where there is no pinned_host tier to place into
    expected = "memory_kind" if supports_memory_kinds() else "modeled"
    assert resolve_backend("auto") == expected
    with pytest.raises(ValueError):
        resolve_backend("nope")
    assert set(BACKENDS) == {"modeled", "staged", "memory_kind"}


def test_memory_kind_backend_raises_on_cpu():
    """An explicit memory_kind backend is never quietly modeled: on the
    CPU, whose device memory already is host memory, it raises."""
    assert jax.devices()[0].platform == "cpu"
    assert not supports_memory_kinds()
    with pytest.raises(ValueError, match="pinned_host"):
        resolve_backend("memory_kind")
    with pytest.raises(ValueError, match="pinned_host"):
        InterleavedTensor.from_array(jnp.zeros((64, FEAT)),
                                     MemPolicy.from_slow_fraction(
                                         "fast", "slow", 0.5),
                                     page_rows=8, backend="memory_kind")


def test_staged_backend_equivalence():
    """The staged backend (jax-slab descriptors, device-resident shards)
    produces the same arrays as the modeled backend across a
    repartition + scatter sequence."""
    rng = np.random.default_rng(5)
    a, x = _tensor(rng, backend="modeled")
    rng = np.random.default_rng(5)
    b, _ = _tensor(rng, backend="staged")
    assert b.backend == "staged"
    idx = np.arange(16)
    vals = jnp.ones((16, FEAT), jnp.float32)
    for f in (0.375, 0.25):
        a = a.repartition_fraction(f, telemetry=Telemetry())
        b = b.repartition_fraction(f, telemetry=Telemetry())
    a = a.update_rows(idx, vals)
    b = b.update_rows(idx, vals)
    assert np.array_equal(np.asarray(a.to_array()), np.asarray(b.to_array()))


# -- mover: post-cast byte billing + pipelined executor -----------------------
def test_mover_bills_post_cast_bytes():
    """A fused-cast descriptor's wire bytes are the POST-cast size: a
    bf16 -> fp32 migration bills 4 bytes/element, not 2 (regression for
    the compressed-staging upcast path)."""
    topo = paper_three_device_topology()
    payload = jnp.ones((64, 16), jnp.bfloat16)
    d = Descriptor(topo.fast.name, topo.slows[0].name, payload,
                   out_dtype=jnp.float32)
    assert d.nbytes == 64 * 16 * 4
    plain = Descriptor(topo.fast.name, topo.slows[0].name, payload)
    assert plain.nbytes == 64 * 16 * 2
    with BulkMover(topo, telemetry=Telemetry()) as mover:
        mover.submit([d])
        assert mover.bytes_submitted == 64 * 16 * 4


def test_stream_executor_casts_and_copies():
    """The double-buffered migration executor moves and casts payloads
    through the Pallas kernel (interpret mode on CPU)."""
    topo = paper_three_device_topology()
    src = jnp.asarray(np.random.default_rng(6).normal(size=(100, 8)),
                      jnp.float32)
    got = {}
    with BulkMover(topo, execute=stream_executor(block_rows=32),
                   telemetry=Telemetry()) as mover:
        assert mover.pipelined
        mover.submit([Descriptor(topo.fast.name, topo.slows[0].name, src,
                                 out_dtype=jnp.bfloat16,
                                 on_done=lambda r: got.setdefault("x", r))])
    out = got["x"]
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(src.astype(jnp.bfloat16)))


# -- KV cache: per-device pools -----------------------------------------------
@pytest.fixture(scope="module")
def tiny_model(key):
    from repro.models import registry
    arch = registry.get("internvl2-2b").tiny()
    return arch.cfg, arch.module.init(arch.cfg, jax.random.PRNGKey(0))


def _decode_n(cfg, params, cache, toks, n):
    from repro.serving.kv_cache import tiered_decode_step
    logits = None
    for _ in range(n):
        logits, cache = tiered_decode_step(cfg, params, cache, toks)
    return logits, cache


def test_kv_storage_matches_read_accounting(tiny_model):
    """ISSUE 7 invariant: with per-device physical pools, the bytes each
    device actually stores equal the read-accounting bytes per device
    (modulo the fast tier's >= 1-page billing floor, avoided here by
    keeping a fast page in every slot)."""
    from repro.serving.kv_cache import TieredKVCache
    cfg, params = tiny_model
    pol = MemPolicy.from_tier_fractions("fast", ("cxl-a", "cxl-b"),
                                        (0.25, 0.25))
    cache = TieredKVCache.create(cfg, 3, 32, pol, page_t=4, slow_headroom=2)
    assert len(cache.k_parts) == 3  # one physical pool pair per device
    assert cache.storage_bytes_per_device() == cache.read_bytes_per_device()
    # still equal after a weight shift (stable path)
    cache = cache.repartition_weights((0.375, 0.125),
                                      telemetry=Telemetry())
    assert cache.storage_bytes_per_device() == cache.read_bytes_per_device()


def test_kv_donated_retile_bit_exact(tiny_model):
    from repro.serving.kv_cache import TieredKVCache
    cfg, params = tiny_model
    toks = jnp.asarray([3, 9], jnp.int32)
    pol = MemPolicy.from_slow_fraction("fast", "slow", 0.0)
    a = TieredKVCache.create(cfg, 2, 32, pol, page_t=4, slow_headroom=4)
    _, a = _decode_n(cfg, params, a, toks, 4)
    cow = a.repartition_fraction(0.5, telemetry=Telemetry())
    l_cow, _ = _decode_n(cfg, params, cow, toks, 4)
    FULL_SHARD_COPIES.reset()
    slow_ptr = a.k_parts[1].unsafe_buffer_pointer()
    don = a.repartition_fraction(0.5, telemetry=Telemetry(),
                                 donate=True)  # a dies here
    assert FULL_SHARD_COPIES.reset() == 0
    assert don.k_parts[1].unsafe_buffer_pointer() == slow_ptr
    assert a.k_parts[1].is_deleted()
    l_don, _ = _decode_n(cfg, params, don, toks, 4)
    assert np.array_equal(np.asarray(l_cow), np.asarray(l_don))


def test_kv_three_device_drain_bit_exact(tiny_model):
    """Draining a slow device (donated) leaves decode bit-exact vs the
    same drain through the copy-on-write path."""
    from repro.serving.kv_cache import TieredKVCache
    cfg, params = tiny_model
    toks = jnp.asarray([3, 9], jnp.int32)
    pol = MemPolicy.from_tier_fractions("fast", ("cxl-a", "cxl-b"),
                                        (0.25, 0.25))

    def build():
        c = TieredKVCache.create(cfg, 2, 32, pol, page_t=4, slow_headroom=8)
        _, c = _decode_n(cfg, params, c, toks, 4)
        return c

    ref = build().drain_device("cxl-a")
    l_ref, _ = _decode_n(cfg, params, ref, toks, 4)
    don = build().drain_device("cxl-a", donate=True)
    assert don.weights()[0] == 0.0
    l_don, _ = _decode_n(cfg, params, don, toks, 4)
    assert np.array_equal(np.asarray(l_ref), np.asarray(l_don))
    # the drained cache keeps per-device storage == accounting
    assert don.storage_bytes_per_device()["cxl-a"] == 0


def test_kv_retile_roundtrip_bit_exact(tiny_model):
    """Rebuild path (headroom=0) round-trips through a mixed placement
    and back, matching a never-retiled cache exactly."""
    from repro.serving.kv_cache import TieredKVCache
    cfg, params = tiny_model
    toks = jnp.asarray([3, 9], jnp.int32)
    pol = MemPolicy.from_slow_fraction("fast", "slow", 0.0)
    a = TieredKVCache.create(cfg, 2, 32, pol, page_t=4)
    _, a = _decode_n(cfg, params, a, toks, 4)
    a = a.repartition_fraction(0.5, telemetry=Telemetry())
    a = a.repartition_fraction(0.0, telemetry=Telemetry())
    l_a, _ = _decode_n(cfg, params, a, toks, 4)
    b = TieredKVCache.create(cfg, 2, 32, pol, page_t=4)
    _, b = _decode_n(cfg, params, b, toks, 4)
    l_b, _ = _decode_n(cfg, params, b, toks, 4)
    assert np.array_equal(np.asarray(l_a), np.asarray(l_b))


def test_engine_donated_actuation(tiny_model):
    """The engine's Caption/pin actuations run donated by default and
    keep the full-pool copy counter at zero across a served workload."""
    from repro.core.caption import CaptionConfig, CaptionController
    from repro.core.tiers import tpu_v5e_topology
    from repro.serving.engine import ServingEngine
    cfg, params = tiny_model
    topo = tpu_v5e_topology()
    ctl = CaptionController(topo, CaptionConfig(epoch_steps=2,
                                                probe_epochs=1, step=0.1),
                            initial_fraction=0.1)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=16,
                        topology=topo, page_t=4, caption=ctl)
    assert eng.donate_kv
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.submit([4, 5], max_new_tokens=6, slo="latency")
    FULL_SHARD_COPIES.reset()
    eng.run_until_drained(max_steps=64)
    assert FULL_SHARD_COPIES.reset() == 0
    assert eng.decode_traces == 1
    assert len(eng.done) == 2
