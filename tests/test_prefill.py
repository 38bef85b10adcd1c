"""Chunked prefill (``kv_cache.tiered_prefill_step`` under the engine's
jitted ``_prefill``) against the per-token replay through the decode
program (``ServingEngine._step_slot_token``), on small float32 models:
the same K/V rows in every device part, the same lengths and the same
next-step logits, under every placement, behind an attached prefix and a
copy-on-write partial page, for suffixes on either side of a chunk."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import MemPolicy
from repro.core.telemetry import Telemetry
from repro.models import registry
from repro.serving.engine import PREFILL_CHUNK, ServingEngine
from repro.serving.kv_cache import TieredKVCache, tiered_prefill_step

C = PREFILL_CHUNK
#: suffix lengths on either side of one chunk.
SUFFIXES = (1, C - 1, C, C + 1)
ATOL = 1e-5
PLACEMENTS = {
    "fast": lambda: MemPolicy.from_slow_fraction("fast", "slow", 0.0),
    "half_slow": lambda: MemPolicy.from_slow_fraction("fast", "slow", 0.5),
    "two_slow": lambda: MemPolicy.from_tier_fractions(
        "fast", ("cxl-a", "cxl-b"), (0.25, 0.25)),
}


@pytest.fixture(scope="module")
def model():
    arch = registry.get("starcoder2-3b").tiny()
    return arch.cfg, arch.module.init(arch.cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, policy, *, replay=False, prefix_pages=0, **kw):
    kw = dict(dict(max_batch=len(SUFFIXES), max_len=160, page_t=8), **kw)
    eng = ServingEngine(cfg, params, policy=policy,
                        prefix_pages=prefix_pages, **kw)
    if replay:
        def per_token(i, tokens):
            for tok in tokens:
                eng._step_slot_token(i, tok)
        eng._prefill_slot = per_token
    return eng


def _tokens(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_padded, size=n).tolist()


def _live_rows(cache, b):
    """Slot ``b``'s K/V rows below its length, per device part, keyed by
    the position each holds."""
    n = int(cache.lengths[b])
    out = []
    for k, v, pos in zip(cache.k_parts, cache.v_parts, cache.pos_parts):
        pos = np.asarray(pos[b])
        sel = np.nonzero(pos < n)[0]
        order = np.argsort(pos[sel])
        out.append((pos[sel][order], np.asarray(k[:, b])[:, sel[order]],
                    np.asarray(v[:, b])[:, sel[order]]))
    return out


def _admit_both(cfg, params, policy, prompts, **kw):
    """Both engines after admitting ``prompts`` and after one decode step
    more: ``[(cache after admission, logits of that step), ...]``."""
    out = []
    for replay in (False, True):
        eng = _engine(cfg, params, policy(), replay=replay, **kw)
        for p in prompts:
            eng.submit(p, max_new_tokens=2)
        eng._admit()
        cache = eng.cache
        eng.step()
        out.append((eng, cache, np.asarray(eng.last_logits)))
    return out


def _assert_slot_agrees(pair, b):
    (_, c_pre, l_pre), (_, c_ref, l_ref) = pair
    assert int(c_pre.lengths[b]) == int(c_ref.lengths[b])
    for (p0, k0, v0), (p1, k1, v1) in zip(_live_rows(c_pre, b),
                                          _live_rows(c_ref, b)):
        np.testing.assert_array_equal(p0, p1)
        np.testing.assert_allclose(k0, k1, atol=ATOL, rtol=0)
        np.testing.assert_allclose(v0, v1, atol=ATOL, rtol=0)
    np.testing.assert_allclose(l_pre[b], l_ref[b], atol=ATOL, rtol=0)


@pytest.fixture(scope="module", params=sorted(PLACEMENTS))
def admitted(request, model):
    """One request per suffix length (prompt = suffix + its last token),
    admitted by chunked prefill and by replay."""
    cfg, params = model
    prompts = [_tokens(cfg, s + 1, seed=s) for s in SUFFIXES]
    return _admit_both(cfg, params, PLACEMENTS[request.param], prompts)


@pytest.mark.parametrize("b", range(len(SUFFIXES)),
                         ids=[f"suffix{s}" for s in SUFFIXES])
def test_prefill_matches_replay(admitted, b):
    _assert_slot_agrees(admitted, b)
    assert int(admitted[0][1].lengths[b]) == SUFFIXES[b]


FAMILIES = {
    "rmsnorm_swiglu_qkv_bias": ("qwen2.5-32b", {}),
    "partial_rope_layernorm": ("stablelm-12b", {}),
    "tied_head": ("starcoder2-3b", {"tie_embeddings": True}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_prefill_matches_replay_across_model_families(family):
    arch_id, changes = FAMILIES[family]
    arch = registry.get(arch_id)
    cfg = dataclasses.replace(arch.tiny().cfg, **changes)
    params = arch.module.init(cfg, jax.random.PRNGKey(1))
    prompts = [_tokens(cfg, s + 1, seed=s) for s in (C + 1, 5)]
    pair = _admit_both(cfg, params, PLACEMENTS["half_slow"], prompts,
                       max_batch=2)
    for b in range(2):
        _assert_slot_agrees(pair, b)


@pytest.fixture(scope="module")
def admitted_behind_prefix(model):
    """Requests behind a 20-token prefix that an earlier request put in
    the pool: two full pages attached by reference, and the half page
    after them copied on write into each slot's own pages."""
    cfg, params = model
    shared = _tokens(cfg, 20, seed=99)
    prompts = [shared + _tokens(cfg, s + 1, seed=s) for s in SUFFIXES]
    out = []
    for replay in (False, True):
        eng = _engine(cfg, params, PLACEMENTS["half_slow"](), replay=replay,
                      prefix_pages=32)
        eng.submit(shared + _tokens(cfg, 5, seed=7), max_new_tokens=2)
        eng.run_until_drained()
        for p in prompts:
            eng.submit(p, max_new_tokens=2)
        eng._admit()
        cache = eng.cache
        eng.step()
        out.append((eng, cache, np.asarray(eng.last_logits)))
    return out


@pytest.mark.parametrize("b", range(len(SUFFIXES)),
                         ids=[f"suffix{s}" for s in SUFFIXES])
def test_prefill_matches_replay_behind_a_prefix(admitted_behind_prefix, b):
    eng = admitted_behind_prefix[0][0]
    assert eng.prefix_index.cow_copies == len(SUFFIXES)
    cache = admitted_behind_prefix[0][1]
    assert int(cache.prefix.slot_shared[b]) == 16
    _assert_slot_agrees(admitted_behind_prefix, b)
    assert int(cache.lengths[b]) == 20 + SUFFIXES[b]


def _filled_cache(cfg, policy, max_len=96, batch=3, seed=1):
    """A cache whose pools hold random rows, slots at various lengths."""
    cache = TieredKVCache.create(cfg, batch, max_len, policy, page_t=8)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(cache.k_parts))
    k = tuple(jax.random.normal(kk, p.shape, p.dtype)
              for kk, p in zip(keys[0::2], cache.k_parts))
    v = tuple(jax.random.normal(kk, p.shape, p.dtype)
              for kk, p in zip(keys[1::2], cache.v_parts))
    return dataclasses.replace(cache, k_parts=k, v_parts=v,
                               lengths=jnp.asarray([40, 17, 70][:batch],
                                                   jnp.int32))


def _prefill(cfg, params, cache, slot, n_valid, seed=3):
    block = jnp.asarray(_tokens(cfg, C, seed), jnp.int32)
    step = jax.jit(lambda c, s, t, n: tiered_prefill_step(cfg, params, c,
                                                          s, t, n))
    return step(cache, jnp.int32(slot), block, jnp.int32(n_valid))


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_other_slots_rows_and_lengths_are_untouched(model, placement):
    cfg, params = model
    cache = _filled_cache(cfg, PLACEMENTS[placement]())
    out = _prefill(cfg, params, cache, slot=1, n_valid=20)
    lengths = np.asarray(out.lengths)
    np.testing.assert_array_equal(lengths, [40, 17 + 20, 70])
    changed = 0
    for before, after in zip(cache.k_parts, out.k_parts):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(np.delete(before, 1, axis=1),
                                      np.delete(after, 1, axis=1))
        changed += int((before[:, 1] != after[:, 1]).any(
            axis=(0, 2, 3)).sum())
    for before, after in zip(cache.v_parts, out.v_parts):
        np.testing.assert_array_equal(np.delete(np.asarray(before), 1, 1),
                                      np.delete(np.asarray(after), 1, 1))
    assert changed == 20


@pytest.mark.parametrize("n_valid", [3, 10],
                         ids=["padding_past_the_end", "tokens_past_the_end"])
def test_rows_past_max_len_are_dropped(model, n_valid):
    """A chunk that starts 3 positions before the slot's end writes those
    3 rows and nothing else: no row past the end lands on a live row."""
    cfg, params = model
    max_len = 96
    cache = _filled_cache(cfg, PLACEMENTS["half_slow"](), max_len=max_len)
    cache = dataclasses.replace(cache,
                                lengths=cache.lengths.at[0].set(max_len - 3))
    out = _prefill(cfg, params, cache, slot=0, n_valid=n_valid)
    for d in range(len(cache.k_parts)):
        pos = np.asarray(cache.pos_parts[d][0])
        kept = pos < max_len - 3
        for before, after in ((cache.k_parts[d], out.k_parts[d]),
                              (cache.v_parts[d], out.v_parts[d])):
            before, after = np.asarray(before), np.asarray(after)
            np.testing.assert_array_equal(before[:, 0, kept],
                                          after[:, 0, kept])
            np.testing.assert_array_equal(np.delete(before, 0, axis=1),
                                          np.delete(after, 0, axis=1))
    written = [np.asarray(out.k_parts[d][:, 0])[
        :, (np.asarray(cache.pos_parts[d][0]) >= max_len - 3)
        & (np.asarray(cache.pos_parts[d][0]) < max_len)]
        for d in range(len(cache.k_parts))]
    assert sum(w.shape[1] for w in written) == 3


def _serve(cfg, params, prompts, *, replay, telemetry=None):
    eng = _engine(cfg, params, PLACEMENTS["half_slow"](), replay=replay,
                  prefix_pages=32, max_batch=3, max_len=256,
                  telemetry=telemetry or Telemetry())
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = eng.run_until_drained()
    return eng, {r.rid: r.generated for r in done}


def _session_prompts(cfg):
    shared = _tokens(cfg, 40, seed=11)
    return [shared + _tokens(cfg, s, seed=s) for s in (1, 2, 30, 64, 65,
                                                      130, 5)]


def test_engine_serves_the_replay_paths_tokens(model):
    cfg, params = model
    prompts = _session_prompts(cfg)
    eng, tokens = _serve(cfg, params, prompts, replay=False)
    _, reference = _serve(cfg, params, prompts, replay=True)
    assert tokens == reference
    assert eng.prefill_tokens_avoided > 0


def test_prefill_program_traces_once_across_slots_and_lengths(model):
    cfg, params = model
    eng, tokens = _serve(cfg, params, _session_prompts(cfg), replay=False)
    assert len(tokens) == 7
    assert eng.prefill_traces == 1
    assert eng.decode_traces == 1


def test_prefill_counters_are_the_chunk_arithmetic(model):
    cfg, params = model
    tel = Telemetry()
    suffixes = (1, C - 1, C, C + 1, 2 * C + 2)
    prompts = [_tokens(cfg, s + 1, seed=s) for s in suffixes]
    eng = _engine(cfg, params, PLACEMENTS["fast"](), max_batch=2,
                  max_len=256, telemetry=tel)
    for p in prompts:
        eng.submit(p, max_new_tokens=2)
    eng.run_until_drained()
    calls = sum(-(-s // C) for s in suffixes)
    assert tel.counters["prefill.calls"] == calls
    assert tel.counters["prefill.tokens"] == sum(suffixes)
    assert tel.counters["prefill.padded_tokens"] == calls * C - sum(suffixes)
