"""The serving engine's host spans and host-read counters: every span is
recorded on the profiler's clock with the request it serves, none takes a
name the benchmark's wrappers use, tracing leaves the served tokens as
they are, and the counters count what crosses to the host once."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.caption import CaptionConfig, CaptionController
from repro.core.policy import MemPolicy
from repro.core.telemetry import HostReads, Telemetry, host_array
from repro.core.tiers import tpu_v5e_topology
from repro.models import registry
from repro.serving.engine import ServingEngine

SPANS = ("engine.prefix.attach", "engine.prefix.promote", "engine.prefill",
         "engine.caption.observe", "engine.caption.actuate",
         "engine.step.inputs", "engine.step.model_time",
         "engine.step.sample")
#: the spans the benchmark opens around whole engine methods.
WRAPPER_SPANS = ("engine.admit", "engine.replay_token",
                 "engine.caption_epoch")


@pytest.fixture(scope="module")
def model():
    arch = registry.get("starcoder2-3b").tiny()
    return arch.cfg, arch.module.init(arch.cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, telemetry):
    topo = tpu_v5e_topology()
    ctl = CaptionController(
        topo, CaptionConfig(epoch_steps=2, probe_epochs=1, step=0.25))
    return ServingEngine(
        cfg, params, max_batch=2, max_len=64, page_t=8, topology=topo,
        policy=MemPolicy.from_slow_fraction("fast", "slow", 0.5),
        caption=ctl, prefix_pages=16, telemetry=telemetry)


def _prompts(cfg, n=4):
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_padded, size=20).tolist()
    return [shared + rng.integers(0, cfg.vocab_padded, size=6).tolist()
            for _ in range(n)]


def _serve(cfg, params, telemetry=None):
    eng = _engine(cfg, params, telemetry or Telemetry())
    for p in _prompts(cfg):
        eng.submit(p, max_new_tokens=6)
    done = eng.run_until_drained()
    return eng, {r.rid: r.generated for r in done}


def _engine_events(directory):
    """``[(name, stats)]`` of every host event named ``engine.*``."""
    path, = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith("engine.")]
    return out


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    cfg, params = model
    directory = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(directory):
        eng, tokens = _serve(cfg, params)
    return eng, tokens, _engine_events(directory)


def test_every_span_is_recorded_and_no_other(traced):
    eng, _, events = traced
    names = [n for n, _ in events]
    assert set(names) == set(SPANS)
    assert not set(names) & set(WRAPPER_SPANS)
    counters = eng.telemetry.counters
    assert names.count("engine.caption.observe") == counters[
        "caption.epochs"] == len(eng.caption_trace)
    assert names.count("engine.caption.actuate") == counters[
        "caption.actuations"] > 0


def test_prefix_spans_carry_the_request_id(traced):
    eng, tokens, events = traced
    for span in ("engine.prefix.attach", "engine.prefix.promote",
                 "engine.prefill"):
        rids = [stats.get("rid") for n, stats in events if n == span]
        assert sorted(rids) == sorted(tokens), span
    assert all(r.admitted_at is not None
               and r.submitted_at <= r.admitted_at <= r.first_token_at
               for r in eng.done)


def test_tracing_leaves_the_served_tokens_as_they_are(model, traced):
    cfg, params = model
    _, untraced = _serve(cfg, params)
    assert traced[1] == untraced


def test_promoted_pages_are_the_pages_the_index_inserted(model):
    cfg, params = model
    tel = Telemetry()
    eng = _engine(cfg, params, tel)
    insert, inserted = eng.prefix_index.insert, []

    def counted(prompt, matched):
        placed = insert(prompt, matched)
        inserted.extend(placed)
        return placed

    eng.prefix_index.insert = counted
    for p in _prompts(cfg):
        eng.submit(p, max_new_tokens=6)
    eng.run_until_drained()
    assert inserted
    assert tel.counters["prefix.promoted_pages"] == len(inserted)


def test_a_promotion_counts_each_pool_it_reads_once(model):
    cfg, params = model
    tel = Telemetry()
    eng = _engine(cfg, params, tel)
    prompt = _prompts(cfg, n=1)[0]  # 26 tokens: 3 full pages promoted
    eng.submit(prompt, max_new_tokens=6)
    pools = []
    promote = eng._promote_prefix

    def seen(i, req):
        pages = (len(req.prompt) - 1) // eng.cache.page_t
        devs = sorted(set(eng.cache._host_dev()[i][:pages].tolist()))
        pools.extend(p for d in devs
                     for p in (eng.cache.k_parts[d], eng.cache.v_parts[d]))
        promote(i, req)

    eng._promote_prefix = seen
    eng.step()
    assert len(pools) == 4  # pages on both devices, each read per page
    assert tel.counters["host_read_bytes|promote"] == sum(
        p.nbytes for p in pools)
    assert tel.counters["host_reads|promote"] == len(pools)


def test_host_reads_count_each_array_once_and_only_when_open():
    tel = Telemetry()
    reads = HostReads(tel)
    a, b = jnp.ones((4, 8), jnp.float32), jnp.zeros(3, jnp.int8)
    host_array(a, "promote")  # no meter open: not counted
    assert not tel.counters
    with reads.open():
        for _ in range(3):
            np.testing.assert_array_equal(host_array(a, "promote"), 1.0)
        host_array(b, "page_map")
        host_array(np.ones(5), "page_map")  # already on the host
    assert tel.counters["host_read_bytes|promote"] == 128
    assert tel.counters["host_reads|promote"] == 1
    assert tel.counters["host_read_bytes|page_map"] == 3
    assert tel.counters["host_reads|page_map"] == 1
    with reads.open():
        host_array(a + 1, "promote")  # a new array crosses again
    assert tel.counters["host_reads|promote"] == 2
