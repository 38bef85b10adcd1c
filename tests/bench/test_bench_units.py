"""The benchmark's yardstick on the CPU: traffic generation, metric
arithmetic, trace reduction, the peaks table, the references, the shape
of the result line, and the command's refusal to run off a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import peaks  # noqa: E402
from bench.harness import (cell, costs, dense, readers, stats,  # noqa: E402
                           traffic)
from bench.harness import trace as trace_mod  # noqa: E402

MANIFEST = cell.load_json(ROOT, "BENCHMARK.json")
BIG_SEED = 2**31 + 12345


def _mix(name: str) -> dict:
    return cell.load_json(ROOT, f"bench/traffic/{name}.json")


def _tiny_dense(**kw) -> dict:
    cfg = cell.load_json(ROOT, "bench/configs/starcoder2-3b.json")
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab=256, **kw)
    return cfg


# -- traffic -------------------------------------------------------------------

def _lengths(reqs):
    return sorted((len(r.prompt), r.max_new_tokens) for r in reqs)


def test_sessions_are_deterministic_and_seeds_share_sizes():
    mix = _mix("sessions")
    a = traffic.session_schedule(mix, BIG_SEED, 20.0, 49152)
    b = traffic.session_schedule(mix, BIG_SEED, 20.0, 49152)
    c = traffic.session_schedule(mix, 7, 20.0, 49152)
    for x, y in zip(a, b):
        assert [(r.due_s, r.prompt, r.max_new_tokens) for r in x] == \
               [(r.due_s, r.prompt, r.max_new_tokens) for r in y]
    window_a, window_c = a[3], c[3]
    assert [(r.due_s, len(r.prompt), r.max_new_tokens, r.session)
            for r in window_a] == [(r.due_s, len(r.prompt), r.max_new_tokens,
                                    r.session) for r in window_c]
    assert [r.prompt for r in window_a] != [r.prompt for r in window_c]
    assert all(0 <= r.due_s < 20.0 for r in window_a)
    assert all(-mix["lead_in_s"] <= r.due_s < 0 for r in a[2])
    # every request of a session starts with that session's context
    ctx = {r.session: r.prompt[:mix["context_tokens"]] for r in a[0]}
    assert len(ctx) == mix["sessions"]
    assert all(r.prompt[:mix["context_tokens"]] == ctx[r.session]
               for r in window_a + a[1])
    n = len(window_a)
    assert n == round(mix["rate_per_s"] * 20.0)
    for r in window_a:
        suffix = len(r.prompt) - mix["context_tokens"]
        assert mix["suffix"]["min"] <= suffix <= mix["suffix"]["max"]
        assert mix["answer"]["min"] <= r.max_new_tokens <= mix["answer"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= mix["engine"]["max_len"]


def test_set_up_meets_every_number_of_promoted_pages():
    """The warm requests bring every number of full suffix pages that a
    request of the mix can bring, so that no promotion shape compiles
    inside the window."""
    mix = _mix("sessions")
    _, warm, lead_in, window = traffic.session_schedule(mix, 5, 51.0, 49152)
    pt = mix["engine"]["page_t"]

    def pages(r):
        return (len(r.prompt) - 1) // pt - mix["context_tokens"] // pt

    assert {pages(r) for r in lead_in + window} <= {pages(r) for r in warm}


# -- metric arithmetic ------------------------------------------------------------

def _run(**data):
    return types.SimpleNamespace(data=data, trace_result=None,
                                 peaks=peaks.peaks_for("TPU v5 lite"))


def test_latency_and_rate_metrics():
    r = _run(ttft_s=[0.1 * i for i in range(1, 11)], window_s=2.0,
             setup_s=5.0)
    assert readers.ttft_p50_ms(r) == pytest.approx(550.0)
    assert readers.setup_s(r) == 5.0
    assert readers.ttft_p50_ms(_run(ttft_s=[])) is None


def test_counter_metrics():
    c0 = {"prefill_tokens_total": 100, "prefill_tokens_avoided": 60,
          "decode_steps": 10}
    c1 = {"prefill_tokens_total": 500, "prefill_tokens_avoided": 380,
          "decode_steps": 90}
    r = _run(counters=(c0, c1))
    # replayed 400 - 320 = 80 of 80 + 80 calls; 320 of 400 avoided
    assert readers.replay_share(r) == pytest.approx(50.0)
    assert readers.prefix_hit_share(r) == pytest.approx(80.0)
    assert readers.replay_share(_run()) is None


def test_spread_and_percentile():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_dense_costs_match_the_published_parameter_count():
    cfg = cell.load_json(ROOT, "bench/configs/starcoder2-3b.json")
    s = costs.dense_shapes(cfg)
    assert s["params"] == 3_029_818_368  # the head tied to the embedding
    assert costs.dense_shapes(dict(cfg, tie_embeddings=False))["params"] \
        == 3_180_813_312
    assert s["kv_bytes_per_pos"] == 30_720
    flops, nbytes = costs.decode_call(s, [100, 200])
    assert flops == 2.0 * s["matmul_params"] * 2 + s["attn_flops_per_pos"] * 300
    assert nbytes == s["weight_bytes"] + 2 * s["embed_row_bytes"] \
        + 302 * s["kv_bytes_per_pos"]


def test_roofline_readers():
    cfg = _tiny_dense()
    ev = [trace_mod.Event("op", 0.0, 4e6), trace_mod.Event("op", 6e6, 8e6)]
    mods = [trace_mod.Event("jit__decode_traced(1)", 0.0, 4e6, "m"),
            trace_mod.Event("jit__decode_traced(2)", 6e6, 8e6, "m")]
    tr = trace_mod.Trace(ev, mods, [], (0.0, 10e6))
    calls = [(0.0, np.array([3, 5]), [0, 1]), (1.0, np.array([3, 5]), [1])]
    r = _run(cfg=cfg, calls=calls, trace_window_s=0.01)
    r.trace_result = tr
    assert readers.device_idle_pct(r) == pytest.approx(40.0)
    assert readers.decode_call_ms(r) == pytest.approx(3.0)
    pk = r.peaks
    s = costs.dense_shapes(cfg)
    least = [peaks.least_seconds(*costs.decode_call(s, [4, 6]), pk),
             peaks.least_seconds(*costs.decode_call(s, [6]), pk)]
    assert readers.decode_roofline_pct(r) == pytest.approx(
        100 * (sum(least) / 2) / 3e-3)
    flops = costs.decode_call(s, [4, 6])[0] + costs.decode_call(s, [6])[0]
    assert readers.mfu_serving_pct(r) == pytest.approx(
        100 * flops / 0.01 / pk["bf16_flops_per_s"])
    r.trace_result = None
    assert readers.device_idle_pct(r) is None
    assert readers.decode_roofline_pct(r) is None
    assert readers.mfu_serving_pct(_run(cfg=cfg)) is None


def test_peaks_refuse_an_unknown_chip():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert peaks.least_seconds(197e12, 1.0, pk) == 1.0
    assert peaks.least_seconds(1.0, 819e9, pk) == 1.0


# -- trace reduction ------------------------------------------------------------------

CPU_TRACE = os.path.join(ROOT, "bench", "data", "cpu_trace.xplane.pb")


def test_trace_reduction_on_a_recorded_cpu_trace():
    tr = trace_mod.load(CPU_TRACE, host_ops=True)
    assert tr.ops and tr.window_s > 0
    busy = trace_mod.busy_seconds(tr)
    assert 0 < busy < tr.window_s
    n, dev_s = trace_mod.module_calls(tr, "step_f")
    assert n >= 3 and dev_s > 0
    spans, inside = trace_mod.busy_within(tr, "bench.step")
    assert spans == 3 and inside == pytest.approx(dev_s)
    gaps = dict(trace_mod.idle_gaps(tr))
    assert sum(gaps.values()) == pytest.approx(tr.window_s - busy)
    assert set(gaps) <= {"no span", "bench.step", "bench.other"}
    assert trace_mod.top_ops(tr, 2)[0][1] >= trace_mod.top_ops(tr, 2)[1][1]


def test_a_trace_without_a_device_plane_is_refused():
    """Host operations never stand in for the device's unless asked."""
    with pytest.raises(trace_mod.NoDevicePlane):
        trace_mod.load(CPU_TRACE)


def test_union_and_op_names():
    ev = [trace_mod.Event("a", 0, 2), trace_mod.Event("b", 1, 3),
          trace_mod.Event("c", 5, 6)]
    assert trace_mod.union(ev) == [(0, 3), (5, 6)]
    assert trace_mod._short(
        "%copy.7 = bf16[30,16,320]{2,1,0:T(2,128)} copy(bf16[30,16,320]") \
        == "%copy.7 bf16[30,16,320] copy"


# -- references --------------------------------------------------------------------------

def test_weights_are_the_same_whole_and_layer_by_layer():
    cfg = _tiny_dense()
    p = dense.make_params(cfg, BIG_SEED)
    key = dense.base_key(BIG_SEED)
    one = dense._layer(cfg, key, jnp.uint32(1), jnp.bfloat16)
    got = jax.tree_util.tree_map(lambda x: x[1], p["layers"])
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), got, one))
    assert p["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    assert p["layers"]["ln1"]["scale"].dtype == jnp.float32


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
def test_reference_matches_the_models_own_forward_in_float32(tie):
    """The reference is written apart from the program; at float32 the two
    must agree on the same weights."""
    from repro.configs.base import ArchConfig
    from repro.models import transformer

    from bench.kinds.serve import _arch_config

    cfg = _tiny_dense(param_dtype="float32", tie_embeddings=tie)
    arch: ArchConfig = _arch_config(cfg)
    params = dense.make_params(cfg, 3)
    assert ("lm_head" in params) is not tie
    greedy = np.random.default_rng(0).integers(0, 256, size=6).tolist()
    with jax.default_matmul_precision("highest"):
        for _ in range(6):
            logits = transformer.forward(arch, params, jnp.asarray([greedy]))
            greedy.append(int(jnp.argmax(logits[0, -1])))
    got = dense.reference(cfg, 3, [greedy], [6])
    assert got["tokens"] == 6 and got["gap"] < 1e-4
    wrong = greedy[:6] + [(t + 1) % 256 for t in greedy[6:]]
    assert dense.reference(cfg, 3, [wrong], [6])["gap"] > 1e-3


def test_reference_in_blocks_agrees_with_one_block():
    """Sequences of different lengths give the same gaps whether the
    reference runs them in one block or one to a block."""
    cfg = _tiny_dense(param_dtype="float32")
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in (9, 14, 5)]
    starts = [4, 10, 3]
    whole = dense.reference(cfg, 3, seqs, starts, control=True)
    one = dense.reference(cfg, 3, seqs, starts, control=True, block_bytes=1)
    assert whole["tokens"] == one["tokens"] == 5 + 4 + 2
    for k in ("gap", "control_gap"):
        assert one[k] == pytest.approx(whole[k], rel=1e-5, abs=1e-6)


# -- the result line and the command ---------------------------------------------------------

def test_result_line_schema():
    run = cell.Run(MANIFEST["workloads"][0], {}, {}, 1, 1.0, False, ROOT,
                   None, 0.0, "TPU v5 lite")
    run.data.update(ttft_s=[0.5, 0.7], tpot_s=[0.1, 0.2], setup_s=3.0,
                    window_s=1.0)
    run.attempted = 2
    run.check("logit_gap", 0.1, 1.0)
    out = cell.assemble(MANIFEST, run)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ttft_p50_ms", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    run.check("other", 2.0, 1.0)
    assert cell.assemble(MANIFEST, run)["correct"] is False
    json.dumps(out)


def test_manifest_names_a_reader_and_data_for_everything():
    manifest = MANIFEST
    names = set()
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            assert os.path.exists(os.path.join(
                ROOT, "bench", "metrics", m["name"] + ".py")), m["name"]
            names.add(m["name"])
    for c in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", c["traffic"] + ".json"))
        e2e = cell.metrics_of(manifest, c["name"], False)
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert cell.metrics_of(manifest, c["name"], True)
    for c in manifest["configs"]:
        assert cell.load_json(ROOT, c["file"])["name"] == c["name"]


def test_command_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "sc2-3b.sessions", "--seed", str(BIG_SEED),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
