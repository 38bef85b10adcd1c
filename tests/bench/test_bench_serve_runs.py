"""Whole runs of the serving cell on the CPU, at a small size, without the
look for a chip: the run is correct, and each fault a serving cell can
have, planted under the timed path, makes it not correct.

The size keeps the published widths of starcoder2-3b (d_model 3072,
GQA 24/2, head_dim 128, d_ff 12288), so that logits have the scale the
limit was set at, and cuts depth to one layer, the vocabulary to 4096
rows, and the served dtype to float32 (bf16 matmuls are slow on a CPU)."""
from __future__ import annotations

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import cell  # noqa: E402

SEED = 2**31 + 77


def _small(cfg: dict, mix: dict):
    cfg = dict(cfg, n_layers=1, vocab=4096, param_dtype="float32")
    eng = dict(mix["engine"], max_batch=4, max_len=96, page_t=8,
               prefix_pages=64)
    mix = dict(mix, check_sample=64,  # every finished request
               context_tokens=32, context_answer=4, warm_requests=4,
               rate_per_s=4.0, lead_in_s=0.5, drain_s=20, trace_seconds=1,
               suffix=dict(mix["suffix"], median=8, min=2, max=24),
               answer=dict(mix["answer"], median=24, min=16, max=32))
    return cfg, dict(mix, engine=eng)


MANIFEST = cell.load_json(ROOT, "BENCHMARK.json")


def _run(workload: str, trace: bool = False, tweak=_small) -> dict:
    return cell.execute(workload, SEED, 2.0, trace, root=ROOT,
                        t_start=time.perf_counter(), require_tpu=False,
                        device_kind="TPU v5 lite", tweak=tweak,
                        manifest=MANIFEST)


@pytest.mark.parametrize("workload", ["sc2-3b.sessions"])
def test_serving_run_is_correct_and_reports_its_metrics(workload):
    manifest = MANIFEST
    out = _run(workload)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in cell.metrics_of(manifest, workload, False)}
    assert set(out["metrics"]) == want
    traced = _run(workload, trace=True)
    assert traced["correct"] is True, traced["checks"]
    want = {m["name"] for m in cell.metrics_of(manifest, workload, True)}
    assert set(traced["metrics"]) == want
    assert traced["device"]["busy_s"] > 0
    assert len(traced["breakdown"]["device_ops"]) <= 10


def _token_altered(monkeypatch):
    import repro.serving.engine as engine

    greedy = engine.sample_greedy

    def altered(logits):
        return (greedy(logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_greedy", altered)


def _state_unchanged(monkeypatch):
    """The decode step returns the cache it was given, lengths advanced but
    no key or value written."""
    import dataclasses

    import repro.serving.engine as engine

    step = engine.tiered_decode_step

    def frozen(cfg, params, cache, tokens):
        logits, _ = step(cfg, params, cache, tokens)
        return logits, dataclasses.replace(cache, lengths=cache.lengths + 1)

    monkeypatch.setattr(engine, "tiered_decode_step", frozen)


@pytest.mark.parametrize("plant", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("workload", ["sc2-3b.sessions"])
def test_faults_are_not_correct(workload, plant, monkeypatch):
    plant(monkeypatch)
    out = _run(workload)
    assert out["correct"] is False, out["checks"]
