"""The serving configuration's control on the CPU: the program serving its
weights rounded to fp8 (e4m3, one scale per column), the next precision
below the bf16 that starcoder2-3b states, while the reference keeps the
weights that the seed gives.  The run must come out not correct.

Two layers at the published widths, every finished request compared."""
from __future__ import annotations

import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests", "bench"), ROOT + "/src"]

from bench.harness import dense  # noqa: E402
from test_bench_serve_runs import _run, _small  # noqa: E402


def _two_layers(cfg: dict, mix: dict):
    """Two layers, and 64 tokens served to each set-up request, so that
    the comparison reads some hundreds of tokens however few requests the
    window finishes."""
    cfg, mix = _small(cfg, mix)
    return dict(cfg, n_layers=2), dict(
        mix, context_answer=64, engine=dict(mix["engine"], max_len=160))


def test_fp8_served_weights_are_not_correct(monkeypatch):
    make = dense.make_params

    def low(cfg, seed):
        p = make(cfg, seed)
        return jax.tree_util.tree_map(lambda q, a: q.astype(a.dtype),
                                      dense.fp8_weights(p), p)

    monkeypatch.setattr(dense, "make_params", low)
    out = _run("sc2-3b.sessions", tweak=_two_layers)
    assert out["correct"] is False, out["checks"]
