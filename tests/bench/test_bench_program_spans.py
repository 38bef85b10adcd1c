"""The five readers of the device's idle time under the engine's own host
spans, on hand-built traces: nested spans, clipping to the window, a
program without the spans, and a layer that did no work."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import cell, program_spans  # noqa: E402
from bench.harness.trace import Event, Trace  # noqa: E402

MS = 1e6  # ns

METRICS = {
    "prefix_attach_idle.sessions": program_spans.prefix_attach_idle,
    "prefix_promote_idle.sessions": program_spans.prefix_promote_idle,
    "caption_observe_idle.sessions": program_spans.caption_observe_idle,
    "caption_actuate_idle.sessions": program_spans.caption_actuate_idle,
    "step_host_idle.sessions": program_spans.step_host_idle,
}


def _trace(spans, window=(0, 200)) -> Trace:
    ops = [Event("op", a * MS, b * MS)
           for a, b in [(0, 5), (20, 25), (60, 70), (120, 130), (195, 210)]]
    return Trace(ops, [], [Event(n, a * MS, b * MS) for n, a, b in spans],
                 (window[0] * MS, window[1] * MS))


#: wrapper spans around program spans; a second attach span nested in the
#: first, two step spans end to end.
NESTED = [("engine.admit", 0, 100), ("engine.prefix.attach", 10, 40),
          ("engine.prefix.attach", 20, 30), ("engine.prefix.promote", 50, 90),
          ("bench.step", 100, 160), ("engine.step.inputs", 100, 110),
          ("engine.step.sample", 110, 150),
          ("engine.caption_epoch", 150, 200),
          ("engine.caption.observe", 160, 200)]


def _read(name, tr):
    return METRICS[name](types.SimpleNamespace(trace_result=tr))


@pytest.mark.parametrize("name,idle_ms", [
    ("prefix_attach_idle.sessions", 30 - 5),
    ("prefix_promote_idle.sessions", 40 - 10),
    ("caption_observe_idle.sessions", 40 - 5),
    ("caption_actuate_idle.sessions", 0),
    ("step_host_idle.sessions", 50 - 10),
])
def test_idle_under_nested_spans(name, idle_ms):
    assert _read(name, _trace(NESTED)) == pytest.approx(100 * idle_ms / 200)


@pytest.mark.parametrize("name,idle_ms", [
    ("prefix_attach_idle.sessions", 0),  # wholly before the window
    ("prefix_promote_idle.sessions", 40 - 10),
    ("caption_observe_idle.sessions", 0),  # wholly after it
    ("step_host_idle.sessions", 45 - 10),  # cut at 145
])
def test_spans_are_clipped_to_the_window(name, idle_ms):
    tr = _trace(NESTED, window=(45, 145))
    assert _read(name, tr) == pytest.approx(100 * idle_ms / 100)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_nothing_to_read_without_the_programs_step_spans(name):
    wrappers_only = [s for s in NESTED if not s[0].startswith("engine.step.")]
    assert _read(name, _trace(wrappers_only)) is None
    assert _read(name, None) is None


@pytest.mark.parametrize("name", [
    "prefix_attach_idle.sessions", "prefix_promote_idle.sessions",
    "caption_observe_idle.sessions", "caption_actuate_idle.sessions"])
def test_a_layer_that_did_no_work_reads_zero(name):
    steps_only = [("engine.step.inputs", 0, 40), ("engine.step.sample",
                                                  40, 90)]
    assert _read(name, _trace(steps_only)) == 0.0
    assert _read("step_host_idle.sessions", _trace(steps_only)) == \
        pytest.approx(100 * (90 - 20) / 200)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_file_binds_its_reader(name):
    assert cell._reader(ROOT, name) is METRICS[name]
    entry, = [m for m in cell.load_json(ROOT, "BENCHMARK.json")["per_layer"]
              if m["name"] == name]
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "%", "lower", "ttft_p50_ms")
