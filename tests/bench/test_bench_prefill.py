"""The reader of the prefill program's device time per call, on hand-built
traces: it counts only the prefill program's calls inside the window, the
decode reader does not count them, and a program without the prefill
program (one that replays prompts through decode) reads nothing."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import cell, prefill, readers  # noqa: E402
from bench.harness.trace import Event, Trace  # noqa: E402

MS = 1e6  # ns
NAME = "prefill_call_ms.sessions"


def _trace(modules, window=(0, 200)) -> Trace:
    mods = [Event(n, a * MS, b * MS, n) for n, a, b in modules]
    return Trace([Event("op", m.start_ns, m.end_ns) for m in mods], mods, [],
                 (window[0] * MS, window[1] * MS))


def _read(fn, tr):
    return fn(types.SimpleNamespace(trace_result=tr))


#: two prefill calls (8 and 12 ms) and three decode calls inside the
#: window, one prefill call cut by its end.
CALLS = [("jit__prefill_traced", 10, 18), ("jit__decode_traced", 20, 42),
         ("jit__prefill_traced", 50, 62), ("jit__decode_traced", 70, 92),
         ("jit__decode_traced", 100, 122), ("jit__prefill_traced", 190, 210)]


def test_prefill_call_ms_is_device_time_over_calls_in_the_window():
    assert _read(prefill.prefill_call_ms, _trace(CALLS)) == \
        pytest.approx((8 + 12) / 2)
    assert _read(readers.decode_call_ms, _trace(CALLS)) == pytest.approx(22)


def test_a_program_that_replays_prompts_reads_nothing():
    decode_only = [c for c in CALLS if "decode" in c[0]]
    assert _read(prefill.prefill_call_ms, _trace(decode_only)) is None
    assert _read(prefill.prefill_call_ms, None) is None


def test_the_metric_file_binds_its_reader():
    assert cell._reader(ROOT, NAME) is prefill.prefill_call_ms
    entry, = [m for m in cell.load_json(ROOT, "BENCHMARK.json")["per_layer"]
              if m["name"] == NAME]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"], entry["workloads"]) == (
        "ms", "lower", "device_trace", "ttft_p50_ms", ["sc2-3b.sessions"])
