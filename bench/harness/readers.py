"""The arithmetic of each metric, from what a run recorded.  Each metric's
own file under ``bench/metrics`` binds one of these to its name.  A reader
that finds nothing to read returns ``None``, and the metric is left out of
the line; it never returns 0 for a share of a roofline or of a peak."""
from __future__ import annotations

from typing import Optional

from bench import peaks as peaks_mod
from bench.harness import costs, stats
from bench.harness import trace as trace_mod

#: the name of the engine's jitted decode program, as the device sees it.
DECODE_PROGRAM = "_decode_traced"


# -- end to end ----------------------------------------------------------------

def setup_s(r) -> Optional[float]:
    return r.data.get("setup_s")


def ttft_p50_ms(r) -> Optional[float]:
    v = r.data.get("ttft_s")
    return stats.percentile(v, 50) * 1e3 if v else None


# -- engine counters -------------------------------------------------------------

def _delta(r, key: str) -> Optional[int]:
    c = r.data.get("counters")
    if not c or key not in c[0]:
        return None
    return c[1][key] - c[0][key]


def replay_share(r) -> Optional[float]:
    """Replayed prompt tokens over replayed tokens and decode steps: the
    share of the decode program's calls in the window that replay a
    prompt."""
    total = _delta(r, "prefill_tokens_total")
    avoided = _delta(r, "prefill_tokens_avoided")
    steps = _delta(r, "decode_steps")
    if total is None or avoided is None or steps is None:
        return None
    replayed = total - avoided
    if replayed + steps == 0:
        return None
    return 100.0 * replayed / (replayed + steps)


def prefix_hit_share(r) -> Optional[float]:
    """Prompt tokens served from the prefix cache over all prompt tokens
    admitted in the window."""
    total = _delta(r, "prefill_tokens_total")
    avoided = _delta(r, "prefill_tokens_avoided")
    if not total:
        return None
    return 100.0 * avoided / total


# -- device trace ------------------------------------------------------------------

def device_idle_pct(r) -> Optional[float]:
    tr = r.trace_result
    if tr is None or tr.window_s <= 0 or not tr.ops:
        return None
    return 100.0 * (1.0 - trace_mod.busy_seconds(tr) / tr.window_s)


def decode_call_ms(r) -> Optional[float]:
    tr = r.trace_result
    if tr is None:
        return None
    n, dev_s = trace_mod.module_calls(tr, DECODE_PROGRAM)
    return dev_s / n * 1e3 if n else None


def _call_costs(r):
    """``(flops, least seconds)`` of each decode-program call made in the
    traced window, from the KV lengths it was given: a replay call
    advances one slot, a decode call every active slot."""
    calls = r.data.get("calls")
    if not calls:
        return []
    shapes = costs.dense_shapes(r.data["cfg"])
    pk = r.peaks
    out = []
    for t, lengths, slots in calls:
        flops, nbytes = costs.decode_call(
            shapes, [int(lengths[i]) + 1 for i in slots])
        out.append((flops, peaks_mod.least_seconds(flops, nbytes, pk)))
    return out


def decode_roofline_pct(r) -> Optional[float]:
    """Least time per decode-program call over its device time per call,
    in the traced window."""
    tr = r.trace_result
    if tr is None:
        return None
    n, dev_s = trace_mod.module_calls(tr, DECODE_PROGRAM)
    cc = _call_costs(r)
    if not n or not cc or dev_s <= 0:
        return None
    least = sum(s for _, s in cc) / len(cc)
    return 100.0 * least / (dev_s / n)


def mfu_serving_pct(r) -> Optional[float]:
    """Model FLOPs of every token computed in the traced window (prompt
    tokens replayed and tokens generated, with attention over the live
    KV) over the traced window and the chip's peak."""
    cc = _call_costs(r)
    if not cc:
        return None
    flops = sum(f for f, _ in cc)
    return 100.0 * flops / r.data["trace_window_s"] \
        / r.peaks["bf16_flops_per_s"]
