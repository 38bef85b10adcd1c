"""The device's idle time under the serving engine's own host spans.

The engine (``repro.serving.engine``) opens ``engine.<layer>.<part>``
spans on the profiler's clock around the host work of each layer; the
trace loader keeps them beside the benchmark's wrapper spans.  Each
reader here is the share of the traced window in which no operation ran
on the device while one of its layer's spans was open.  A reader returns
``None`` where the trace holds no ``engine.step.*`` span (a program
without the spans), and ``0.0`` where the spans are there but its layer
did no work in the window."""
from __future__ import annotations

import bisect
from typing import Callable, Optional

from bench.harness import trace as trace_mod

#: every decode step opens spans under this prefix.
STEP_PREFIX = "engine.step."


def idle_pct(tr: Optional[trace_mod.Trace],
             layer: Callable[[str], bool]) -> Optional[float]:
    """Device idle time inside the union of the spans whose name
    ``layer`` accepts, clipped to the traced window, over the window."""
    if tr is None or tr.window_s <= 0 \
            or not any(s.name.startswith(STEP_PREFIX) for s in tr.spans):
        return None
    lo, hi = tr.window
    busy = trace_mod.union(tr.ops)
    starts = [x for x, _ in busy]
    idle = 0.0
    for a, b in trace_mod.union([s for s in tr.spans if layer(s.name)]):
        a, b = max(a, lo), min(b, hi)
        idle += max(b - a, 0.0)
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while j < len(busy) and busy[j][0] < b:
            idle -= max(min(busy[j][1], b) - max(busy[j][0], a), 0.0)
            j += 1
    return 100.0 * idle * 1e-9 / tr.window_s


def prefix_attach_idle(r) -> Optional[float]:
    return idle_pct(r.trace_result, lambda n: n == "engine.prefix.attach")


def prefix_promote_idle(r) -> Optional[float]:
    return idle_pct(r.trace_result, lambda n: n == "engine.prefix.promote")


def caption_observe_idle(r) -> Optional[float]:
    return idle_pct(r.trace_result, lambda n: n == "engine.caption.observe")


def caption_actuate_idle(r) -> Optional[float]:
    return idle_pct(r.trace_result, lambda n: n == "engine.caption.actuate")


def step_host_idle(r) -> Optional[float]:
    return idle_pct(r.trace_result, lambda n: n.startswith(STEP_PREFIX))
