"""The engine's chunked prefill program, as the device trace shows it.

``ServingEngine._admit`` writes a prompt's K/V into its slot through a
jitted program of its own (``kv_cache.tiered_prefill_step`` under
``_prefill_traced``), a fixed block of prompt tokens a call.  A program
without it, which replays the prompt through the decode program, leaves
no such call in the trace, and the reader returns ``None``."""
from __future__ import annotations

from typing import Optional

from bench.harness import trace as trace_mod

#: the name of the engine's jitted prefill program, as the device sees it.
PREFILL_PROGRAM = "_prefill_traced"


def prefill_call_ms(r) -> Optional[float]:
    """Device time of the prefill program over its calls in the traced
    window, in milliseconds."""
    tr = r.trace_result
    if tr is None:
        return None
    n, dev_s = trace_mod.module_calls(tr, PREFILL_PROGRAM)
    return dev_s / n * 1e3 if n else None
