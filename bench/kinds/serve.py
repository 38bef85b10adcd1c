"""Serving cells: the program's ``ServingEngine`` driven by open-loop
sessions, timed on the host clock per token, and checked against the
float32 reference on a sample of finished requests.

The engine is built the way ``repro.launch.serve`` builds it.  The
benchmark hands it the weights it made from the seed and the requests the
generator made, and reads back only the served tokens and the engine's
counters."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from bench.harness import dense, traffic
from bench.harness.trace import Capture

#: engine counters read at the edges of the window.
COUNTERS = ("prefill_tokens_total", "prefill_tokens_avoided")


def _arch_config(cfg: dict):
    from repro.configs.base import ArchConfig

    names = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in cfg.items() if k in names})


def _engine(cfg, params, eng: dict):
    from repro.core.arbiter import CaptionArbiter, budgeted_config
    from repro.core.caption import CaptionConfig, CaptionController
    from repro.core.policy import MemPolicy
    from repro.core.tiers import topology_from_spec
    from repro.serving.engine import ServingEngine, kv_access_profile

    topology = topology_from_spec(eng["devices"])
    policy = MemPolicy.from_slow_fraction("fast", "slow", eng["slow_fraction"])
    caption = arbiter = None
    if eng["caption"]:
        profile = kv_access_profile(cfg, eng["max_batch"], eng["max_len"],
                                    page_t=eng["page_t"])
        caption = CaptionController.from_profile(
            profile, topology,
            CaptionConfig(epoch_steps=eng["caption_epoch_steps"]),
            initial_fraction=eng["slow_fraction"])
        arbiter = CaptionArbiter(topology, budgeted_config(topology, 0.0))
    return ServingEngine(
        cfg, params, max_batch=eng["max_batch"], max_len=eng["max_len"],
        policy=policy, topology=topology, page_t=eng["page_t"],
        caption=caption, arbiter=arbiter, prefix_pages=eng["prefix_pages"])


class _Tracker:
    """Host-clock time of every token of every request the benchmark
    submitted, read after each ``step()``."""

    def __init__(self, engine):
        self.engine = engine
        self.reqs: dict[int, object] = {}
        self.specs: dict[int, traffic.RequestSpec] = {}
        self.times: dict[int, list[float]] = {}
        self.step_tokens: list[tuple[float, int]] = []
        self._done = 0

    def submit(self, spec: traffic.RequestSpec) -> None:
        rid = self.engine.submit(spec.prompt, max_new_tokens=spec.max_new_tokens)
        self.reqs[rid] = self.engine.queue[-1]
        self.specs[rid] = spec
        self.times[rid] = []

    def step(self) -> int:
        active = self.engine.step()
        now = time.perf_counter()
        new = self.engine.done[self._done:]
        self._done = len(self.engine.done)
        made = 0
        for req in [r for r in self.engine.slots if r is not None] + new:
            seen = self.times[req.rid]
            k = len(req.generated) - len(seen)
            seen.extend([now] * k)
            made += k
        self.step_tokens.append((now, made))
        return active

    def busy(self) -> bool:
        return bool(self.engine.queue) or any(
            r is not None for r in self.engine.slots)


def _counters(engine, n_steps: int, clock) -> dict:
    out = {k: getattr(engine, k) for k in COUNTERS}
    out["decode_steps"] = n_steps
    out["compiles"] = clock.snapshot()[1]
    return out


def _instrument(engine, calls: list):
    """Host spans around the engine's admission, replay and Caption
    epochs, and a record of every decode-program call with the KV lengths
    it was given (device arrays, read after the window), for the traced
    run."""
    import jax

    def span(name, fn):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    replaying = [None]
    decode, replay = engine._decode, engine._step_slot_token

    def on_decode(p, c, t):
        slots = [replaying[0]] if replaying[0] is not None else [
            i for i, r in enumerate(engine.slots) if r is not None]
        calls.append((time.perf_counter(), c.lengths, slots))
        return decode(p, c, t)

    def on_replay(i, tok):
        replaying[0] = i
        try:
            return replay(i, tok)
        finally:
            replaying[0] = None

    engine._decode = on_decode
    engine._step_slot_token = span("engine.replay_token", on_replay)
    engine._admit = span("engine.admit", engine._admit)
    engine._caption_epoch = span("engine.caption_epoch",
                                 engine._caption_epoch)


def _sample(done: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest answer
    among them."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].generated))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def _setup(r):
    """Weights from the seed, the engine, and the set-up traffic served:
    every session's context in the prefix cache and every promotion
    shape compiled.  Returns ``(engine, tracker, calls, lead_in,
    window)``."""
    import jax

    cfg_d, mix = r.config, r.mix
    cfg = _arch_config(cfg_d)
    params = jax.block_until_ready(dense.make_params(cfg_d, r.seed))
    engine = _engine(cfg, params, mix["engine"])
    del params
    trk = _Tracker(engine)
    calls: list = []
    if r.trace:
        _instrument(engine, calls)
    contexts, warm, lead_in, window = traffic.session_schedule(
        mix, r.seed, r.seconds, cfg_d["vocab"])
    for group in (contexts, warm):
        for spec in group:
            trk.submit(spec)
        while trk.busy():
            trk.step()
    return engine, trk, calls, lead_in, window


def _serve(r, engine, trk, schedule: list, lead: float, *, t0: float,
           capture=None) -> dict:
    """Submit ``schedule`` on time from ``t0 - lead`` (open loop), step
    the engine until ``t0 + r.seconds``, then on past the close until
    every request due in the window has its first token.  Returns what
    the metrics read."""
    mix = r.mix
    t_close = t0 + r.seconds
    trace_lo = t0 + max(0.0, (r.seconds - r.trace_seconds) / 2)
    c0 = None
    n_steps = 0
    queue: list = []
    window_rids = set()
    i = 0
    while True:
        now = time.perf_counter()
        if c0 is None and now >= t0:
            c0 = _counters(engine, n_steps, r.clock)
        if now >= t_close:
            break
        if capture is not None and not capture.started and now >= trace_lo:
            capture.start()
        if capture is not None and now >= capture.lo + r.trace_seconds:
            capture.stop()
        while i < len(schedule) and t0 + schedule[i].due_s <= now:
            with _span("bench.submit", r.trace):
                trk.submit(schedule[i])
            if schedule[i].due_s >= 0:
                window_rids.add(max(trk.reqs))
            i += 1
        if trk.busy():
            with _span("bench.step", r.trace):
                if trk.step():
                    n_steps += 1
            queue.append((time.perf_counter() - t0, len(engine.queue)))
        elif i < len(schedule):
            time.sleep(max(0.0, min(t0 + schedule[i].due_s, t_close) - now))
        else:
            time.sleep(max(0.0, t_close - now))
    t_end = time.perf_counter()
    if capture is not None:
        capture.stop()
    c1 = _counters(engine, n_steps, r.clock)
    # Requests due in the window but not yet submitted are attempted too.
    while i < len(schedule) and schedule[i].due_s < r.seconds:
        trk.submit(schedule[i])
        window_rids.add(max(trk.reqs))
        i += 1
    # Past the close, run until every request due in the window has its
    # first token, so that a late first token counts with its wait.
    t_drain = time.perf_counter() + mix["drain_s"]
    while trk.busy() and time.perf_counter() < t_drain \
            and any(not trk.times[rid] for rid in window_rids):
        trk.step()

    ttft, tpot, failed = [], [], 0
    for rid in sorted(window_rids):
        ts = trk.times[rid]
        if not ts:
            failed += 1
            continue
        ttft.append(ts[0] - (t0 + trk.specs[rid].due_s))
        inside = [t for t in ts if t <= t_end]
        if len(inside) >= 2:
            tpot.append((inside[-1] - inside[0]) / (len(inside) - 1))
    return dict(
        attempted=len(window_rids), failed=failed,
        window_s=t_end - t0, ttft_s=ttft, tpot_s=tpot,
        queue_growth=_growth([q for t, q in queue if t >= 0]),
        counters=(c0, c1), cfg=r.config,
        lateness_s=_lateness(trk, window_rids, t0), t_end=t_end)


def run(r) -> None:
    """Drive one serving cell; fills ``r.data``, ``r.checks`` and the
    device readings of the :class:`~bench.harness.cell.Run` ``r``."""
    engine, trk, calls, lead_in, window = _setup(r)
    lead = r.mix["lead_in_s"]
    t0 = time.perf_counter() + lead
    r.mark_setup_end(t0)
    cap = Capture(r.trace_dir, host_ops=r.host_ops) if r.trace else None
    got = _serve(r, engine, trk, lead_in + window, lead, t0=t0, capture=cap)
    r.attempted, r.failed = got.pop("attempted"), got.pop("failed")
    r.data.update(got)
    if r.trace:
        r.data["calls"] = [(t, np.asarray(lengths), slots)
                           for t, lengths, slots in calls
                           if cap.lo <= t <= cap.hi]
        r.data["trace_window_s"] = cap.hi - cap.lo
        r.trace_result = cap.load()
    r.read_memory()
    _check(r, engine, trk)


def sweep(r, rates: list[float]):
    """The knee sweep: one set-up, then one window at each arrival rate,
    each after the last has drained.  Yields the set-up's seconds, then
    ``ttft_p90_ms`` and the queue's growth at each rate."""
    engine, trk, _, _, _ = _setup(r)
    yield {"setup_s": time.perf_counter() - r.t_start,
           "compile_s": r.clock.snapshot()[0]}
    for rate in rates:
        mix = dict(r.mix, rate_per_s=rate)
        _, _, lead_in, window = traffic.session_schedule(
            mix, r.seed, r.seconds, r.config["vocab"])
        lead = mix["lead_in_s"]
        got = _serve(r, engine, trk, lead_in + window, lead,
                     t0=time.perf_counter() + lead)
        while trk.busy():
            trk.step()
        ttft = sorted(got["ttft_s"])
        tpot = got["tpot_s"]
        yield {"rate_per_s": rate, "requests": got["attempted"],
               "failed": got["failed"],
               "ttft_p90_ms": (float(np.percentile(ttft, 90)) * 1e3
                               if ttft else None),
               "tpot_p90_ms": (float(np.percentile(tpot, 90)) * 1e3
                               if tpot else None),
               "queue_growth": got["queue_growth"],
               "compiles": got["counters"][1]["compiles"]
               - got["counters"][0]["compiles"]}


def _check(r, engine, trk) -> None:
    """The served tokens of a sample of finished requests against the
    float32 reference, once the engine's state is freed."""
    finished = [q for q in engine.done if q.rid in trk.times]
    sample = _sample(finished, r.mix["check_sample"], r.seed)
    seqs = [q.prompt + q.generated for q in sample]
    starts = [len(q.prompt) for q in sample]
    r.data["checked_requests"] = len(sample)
    r.data["distinct_tokens"] = len({t for q in sample for t in q.generated})
    engine.params = engine.cache = None
    del engine, trk, finished
    gc.collect()
    r.check_serving(seqs, starts)


def _growth(queue: list) -> float | None:
    """Mean queue length over the last third of the window less the mean
    over its first third: above 0 where the load outruns the engine."""
    n = len(queue) // 3
    if n == 0:
        return None
    return sum(queue[-n:]) / n - sum(queue[:n]) / n


def _lateness(trk: _Tracker, rids, t0: float) -> float:
    """How late the generator submitted, at most (seconds)."""
    late = [trk.reqs[rid].submitted_at - (t0 + trk.specs[rid].due_s)
            for rid in rids]
    return max(late, default=0.0)


def _span(name: str, on: bool):
    """A host span in the profiler's trace, in traced runs only."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
