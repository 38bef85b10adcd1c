"""Profiler traces: capture one window, and reduce it to device busy and
idle time, per-program device time, and idle gaps by host activity.

Read with ``jax.profiler.ProfileData``.  On a TPU the device's operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:n`` plane and
its programs those of ``XLA Modules``.  A trace without a device plane
is refused, unless the caller asks for ``host_ops``: on the CPU the XLA
operations run on host threads and carry an ``hlo_module`` stat, and
with ``host_ops`` they stand in for the device's, so that the reduction
can be tested on a trace recorded without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Optional

#: prefix of the host spans the benchmark records around its calls.
SPAN_PREFIXES = ("bench.", "engine.")
#: the span that marks the traced window itself.
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    module: str = ""


@dataclasses.dataclass
class Trace:
    """The events of one traced window, on one clock."""

    ops: list[Event]       # device operations
    modules: list[Event]   # device program executions
    spans: list[Event]     # the benchmark's host spans
    window: tuple[float, float]  # traced window, ns
    n_devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _short(hlo: str) -> str:
    """``%fusion.12 = bf16[16,3072]{...} fusion(...)`` -> the op's name,
    its result shape and its kind: ``%fusion.12 bf16[16,3072] fusion``."""
    name, _, rest = hlo.partition(" = ")
    m = re.search(r"[}\)\]]\s+([a-z][\w.\-]*)\(", rest)
    if not m:
        return hlo[:120]
    shapes = re.findall(r"[a-z]\w*\[[\d,]*\]", rest[:m.start(1)])
    return f"{name} {','.join(shapes)} {m.group(1)}"[:120]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


class NoDevicePlane(ValueError):
    """The trace holds no ``/device:TPU:n`` plane."""


def load(path: str, host_ops: bool = False) -> Trace:
    """Read an ``.xplane.pb`` file into a :class:`Trace`.  Raises
    :class:`NoDevicePlane` where it has no device plane, unless
    ``host_ops`` lets the host's XLA operations stand in."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    dev_planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not dev_planes and not host_ops:
        raise NoDevicePlane(f"no /device:TPU plane in {path}; planes: "
                            f"{[p.name for p in pd.planes]}")
    for plane in dev_planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [Event(_short(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                        for e in line.events]
            elif line.name == "XLA Modules":
                modules += [Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
    host_ops_by_module: dict[str, list[Event]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    spans.append(Event(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns))
                elif not dev_planes:
                    mod = _stats(e).get("hlo_module")
                    if mod:
                        ev = Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, str(mod))
                        ops.append(ev)
                        host_ops_by_module.setdefault(str(mod), []).append(ev)
    if not dev_planes:
        # One "execution" per contiguous run of a module's operations.
        for mod, evs in host_ops_by_module.items():
            for a, b in union(evs):
                modules.append(Event(mod, a, b, mod))
    marks = [s for s in spans if s.name == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0].start_ns, marks[0].end_ns
    else:
        everything = ops + spans
        lo = min((e.start_ns for e in everything), default=0.0)
        hi = max((e.end_ns for e in everything), default=0.0)
    spans = [s for s in spans if s.name != WINDOW_SPAN]
    return Trace(ops, modules, spans, (lo, hi), max(len(dev_planes), 1))


def union(events) -> list[tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some operation ran on the device, averaged over
    the devices traced."""
    busy = _clip(union(tr.ops), *tr.window)
    return sum(b - a for a, b in busy) * 1e-9 / tr.n_devices


def module_calls(tr: Trace, needle: str) -> tuple[int, float]:
    """``(calls, device seconds)`` of the programs whose name contains
    ``needle``, inside the traced window."""
    lo, hi = tr.window
    hits = [m for m in tr.modules if needle in m.name
            and m.start_ns >= lo and m.end_ns <= hi]
    return len(hits), sum(m.end_ns - m.start_ns for m in hits) * 1e-9


def busy_within(tr: Trace, span_name: str) -> tuple[int, float]:
    """``(spans, device seconds)``: the device's busy time that falls
    inside the host spans named ``span_name``."""
    spans = union([s for s in tr.spans if s.name == span_name])
    busy = union(tr.ops)
    total = 0.0
    for a, b in spans:
        total += sum(y - x for x, y in _clip(busy, a, b))
    n = sum(1 for s in tr.spans if s.name == span_name)
    return n, total * 1e-9


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` device operations that took most time: ``[name, s]``."""
    by: dict[str, float] = {}
    for e in tr.ops:
        by[e.name] = by.get(e.name, 0.0) + (e.end_ns - e.start_ns) * 1e-9
    return [[n, s] for n, s in sorted(by.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """Idle time of the device inside the window, summed by what the host
    was doing: the innermost benchmark span that covers the middle of
    each gap, or ``"no span"``.  The ``k`` largest: ``[name, s]``."""
    busy = _clip(union(tr.ops), *tr.window)
    edges = [tr.window[0]] + [x for ab in busy for x in ab] + [tr.window[1]]
    by: dict[str, float] = {}
    spans = sorted(tr.spans, key=lambda s: s.end_ns - s.start_ns)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        name = next((s.name for s in spans
                     if s.start_ns <= mid <= s.end_ns), "no span")
        by[name] = by.get(name, 0.0) + (b - a) * 1e-9
    return [[n, s] for n, s in sorted(by.items(), key=lambda x: -x[1])[:k]]


class Capture:
    """One traced window: :meth:`start` and :meth:`stop` inside the
    measured loop, :meth:`load` after it, which reads the trace, deletes
    the file and returns the :class:`Trace`."""

    def __init__(self, directory: str, host_ops: bool = False):
        self.directory = directory
        self.host_ops = host_ops
        self.started = self.stopped = False
        self.lo = self.hi = 0.0

    def start(self) -> None:
        import jax

        os.makedirs(self.directory, exist_ok=True)
        # No Python tracer: it records every Python call, which slows the
        # host several times over and would show as device idle time.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._mark.__enter__()
        self.started, self.lo = True, time.perf_counter()

    def stop(self) -> None:
        import jax

        if self.started and not self.stopped:
            self.hi = time.perf_counter()
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.stopped = True

    def load(self) -> Optional[Trace]:
        self.stop()
        files = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        try:
            return (load(files[-1], self.host_ops)
                    if files and self.started else None)
        finally:
            for f in files:
                os.remove(f)
