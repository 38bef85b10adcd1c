"""One run of one cell: find its configuration, traffic and metrics by the
names in ``BENCHMARK.json``, drive it through the runner of its
configuration's ``kind`` (``bench/kinds/<kind>.py``), read each metric
with its own reader (``bench/metrics/<metric>.py``) and assemble the
result line."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Callable, Optional

from bench import peaks as peaks_mod
from bench.harness import dense
from bench.harness import trace as trace_mod


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a runner is given, and what it leaves for the readers."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    root: str
    clock: object
    t_start: float
    device_kind: str
    data: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace_result: Optional[trace_mod.Trace] = None
    #: also read the control's number beside each number compared (the
    #: calibration tool; the benchmark's runs never do).
    control: bool = False
    #: let a trace without a device plane stand on the host's XLA
    #: operations (the CPU tests only; on a chip such a trace is refused).
    host_ops: bool = False

    @property
    def trace_seconds(self) -> float:
        return min(float(self.mix["trace_seconds"]), self.seconds)

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.root, "bench", ".out", "trace")

    @property
    def peaks(self) -> dict:
        return peaks_mod.peaks_for(self.device_kind)

    def mark_setup_end(self, t: float) -> None:
        self.data["setup_s"] = t - self.t_start
        self.data["setup_compile_s"] = self.clock.snapshot()[0]

    def read_memory(self) -> None:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared: it passes when it is at most ``limit``."""
        self.checks.append((name, float(value), float(limit)))

    def check_serving(self, sequences, starts) -> None:
        lim = self.config["check"]
        if not sequences:
            self.check("finished_requests", 0, -1)
            return
        got = dense.reference(self.config, self.seed, sequences, starts,
                              control=self.control)
        self.data["checked_tokens"] = got["tokens"]
        if self.control:
            self.data["control"] = {"logit_gap": got["control_gap"],
                                    "witness_bf16_gap": got["witness_gap"]}
        self.check("logit_gap", got["gap"], lim["logit_gap"])


def load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _reader(root: str, name: str) -> Callable:
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(manifest: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones with
    ``trace`` off, the per-layer ones with it on."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout; every program is kept, however fast it compiled."""
    import jax

    path = os.path.join(root, "bench", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def prepare(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str, t_start: float, require_tpu: bool = True,
            device_kind: Optional[str] = None,
            tweak: Optional[Callable] = None, control: bool = False,
            manifest: Optional[dict] = None) -> tuple[dict, Run]:
    """Find the cell, its configuration and traffic by name, check the
    chips, and return ``(manifest, run)`` ready for its runner.

    ``require_tpu=False``, ``device_kind``, ``tweak(config, mix)`` and
    ``manifest`` (in place of ``BENCHMARK.json``) are for the tests, which
    run the rest of a run on the CPU at a small size."""
    import jax

    from bench.harness.clock import CompileClock

    manifest = manifest or load_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(root, entry["file"])
    mix = load_json(root, os.path.join("bench", "traffic",
                                       cell["traffic"] + ".json"))
    if tweak is not None:
        config, mix = tweak(config, mix)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"{workload} needs {cell['chips']} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    kind = device_kind or devices[0].device_kind
    peaks_mod.peaks_for(kind)  # an unknown chip fails before any work
    clock = CompileClock(jax)
    run = Run(cell, config, mix, seed, seconds, trace, root, clock, t_start,
              kind, control=control, host_ops=not require_tpu)
    return manifest, run


def runner_of(run: Run):
    """The module that drives a configuration of this ``kind``."""
    return importlib.import_module(f"bench.kinds.{run.config['kind']}")


def execute(workload: str, seed: int, seconds: float, trace: bool,
            **kw) -> dict:
    """Run one cell and return its result line as a dict; the keywords
    are :func:`prepare`'s."""
    manifest, run = prepare(workload, seed, seconds, trace, **kw)
    runner_of(run).run(run)
    return assemble(manifest, run)


def assemble(manifest: dict, run: Run) -> dict:
    import jax

    metrics = {}
    for m in metrics_of(manifest, run.cell["name"], run.trace):
        value = _reader(run.root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(v <= lim for _, v, lim in run.checks)
           and bool(run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    tr = run.trace_result
    if run.trace and tr is not None:
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                            "idle_gaps": trace_mod.idle_gaps(tr)}
    c0, c1 = run.data.get("counters", ({}, {}))
    out["info"] = {
        "setup_s": run.data.get("setup_s"),
        "setup_compile_s": run.data.get("setup_compile_s"),
        "compiles_in_window": (c1.get("compiles", 0) - c0.get("compiles", 0)
                               if c0 else None),
        "window_s": run.data.get("window_s"),
        "lateness_s": run.data.get("lateness_s"),
        "checked": run.data.get("checked_tokens"),
        "distinct_tokens": run.data.get("distinct_tokens"),
        "control": run.data.get("control"),
        "queue_growth": run.data.get("queue_growth"),
    }
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in run.checks}
    return out


def report_checks(result: dict, stream=sys.stderr) -> None:
    """Each number compared beside its limit, as the last lines."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=stream, flush=True)
