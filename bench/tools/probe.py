"""Several runs of the benchmark in one process, for calibration on the
chip: the control's readings beside the program's (``--control``), the
knee sweep (``--rates``: one set-up, then a window at each arrival rate),
and a summary of the profiler trace's planes and lines
(``--dump-trace``).  One process keeps the chip and JAX's in-memory
compile cache across the runs.

    python3 bench/tools/probe.py --workload sc2-3b.sessions \\
        --seeds 11,12,13 --seconds 20 [--trace 1] [--control] \\
        [--rates 1.0,1.5,2.0] [--out out/probe.jsonl]

Each run prints one JSON line: the result line with its seed, or with
``--rates`` the sweep's reading at each rate.  Off a TPU it exits with
code 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _dump_trace(path: str, out) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: {len(lines)} lines", file=out)
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name}: {len(evs)} events", file=out)
            for e in evs[:4]:
                try:
                    st = {k: str(v)[:60] for k, v in dict(e.stats).items()}
                except (TypeError, ValueError):
                    st = {}
                print(f"    {e.name[:100]} start={e.start_ns} "
                      f"dur={e.duration_ns} {st}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--dump-trace", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="KEY=JSON: a configuration value for this probe")
    ap.add_argument("--log-compiles", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench.harness import cell
    from bench.harness import trace as trace_mod

    if jax.devices()[0].platform != "tpu":
        print("probe: needs a TPU", file=sys.stderr)
        return 2
    cell.enable_cache(ROOT)
    if args.log_compiles:
        jax.config.update("jax_log_compiles", True)
        mark = cell.Run.mark_setup_end

        def marked(self, t):
            print(f"probe: window opens {time.perf_counter()} {t}",
                  file=sys.stderr, flush=True)
            return mark(self, t)

        cell.Run.mark_setup_end = marked
    sets = dict(kv.split("=", 1) for kv in args.set)

    def tweak(config, mix):
        return dict(config, **{k: json.loads(v) for k, v in sets.items()}), mix
    if args.dump_trace:
        load = trace_mod.load

        def dumping(path, host_ops=False):
            with open(args.dump_trace, "a") as f:
                _dump_trace(path, f)
            return load(path, host_ops)

        trace_mod.load = dumping
    rates = [float(x) for x in args.rates.split(",") if x]
    out = open(args.out, "a") if args.out else None

    def emit(line: dict) -> None:
        print(json.dumps(line), flush=True)
        if out is not None:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        kw = dict(root=ROOT, t_start=t, control=args.control, tweak=tweak)
        if rates:
            _, run = cell.prepare(args.workload, seed, args.seconds, False,
                                  **kw)
            for line in cell.runner_of(run).sweep(run, rates):
                emit({"seed": seed, **line,
                      "since_start_s": time.perf_counter() - t})
            continue
        res = cell.execute(args.workload, seed, args.seconds,
                           bool(args.trace), **kw)
        if not args.trace:
            res.pop("breakdown", None)
        emit({"seed": seed, **res})
    return 0


if __name__ == "__main__":
    sys.exit(main())
