"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run needs a TPU with
as many chips as the cell asks for: without one it exits with code 2 and
prints no result.  Its last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``) and
``device``; its last lines of standard error give each number compared
beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import cell

    cell.enable_cache(ROOT)
    try:
        result = cell.execute(args.workload, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, t_start=T_START)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    print(json.dumps({"info": result["info"]}), file=sys.stderr)
    cell.report_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
