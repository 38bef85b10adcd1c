"""Two sets of runs of one cell, each run its own process of
``bench/run.py``, the same seeds in both sets; then the spread of each
metric in each set (first to third quartile over the median).

    python3 bench/tools/sets.py --workload sc2-3b.sessions \\
        --seeds 1,2,3,4,5,6 --seconds 51 [--traced 7,8,9] \\
        --out out/sets_sessions.jsonl

``--traced`` adds one ``--trace 1`` run for each of those seeds after the
sets.  This process never imports JAX, so each child has the chip to
itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.harness.stats import spread  # noqa: E402


def _one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    lines = p.stdout.strip().splitlines()
    try:
        out = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        out = {"error": p.stderr[-2000:]}
    return {"seed": seed, "trace": trace, "rc": p.returncode, **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    with open(args.out, "a") as f:
        for k in range(args.sets):
            for seed in seeds:
                r = _one(args.workload, seed, args.seconds, 0)
                r["set"] = k
                runs.append(r)
                f.write(json.dumps(r) + "\n")
                f.flush()
        for seed in [int(s) for s in args.traced.split(",") if s]:
            r = _one(args.workload, seed, args.seconds, 1)
            f.write(json.dumps(r) + "\n")
            f.flush()
    names = sorted({m for r in runs for m in r.get("metrics", {})})
    for name in names:
        per_set = []
        for k in range(args.sets):
            vals = [r["metrics"][name]["value"] for r in runs
                    if r.get("set") == k and name in r.get("metrics", {})]
            per_set.append(spread(vals) if len(vals) >= 2 else None)
        print(json.dumps({"metric": name, "spreads": per_set}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
