#!/usr/bin/env python3
"""Runs of one cell, one after another, and the spread of their metrics.

    python3 benchmarks/chip/spread.py --workload gpt2s.silo1.c14 \\
        --seeds 1,2,3,4,5,6 --sets 2 --seconds 10 --trace-seeds 7,8,9 \\
        --out spread.jsonl

Each run is ``run.py`` in a process of its own; this parent never touches
JAX, so each child has the chips to itself.  Every set runs the same
seeds.  Per metric and set: the median and the spread, the distance
between the first and third quartiles (``statistics.quantiles``, n=4) as
a share of the median; a bound is about five times the widest spread.
The first run of the call, which may compile, is left out of ``setup_s``.
"""

import argparse
import json
import re
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def one_run(workload: str, seed: int, seconds: int, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True, timeout=1200)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    found = re.findall(r"; readings (\{.*\})$", p.stderr, re.M)
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "result": result,
            "readings": json.loads(found[-1]) if found else None,
            "stderr_tail": p.stderr[-1500:]}


def spread(values: list) -> tuple[float, float]:
    """(median, (q3 - q1) / median)."""
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med


def summary(runs: list, sets: int) -> dict:
    out: dict = {}
    plain = [r for r in runs if r["trace"] == 0]
    per_set = len(plain) // sets
    for name in plain[0]["result"]["metrics"]:
        row = {}
        for s in range(sets):
            vals = [r["result"]["metrics"][name]["value"]
                    for r in plain[s * per_set:(s + 1) * per_set]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            row[f"set{s + 1}"] = spread(vals)
        row["widest_spread"] = max(v[1] for v in row.values())
        out[name] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace-seeds", type=seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    runs = []
    plan = ([(s, 0) for _ in range(args.sets) for s in args.seeds]
            + [(s, 1) for s in args.trace_seeds])
    with open(args.out, "a") as f:
        for seed, trace in plan:
            r = one_run(args.workload, seed, args.seconds, trace)
            runs.append(r)
            f.write(json.dumps(r) + "\n")
            f.flush()
            res = r["result"] or {}
            print(json.dumps({"seed": seed, "trace": trace, "rc": r["rc"],
                              "correct": res.get("correct"),
                              "metrics": res.get("metrics"),
                              "compared": res.get("compared"),
                              "readings": r["readings"]}), flush=True)
        ok = [r for r in runs if r["result"]]
        if len(ok) == len(runs) and any(r["trace"] == 0 for r in runs):
            s = summary(runs, args.sets)
            f.write(json.dumps({"summary": s}) + "\n")
            print(json.dumps({"summary": s}), flush=True)
    return 0 if all(r["result"] and r["result"]["correct"]
                    for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
