#!/usr/bin/env python3
"""FetchSGD chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload gpt2s.silo1.c14 --seed 7 \\
        --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout and its
files under ``benchmarks/chip/`` (see ``harness.py``).  Runs on the chips
of this machine and fails, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.  The last line of standard output is
the result as one JSON object; the numbers the check compared, each with
its limit, are the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    spec = harness.load_cell(args.workload, bench)
    result = harness.run(spec, bench, args.seed, args.seconds,
                         bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
