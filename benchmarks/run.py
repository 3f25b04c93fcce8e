"""Benchmark harness entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Roofline terms for the full
(arch x shape) matrix come from ``python -m repro.launch.dryrun --all``
(see EXPERIMENTS.md §Dry-run / §Roofline); this harness covers the
paper-reproduction benches + kernel micro-benchmarks, all CPU-runnable.

    python -m benchmarks.run                      # everything, CSV
    python -m benchmarks.run --only kernels       # one family
    python -m benchmarks.run --json               # + BENCH_<family>.json
                                                  #   (see EXPERIMENTS.md
                                                  #    §Perf trajectory)
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from repro.xla_env import enable_compile_cache

from . import (bench_aggregation_modes, bench_compression, bench_convergence,
               bench_kernels, bench_simscale, bench_simtime,
               bench_sketch_aggregation, bench_true_topk, trajectory)

MODULES = [
    ("table1", bench_compression),
    ("kernels", bench_kernels),
    ("fig3/4/5", bench_convergence),
    ("fig10", bench_true_topk),
    ("sec3.2", bench_sketch_aggregation),
    ("fed-runtime", bench_aggregation_modes),
    ("simtime", bench_simtime),
    ("simscale", bench_simscale),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="LABEL",
                    help="run a single bench family "
                         f"({', '.join(label for label, _ in MODULES)})")
    ap.add_argument("--json", action="store_true",
                    help="persist each family's rows as BENCH_<label>.json")
    ap.add_argument("--out-dir", default="bench-out",
                    help="directory for BENCH_*.json (default: bench-out/, "
                         "the uncommitted write location — pass '.' to "
                         "refresh a committed repo-root trajectory snapshot)")
    ap.add_argument("--micro", action="store_true",
                    help="CI-sized rows: families that accept run(micro=) "
                         "sample their largest scales at smaller id counts "
                         "(annotated sampled_n=); others are unaffected")
    args = ap.parse_args(argv)
    enable_compile_cache()

    modules = MODULES
    if args.only is not None:
        modules = [(label, mod) for label, mod in MODULES
                   if label == args.only]
        if not modules:
            print(f"# FAILED: unknown bench family {args.only!r} "
                  f"(have: {[label for label, _ in MODULES]})",
                  file=sys.stderr)
            sys.exit(1)

    print("name,us_per_call,derived")
    failed = []
    for label, mod in modules:
        try:
            kwargs = {}
            if args.micro and "micro" in inspect.signature(
                    mod.run).parameters:
                kwargs["micro"] = True
            rows = []
            for row in mod.run(**kwargs):
                # (name, us, derived) or (name, us, derived, mode) — the
                # kernels family tags rows compiled/interpret/unavailable
                name, us, derived = row[:3]
                rows.append(row)
                mode = f",{row[3]}" if len(row) > 3 else ""
                print(f"{name},{us:.1f},{derived}{mode}")
                sys.stdout.flush()
            if args.json:
                path = trajectory.write(label, rows, out_dir=args.out_dir)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:
            traceback.print_exc()
            failed.append(label)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
