#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the control, the faults.

    python3 benchmarks/chip/study.py --workload gpt2s.silo1.c14 \\
        --seeds 11,12,13 --control-seeds 11,12,13 --fault-seeds 11,12,13

In one process, on the chips of this machine, with one compiled step:

* ``program``: the program's checked rounds against the reference, on
  each of ``--seeds``: the lower readings of each compared number;
* ``control``: the reference with every matmul operand rounded to int8
  (``reference.int8``), put in the program's place: the step below the
  configuration's bfloat16 matmuls;
* ``sketch_control`` (``--sketch-control-seeds``): the reference with the
  values its encode and estimate contract rounded to bfloat16
  (``reference.bf16``): the sketch in one MXU pass, below the
  configuration's ``highest``;
* ``half_batch``, ``sign_flip``, ``moved_ids`` (``--fault-seeds``): the
  program with each client's second half of rows replaced by its first,
  with its update applied with the wrong sign, and one element further on;
* ``program_vs_bf16_reference`` (``--bf16-reference-seeds``): the program
  against a reference whose matmul operands are rounded to bfloat16, as
  the program's are: how near a reference at the program's own precision
  comes;
* ``no_exchange`` (cells on several chips): the program with the merge
  left out, built as a second step.

A state left unchanged reads 1 on ``change_gap`` and ``change_diff`` by
construction.  Each reading is one JSON line on standard output, with
``correct`` as the cell's limits judge it; ``--out`` appends them to a
file as well.  No window is measured: these readings need none.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def emit(out, **row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--sketch-control-seeds", type=seeds, default=[])
    ap.add_argument("--bf16-reference-seeds", type=seeds, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    spec = harness.load_cell(args.workload, bench)
    cell, cfg, model, tr = (spec[k] for k in ("cell", "cfg", "model", "tr"))
    harness.devices(cell["chips"], require_chip=True)
    harness.enable_cache()
    vocab = cfg["model"]["vocab"]
    prog = harness.Program(model, cfg, tr, cell["chips"], None)
    refs: dict = {}

    def ref_for(seed, check):
        if seed not in refs:
            refs[seed] = harness.reference_readings(model, cfg, tr, seed,
                                                    check)
        return refs[seed]

    def program(seed, fault=None):
        check = traffic.rounds(tr, vocab, seed)[:tr["check_rounds"]]
        prog.fault = fault
        prog.start(seed)
        got = harness.checked_rounds(prog, check)
        prog.params = prog.opt = None
        return got, check

    def reading(kind, seed, got, ref, **extra):
        numbers = harness.compare(got, ref)
        ok, _ = harness.judge(numbers, spec["limits"])
        emit(args.out, kind=kind, cell=cell["name"], seed=seed, correct=ok,
             **numbers, **extra)

    for seed in args.seeds:
        t0 = time.perf_counter()
        got, check = program(seed)
        ref = ref_for(seed, check)
        reading("program", seed, got, ref, losses=got["losses"],
                ref_losses=ref["losses"], seconds=time.perf_counter() - t0)
    for seed in args.fault_seeds:
        for fault in ("half_batch", *harness.PLANTED):
            got, check = program(seed, fault)
            reading(fault, seed, got, ref_for(seed, check))
    for kind, seed_list, kw in (
            ("control", args.control_seeds, {"q": reference.int8}),
            ("sketch_control", args.sketch_control_seeds,
             {"sketch_q": reference.bf16})):
        for seed in seed_list:
            check = traffic.rounds(tr, vocab, seed)[:tr["check_rounds"]]
            ctl = harness.reference_readings(model, cfg, tr, seed, check,
                                             **kw)
            reading(kind, seed, ctl, ref_for(seed, check))
    for seed in args.bf16_reference_seeds:
        got, check = program(seed)
        ref = harness.reference_readings(model, cfg, tr, seed, check,
                                         q=reference.bf16)
        reading("program_vs_bf16_reference", seed, got, ref)
    if cell["chips"] > 1 and args.fault_seeds:
        prog.free()
        prog = harness.Program(model, cfg, tr, cell["chips"], "no_exchange")
        for seed in args.fault_seeds:
            got, check = program(seed)
            reading("no_exchange", seed, got, ref_for(seed, check))
    return 0


if __name__ == "__main__":
    sys.exit(main())
