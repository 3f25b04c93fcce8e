from repro.xla_env import force_host_devices

force_host_devices(512)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

The two lines above MUST precede any other import (jax locks the device
count on first init; ``repro.xla_env`` touches only the stdlib); 512
placeholder host devices let ``jax.make_mesh`` build the production
meshes: 16x16 (one v5e pod) and 2x16x16 (two pods).

For each combination this prints ``memory_analysis()`` (proves the program
fits per-chip), ``cost_analysis()`` (FLOPs/bytes for §Roofline), and the
collective-byte breakdown parsed from the partitioned HLO.  Failures here
(sharding mismatch, OOM at compile, unsupported collective) are bugs in
the system, not in the matrix.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--aggregate dense]
  python -m repro.launch.dryrun --all --json out.json
"""

import argparse
import json
import os
import sys
import time
import traceback

import jax

from repro import configs, obs
from repro.core import fetchsgd as F
from repro.launch import analysis, mesh as mesh_lib, shapes, steps
from repro.models import transformer


def default_fetchsgd_config() -> F.FetchSGDConfig:
    # Paper-scale sketch: 5 rows x 1M cols (~20 MB upload), k=50k, rho=0.9.
    return F.FetchSGDConfig(rows=5, cols=1 << 20, k=50_000, momentum=0.9)


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            aggregate: str = "sketch", sketch_mode: str = "gathered",
            donate: bool = False, fs_cfg=None, cfg_overrides=None,
            verbose: bool = True, telemetry=None):
    tele = telemetry if telemetry is not None else obs.NOOP
    shape = shapes.SHAPES[shape_name]
    cfg = shapes.adapt_config(configs.get_config(arch), shape)
    if cfg_overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_overrides)
    # v5e pods: 16x16 = 256 chips; two pods add a leading ``pod`` axis
    if multi_pod:
        mesh = mesh_lib.make_mesh((2, 16, 16), ("pod", "data", "model"))
    else:
        mesh = mesh_lib.make_mesh((16, 16), ("data", "model"))
    mesh_name = "x".join(str(s) for s in mesh.shape.values())
    fs_cfg = fs_cfg or default_fetchsgd_config()

    t0 = time.time()
    with tele.span("dryrun.build_step", arch=arch, shape=shape_name):
        if shape.kind == "train":
            bundle = steps.make_train_step(cfg, shape, mesh, fs_cfg,
                                           aggregate=aggregate,
                                           sketch_mode=sketch_mode,
                                           donate=donate)
        elif shape.kind == "prefill":
            bundle = steps.make_prefill_step(cfg, shape, mesh, donate=donate)
        else:
            bundle = steps.make_decode_step(cfg, shape, mesh, donate=donate)
    with mesh:
        with tele.span("dryrun.lower", arch=arch, shape=shape_name):
            lowered = bundle.fn.lower(*bundle.inputs)
        with tele.span("dryrun.compile", arch=arch, shape=shape_name):
            compiled = lowered.compile()
    dt = time.time() - t0

    n_params = sum(int(x.size) for x in jax.tree.leaves(bundle.inputs[0]))
    n_active = analysis.active_params(cfg, n_params)
    mf = analysis.model_flops_estimate(cfg, shape, n_active)
    sf = analysis.step_flops_estimate(
        cfg, shape, n_active, fs_cfg=fs_cfg if shape.kind == "train" else None,
        layout_total=(bundle.layout.total if bundle.layout else None))
    roof = analysis.analyze(compiled, arch=arch, shape=shape_name,
                            mesh_name=mesh_name, n_devices=mesh.size,
                            model_flops=mf, step_flops=sf)
    ma = compiled.memory_analysis()
    if tele.enabled:
        tele.counter("dryrun.compiles").inc()
        tele.histogram("dryrun.compile_seconds").observe(dt)
        tele.emit("dryrun", arch=arch, shape=shape_name, mesh=mesh_name,
                  compile_s=dt, flops=roof.flops, hbm_bytes=roof.hbm_bytes,
                  coll_bytes=roof.coll_bytes,
                  peak_mem_bytes=roof.peak_mem_bytes,
                  bottleneck=roof.bottleneck)
    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_name} "
              f"(aggregate={aggregate if shape.kind == 'train' else '-'}) "
              f"compiled in {dt:.1f}s")
        print(f"   params: {n_params/1e9:.3f}B (active {n_active/1e9:.3f}B)")
        print(f"   memory/device: args={ma.argument_size_in_bytes/2**30:.2f}G "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}G "
              f"out={ma.output_size_in_bytes/2**30:.2f}G "
              f"alias={ma.alias_size_in_bytes/2**30:.2f}G "
              f"peak~{roof.peak_mem_bytes/2**30:.2f}G")
        print(f"   cost/device: hlo_flops={roof.flops:.3e} "
              f"step_flops/dev={roof.step_flops/mesh.size:.3e} "
              f"bytes={roof.hbm_bytes:.3e} coll_bytes={roof.coll_bytes:.3e}")
        print(f"   collectives: { {k: v for k, v in roof.coll_detail.items()} }")
        print(f"   roofline(ms): compute={roof.t_compute*1e3:.2f} "
              f"(hlo-lb {roof.t_compute_hlo*1e3:.2f}) "
              f"memory={roof.t_memory*1e3:.2f} "
              f"collective={roof.t_collective*1e3:.2f} "
              f"-> {roof.bottleneck}-bound  useful={roof.useful_ratio:.3f}")
    return roof, dt, n_params


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=list(shapes.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--aggregate", default="sketch",
                    choices=("sketch", "dense"))
    ap.add_argument("--sketch-mode", default="gathered",
                    choices=("gathered", "model_local"))
    ap.add_argument("--json", default=None, help="append results as JSON lines")
    obs.add_cli_flags(ap)   # --metrics PATH.jsonl / --trace / --obs-summary
    args = ap.parse_args()
    tele = obs.from_args(args, run="dryrun", aggregate=args.aggregate)

    combos = ([(args.arch, args.shape)] if not args.all else
              [(a, s) for a in configs.list_archs() if a != "gpt2s-federated"
               for s in shapes.SHAPES])
    done = set()
    if args.json and os.path.exists(args.json):
        with open(args.json) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                    done.add((rec["arch"], rec["shape"], rec["mesh"],
                              rec.get("aggregate", "sketch")))
                except Exception:
                    pass
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    failures, results = [], []
    for arch, shp in combos:
        if (arch, shp, mesh_name, args.aggregate) in done:
            print(f"== {arch} x {shp} x {mesh_name}: already in {args.json}")
            continue
        try:
            roof, dt, n_params = run_one(arch, shp, multi_pod=args.multi_pod,
                                         aggregate=args.aggregate,
                                         sketch_mode=args.sketch_mode,
                                         telemetry=tele)
            results.append((roof, dt, n_params))
            if args.json:
                with open(args.json, "a") as f:
                    f.write(json.dumps({
                        "arch": arch, "shape": shp, "mesh": roof.mesh,
                        "aggregate": args.aggregate,
                        "sketch_mode": args.sketch_mode,
                        "flops": roof.flops, "hbm_bytes": roof.hbm_bytes,
                        "coll_bytes": roof.coll_bytes,
                        "coll_detail": roof.coll_detail,
                        "peak_mem": roof.peak_mem_bytes,
                        "model_flops": roof.model_flops,
                        "step_flops": roof.step_flops,
                        "params": n_params, "compile_s": dt,
                        "t_compute": roof.t_compute,
                        "t_memory": roof.t_memory,
                        "t_collective": roof.t_collective,
                        "bottleneck": roof.bottleneck,
                        "useful": roof.useful_ratio}) + "\n")
        except shapes.SkipShape as e:
            print(f"== {arch} x {shp}: SKIP ({e})")
        except Exception:
            print(f"== {arch} x {shp}: FAILED")
            traceback.print_exc()
            failures.append((arch, shp))
    tele.close()
    print(f"\n{len(results)} lowered+compiled, {len(failures)} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
