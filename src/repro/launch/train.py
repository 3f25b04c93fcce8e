"""Mesh training driver: FetchSGD on the distributed step builders.

Without ``--debug-mesh`` the mesh is the devices present, one client per
chip (data = device count, model = 1); ``--debug-mesh`` rehearses a mesh
on forced host devices of the CPU, through the same shard_map path.
(For laptop-scale experiments use ``examples/train_federated_lm.py`` —
same optimizer, no mesh.)

Aggregation goes through the federation runtime (``repro.fed``):
``--aggregate flat`` is one pmean, ``tree`` reduces hierarchically per
mesh axis, ``async`` pipelines rounds through a staleness-discounted
buffer (straggling rounds land one-or-more rounds late), and ``dense``
is the full-gradient-psum baseline.

    python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --debug-mesh 4x2 --rounds 5 --aggregate tree
"""

import sys

from repro.xla_env import debug_mesh_devices, enable_compile_cache

debug_mesh_devices(sys.argv)  # must precede the first jax import

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.core import fetchsgd as F
from repro.data import synthetic
from repro.fed import aggregator as fed_agg
from repro.launch import mesh as mesh_lib, shapes, steps
from repro.models import transformer
from repro.optim import triangular


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--debug-mesh", default=None,
                    help="e.g. 4x2 = (data=4, model=2) host-device mesh")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--cols", type=int, default=1 << 14)
    ap.add_argument("--k", type=int, default=512)
    ap.add_argument("--aggregate", default="flat",
                    choices=("flat", "sketch", "tree", "async", "dense"))
    ap.add_argument("--sketch-impl", default="auto",
                    choices=("auto", "jnp", "pallas-interpret", "pallas"),
                    help="count-sketch kernel impl: jnp = XLA "
                         "scatter/gather, pallas = compiled Pallas hot "
                         "path (TPU-only; fails loudly elsewhere), "
                         "pallas-interpret = validation-only interpreter")
    ap.add_argument("--straggle-prob", type=float, default=0.3,
                    help="async: probability a round's cohort reports late")
    ap.add_argument("--staleness-discount", type=float, default=0.9)
    ap.add_argument("--clock", default="round", choices=("round", "event"),
                    help="async: measure staleness in rounds or in virtual "
                         "seconds from heterogeneous upload times")
    ap.add_argument("--staleness-lambda", type=float, default=0.05,
                    help="event clock: discount exp(-lambda * age_seconds)")
    ap.add_argument("--compute-median", type=float, default=1.0)
    ap.add_argument("--bw-median", type=float, default=1e6)
    ap.add_argument("--bw-sigma", type=float, default=1.0)
    ap.add_argument("--profile-stream", default="counter",
                    choices=("legacy", "counter"),
                    help="per-client profile rng: counter = vectorized "
                         "Philox (fed.profile_rng), legacy = per-client "
                         "default_rng (pre-knob checkpoint compatible)")
    obs.add_cli_flags(ap)   # --metrics PATH.jsonl / --trace / --obs-summary
    args = ap.parse_args()
    enable_compile_cache()
    tele = obs.from_args(args, run="train", arch=args.arch,
                         aggregate=args.aggregate, clock=args.clock)

    if args.debug_mesh:
        parts = [int(p) for p in args.debug_mesh.split("x")]
        mesh = mesh_lib.make_mesh(parts, ("data", "model") if len(parts) == 2
                                  else ("pod", "data", "model"))
    else:
        mesh = mesh_lib.make_production_mesh()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    shape = shapes.ShapeSpec("train", "train", args.seq_len,
                             args.global_batch)
    fs = F.FetchSGDConfig(rows=5, cols=args.cols, k=args.k, momentum=0.9,
                          impl=args.sketch_impl)
    bundle = steps.make_train_step(cfg, shape, mesh, fs,
                                   aggregate=args.aggregate)

    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = F.init_state(fs)
    ds = synthetic.ClassShardLM(vocab=cfg.vocab, seq_len=args.seq_len,
                                n_clients=256,
                                samples_per_client=args.global_batch)
    lr_fn = triangular(args.lr, args.rounds)
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name}  "
          f"d={transformer.param_count(params)/1e6:.1f}M  "
          f"aggregate={args.aggregate}")

    is_async = args.aggregate == "async"
    is_event = args.clock == "event"
    if is_event and not is_async:
        # the event clock only drives the host-side staleness buffer; a
        # silent no-op on sync policies would masquerade as a wall-clock run
        raise SystemExit("--clock event requires --aggregate async here; "
                         "for sync policies under the event clock use "
                         "repro.launch.simulate --clock event")
    if is_async:
        buf = fed_agg.AsyncBufferedAggregator(
            fs, discount=args.staleness_discount,
            staleness_lambda=args.staleness_lambda if is_event else None)
        straggle_rng = np.random.default_rng(1234)
    if is_event:
        # virtual wall-clock: each round's cohort gets a heterogeneity
        # profile; a straggled round's table arrives when its (2x slower)
        # compute + upload lands, and is discounted by exp(-lambda * age)
        from repro.fed import simtime as fed_sim
        het = fed_sim.HeterogeneityModel(fed_sim.HeterogeneityConfig(
            compute_median=args.compute_median,
            bandwidth_median=args.bw_median,
            bandwidth_sigma=args.bw_sigma,
            profile_stream=args.profile_stream), seed=1234)
        table_bytes = F.upload_bytes(fs)
        now = 0.0
    with mesh:
        for r in range(args.rounds):
            cb = ds.client_batch(r % 256)
            batch = {"tokens": jnp.asarray(cb["tokens"][:args.global_batch]),
                     "labels": jnp.asarray(cb["labels"][:args.global_batch])}
            if cfg.frontend == "vision":
                batch["patches"] = jnp.zeros(
                    (args.global_batch, cfg.n_patches, cfg.d_model))
            if cfg.is_encdec:
                batch["frames"] = jnp.zeros(
                    (args.global_batch, cfg.enc_seq, cfg.d_model))
            t0 = time.time()
            if is_async:
                t_now = now if is_event else r
                inject, inject_w, n_late, max_s = buf.drain(t_now)
                # the last round always lands on time so training never ends
                # with an unapplied cohort
                straggle = (straggle_rng.random() < args.straggle_prob
                            and r < args.rounds - 1)
                with tele.span("train.step", round=r) as sp:
                    params, opt, m = bundle.fn(
                        params, opt, batch, jnp.float32(lr_fn(r)),
                        jnp.float32(0.0 if straggle else 1.0), inject,
                        jnp.float32(inject_w))
                    sp.sync(m)
                if is_event:
                    prof = het.profile(r % 256)
                    arrive = prof.finish_time(
                        now, table_bytes,
                        compute_scale=2.0 if straggle else 1.0)
                if straggle:
                    buf.submit(m["table"], produced_round=t_now,
                               arrival_round=(arrive if is_event else r + 1))
                    # the server paces on without the straggler: advance by
                    # the nominal round duration, not the slow upload
                    if is_event:
                        now += args.compute_median
                elif is_event:
                    now = max(now, arrive)
                unit = "s" if is_event else ""
                tag = (" [straggled]" if straggle else
                       f" [late merged: {n_late}, "
                       f"staleness {max_s:.1f}{unit}]" if n_late else "")
                if is_event:
                    tag += f" t={now:.1f}s"
            else:
                with tele.span("train.step", round=r) as sp:
                    params, opt, m = bundle.fn(params, opt, batch,
                                               jnp.float32(lr_fn(r)))
                    sp.sync(m)
                tag = ""
            dt = time.time() - t0
            loss = float(m["loss"])
            if tele.enabled:
                tele.gauge("train.loss").set(loss)
                tele.counter("train.rounds").inc()
                tele.histogram("train.step_seconds").observe(dt)
                tele.emit("train_round", round=r, loss=loss, step_seconds=dt)
            print(f"round {r}: loss {loss:.4f} "
                  f"({dt:.1f}s){tag}")
    tele.close()
    assert np.isfinite(float(m["loss"]))
    print("done")


if __name__ == "__main__":
    main()
