"""Single-host federated simulation — the engine behind the paper's figures.

Runs any of the paper's methods (FetchSGD, local top-k, FedAvg,
uncompressed, true top-k) over the synthetic non-i.i.d. federated datasets
and reports loss history + upload/download compression.  This is the
CPU-scale counterpart of the mesh train step in ``steps.py`` — same
optimizer code (repro.core / repro.baselines), different scale.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import fed, obs
from repro.baselines import fedavg, local_topk, uncompressed
from repro.core import compression, fetchsgd as F
from repro.core import layout as layout_lib
from repro.core import topk as TK
from repro.data import federated, synthetic
from repro.models import transformer
from repro.optim import triangular
from repro.xla_env import enable_compile_cache


@dataclasses.dataclass
class SimResult:
    method: str
    losses: list
    traffic: dict
    extras: dict


# one canonical jitted (params, batch) -> (loss, grads); the federation
# runtime owns it so the orchestrator default and this module never diverge
_grad_fn = fed.orchestrator.make_grad_fn


def _client_batches(dataset, clients, pad_to):
    return [dataset.client_batch(int(c)) for c in clients]


def _to_jnp(b):
    return {k: jnp.asarray(v) for k, v in b.items()
            if k in ("tokens", "labels")}


def micro_cfg(name: str = "gpt2s-federated"):
    """Micro variant for CPU-speed convergence runs (tests/benches):
    2 layers, d=64, vocab=128 — compiles in seconds, converges in ~10
    rounds on the class-shard task."""
    from repro import configs
    from repro.models.config import reduce_for_smoke
    return reduce_for_smoke(
        configs.get_config(name), name=name + "-micro", d_model=64,
        n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128, vocab=128,
        attn_chunk=32, loss_chunk=32)


def micro_dataset(cfg, seed: int = 0, n_clients: int = 64):
    from repro.data import synthetic
    return synthetic.ClassShardLM(vocab=cfg.vocab, seq_len=16, n_classes=4,
                                  n_clients=n_clients, samples_per_client=4,
                                  seed=seed)


def run_simulation(cfg, *, method: str = "fetchsgd", rounds: int = 30,
                   clients_per_round: int = 4, peak_lr: float = 0.2,
                   fs_cfg: F.FetchSGDConfig | None = None,
                   topk_cfg: local_topk.LocalTopKConfig | None = None,
                   fa_cfg: fedavg.FedAvgConfig | None = None,
                   dataset=None, seed: int = 0,
                   eval_every: int = 1, aggregate: str = "flat",
                   fed_cfg: fed.FederationConfig | None = None,
                   telemetry=None, health_every: int = 1,
                   sketch_impl: str = "auto") -> SimResult:
    dataset = dataset or synthetic.ClassShardLM(
        vocab=cfg.vocab, seq_len=32, n_classes=8, n_clients=256,
        samples_per_client=4, seed=seed)
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    lay = layout_lib.build_layout(params)
    d = lay.total
    gf = _grad_fn(cfg)
    lr_fn = triangular(peak_lr, rounds)
    meter = compression.TrafficMeter(d=d)
    losses, extras = [], {}

    if method == "fetchsgd":
        # the federation runtime owns the round loop: cohort sampling,
        # dropout/stragglers, and the pluggable aggregation policy
        # (flat = the old inline mean; tree/async exercise linearity).
        fs_cfg = fs_cfg or F.FetchSGDConfig(rows=5, cols=1 << 14, k=512,
                                            momentum=0.9, impl=sketch_impl)
        fed_cfg = fed_cfg or fed.FederationConfig(
            rounds=rounds, clients_per_round=clients_per_round,
            aggregate=aggregate, seed=seed)
        if fed_cfg.rounds != rounds:   # fed_cfg wins; keep the lr schedule
            lr_fn = triangular(peak_lr, fed_cfg.rounds)   # aligned with it
        res = fed.Orchestrator(cfg, fs_cfg, fed_cfg, dataset,
                               params=params, lr_fn=lr_fn,
                               grad_fn=gf, telemetry=telemetry,
                               health_every=health_every).run()
        extras["fs_cfg"] = fs_cfg
        extras["fed_records"] = res.records
        extras["pending_late"] = res.extras["pending_late"]
        extras["in_flight"] = res.extras["in_flight"]
        extras["t_virtual"] = res.extras["t_virtual"]
        return SimResult(method=method,
                         losses=[l if l is not None else float("nan")
                                 for l in res.losses],
                         traffic=res.traffic, extras=extras)

    elif method == "true_topk":
        # Appendix A.3 Fig. 10: full gradients to the server; server keeps a
        # dense error accumulator and applies only the top-k each round.
        fs_cfg = fs_cfg or F.FetchSGDConfig(k=512, momentum=0.9)
        err = jax.tree.map(jnp.zeros_like, params)
        mom = jax.tree.map(jnp.zeros_like, params)
        for r in range(rounds):
            clients = federated.sample_clients(dataset.n_clients,
                                               clients_per_round, r, seed)
            gs, loss_acc = None, 0.0
            for cb in _client_batches(dataset, clients, None):
                loss, grads = gf(params, _to_jnp(cb))
                gs = grads if gs is None else jax.tree.map(
                    jnp.add, gs, grads)
                loss_acc += float(loss)
            gs = jax.tree.map(lambda x: x / clients_per_round, gs)
            mom, err, params = _true_topk_jit(lay, fs_cfg)(
                mom, err, params, gs, lr_fn(r))
            losses.append(loss_acc / clients_per_round)
            meter.record(compression.RoundTraffic(upload=d * 4,
                                                  download=fs_cfg.k * 8),
                         clients_per_round)

    elif method == "local_topk":
        topk_cfg = topk_cfg or local_topk.LocalTopKConfig(k=512)
        st = local_topk.init_server_state(params, topk_cfg)
        compress_j = jax.jit(lambda g, lr: local_topk.client_compress(
            g, None, lr, lay, topk_cfg)[0])
        apply_j = None
        for r in range(rounds):
            clients = federated.sample_clients(dataset.n_clients,
                                               clients_per_round, r, seed)
            deltas, loss_acc = [], 0.0
            for cb in _client_batches(dataset, clients, None):
                loss, grads = gf(params, _to_jnp(cb))
                deltas.append(compress_j(grads, lr_fn(r)))
                loss_acc += float(loss)
            if apply_j is None:
                apply_j = jax.jit(lambda p, ds, s: local_topk.server_apply(
                    p, ds, s, lay, topk_cfg))
            params, st = apply_j(params, deltas, st)
            losses.append(loss_acc / len(deltas))
            union = len(np.unique(np.concatenate(
                [np.asarray(dd.chunk_id) * (2 ** 26)
                 + np.asarray(dd.local_idx) for dd in deltas])))
            meter.record(compression.local_topk_round(topk_cfg.k, union),
                         clients_per_round)

    elif method == "fedavg":
        fa_cfg = fa_cfg or fedavg.FedAvgConfig(local_epochs=2)
        st = fedavg.init_server_state(params, fa_cfg)

        def gf_batch(p, b):
            return gf(p, b)[1]

        for r in range(rounds):
            clients = federated.sample_clients(dataset.n_clients,
                                               clients_per_round, r, seed)
            deltas, weights, loss_acc = [], [], 0.0
            for cb in _client_batches(dataset, clients, None):
                jb = _to_jnp(cb)
                loss, _ = gf(params, jb)
                loss_acc += float(loss)
                reps = {k: jnp.stack([v] * fa_cfg.local_epochs)
                        for k, v in jb.items()}
                deltas.append(fedavg.client_update(params, reps, lr_fn(r),
                                                   gf_batch, fa_cfg))
                weights.append(len(cb["tokens"]))
            params, st = fedavg.server_apply(params, deltas, weights, st,
                                             fa_cfg)
            losses.append(loss_acc / len(deltas))
            meter.record(compression.fedavg_round(d), clients_per_round)

    elif method == "uncompressed":
        ucfg = uncompressed.SGDConfig(momentum=0.9)
        st = uncompressed.init_state(params, ucfg)
        for r in range(rounds):
            clients = federated.sample_clients(dataset.n_clients,
                                               clients_per_round, r, seed)
            gs, loss_acc = None, 0.0
            for cb in _client_batches(dataset, clients, None):
                loss, grads = gf(params, _to_jnp(cb))
                gs = grads if gs is None else jax.tree.map(jnp.add, gs, grads)
                loss_acc += float(loss)
            gs = jax.tree.map(lambda x: x / clients_per_round, gs)
            params, st = uncompressed.step(params, gs, st, lr_fn(r), ucfg)
            losses.append(loss_acc / clients_per_round)
            meter.record(compression.uncompressed_round(d), clients_per_round)
    else:
        raise ValueError(method)

    return SimResult(method=method, losses=losses,
                     traffic=meter.compression(clients_per_round),
                     extras=extras)


def SparseOnes(delta: TK.SparseDelta) -> TK.SparseDelta:
    return TK.SparseDelta(chunk_id=delta.chunk_id, local_idx=delta.local_idx,
                          values=jnp.ones_like(delta.values), k=delta.k)


def main(argv=None):
    """CLI smoke driver: micro-config federated runs on CPU.

        PYTHONPATH=src python -m repro.launch.simulate \
            --aggregate tree --rounds 5
        PYTHONPATH=src python -m repro.launch.simulate \
            --clock event --aggregate async --rounds 5 --bw-sigma 2.0
        PYTHONPATH=src python -m repro.launch.simulate \
            --clock event --population 100000 --rounds 3
        PYTHONPATH=src python -m repro.launch.simulate \
            --clock round --population 100000 --rounds 3 \
            --weight-by profile --profile-stream counter
    """
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="fetchsgd",
                    choices=("fetchsgd", "true_topk", "local_topk", "fedavg",
                             "uncompressed"))
    ap.add_argument("--aggregate", default="flat",
                    choices=("flat", "tree", "async"))
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clients-per-round", type=int, default=None,
                    help="cohort size (default 4; with --population, "
                         "max(4, population // 100))")
    ap.add_argument("--population", type=int, default=None,
                    help="total client population; switches on the "
                         "vectorized dispatch path (event clock: lazy "
                         "events + bucketed queue; round clock: column "
                         "fates/weights + streaming folds) so 10^4-10^6 "
                         "clients simulate with O(sketch) server memory")
    ap.add_argument("--profile-stream", default="counter",
                    choices=("legacy", "counter"),
                    help="per-client profile rng: counter = vectorized "
                         "Philox (fed.profile_rng, ~10^6 clients/s, the "
                         "default); legacy = per-client default_rng, "
                         "bit-compatible with pre-knob checkpoints "
                         "(~10^4 clients/s). A resume must match the "
                         "checkpoint's stream")
    ap.add_argument("--min-clients-per-round", type=int, default=None)
    ap.add_argument("--tree-fanout", type=int, default=2)
    ap.add_argument("--dropout-prob", type=float, default=0.0)
    ap.add_argument("--straggle-prob", type=float, default=0.0)
    ap.add_argument("--max-delay", type=int, default=2)
    ap.add_argument("--staleness-discount", type=float, default=0.9)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--peak-lr", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--weight-by", default="uniform",
                    choices=("uniform", "samples", "profile"),
                    help="per-client merge weights (FedSKETCH-style)")
    ap.add_argument("--sketch-impl", default="auto",
                    choices=("auto", "jnp", "pallas-interpret", "pallas"),
                    help="count-sketch kernel impl (repro.kernels.ops): "
                         "jnp = XLA scatter/gather, pallas = compiled "
                         "Pallas hot path (TPU-only; fails loudly "
                         "elsewhere), pallas-interpret = validation-only "
                         "interpreter, auto = best compiled path")
    # event clock (fed.simtime): wall-clock federation over heterogeneous
    # client profiles
    ap.add_argument("--clock", default="round", choices=("round", "event"))
    ap.add_argument("--quorum", type=int, default=None,
                    help="event+async: server updates every N arrivals")
    ap.add_argument("--staleness-lambda", type=float, default=0.05,
                    help="event: discount exp(-lambda * age_seconds)")
    ap.add_argument("--max-age", type=float, default=None,
                    help="event: drop contributions older than this (s)")
    ap.add_argument("--link-bandwidth", type=float, default=1e8,
                    help="event: backbone bytes/s for internal tree edges")
    ap.add_argument("--compute-median", type=float, default=1.0,
                    help="event: median client compute seconds/round")
    ap.add_argument("--compute-sigma", type=float, default=0.5)
    ap.add_argument("--bw-median", type=float, default=1e6,
                    help="event: median client uplink bytes/s")
    ap.add_argument("--bw-sigma", type=float, default=1.0,
                    help="event: lognormal uplink spread (2+ = heavy skew)")
    ap.add_argument("--avail-period", type=float, default=0.0,
                    help="event: availability window period (0 = always up)")
    ap.add_argument("--avail-duty-min", type=float, default=1.0)
    ap.add_argument("--avail-duty-max", type=float, default=1.0)
    obs.add_cli_flags(ap)   # --metrics PATH.jsonl / --trace / --obs-summary
    ap.add_argument("--health-every", type=int, default=1,
                    help="emit sketch-health diagnostics every N rounds "
                         "(0 = never; only active with --metrics)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.population is not None and args.population < 1:
        ap.error(f"--population must be >= 1, got {args.population}")
    if args.clients_per_round is None:
        args.clients_per_round = (max(4, args.population // 100)
                                  if args.population is not None else 4)

    from repro.kernels import ops as kernel_ops
    kernel_ops.require_impl(args.sketch_impl)   # loud, before any compile

    cfg = micro_cfg()
    dataset = micro_dataset(cfg, seed=args.seed,
                            n_clients=args.population or 64)
    telemetry = obs.from_args(args, run="simulate", method=args.method,
                              aggregate=args.aggregate, clock=args.clock,
                              seed=args.seed)
    if telemetry.trace_enabled:
        from repro.kernels import ops as kernel_ops
        kernel_ops.set_telemetry(telemetry)
    # built for both clocks: the round clock reads the heterogeneity
    # profiles too (weight_by=profile, vectorized column weights), and
    # --profile-stream must thread through either way
    simtime = fed.SimTimeConfig(
        staleness_lambda=args.staleness_lambda, max_age=args.max_age,
        quorum=args.quorum, link_bandwidth=args.link_bandwidth,
        heterogeneity=fed.HeterogeneityConfig(
            compute_median=args.compute_median,
            compute_sigma=args.compute_sigma,
            bandwidth_median=args.bw_median,
            bandwidth_sigma=args.bw_sigma,
            avail_period=args.avail_period,
            avail_duty_min=args.avail_duty_min,
            avail_duty_max=args.avail_duty_max,
            profile_stream=args.profile_stream))
    fed_cfg = fed.FederationConfig(
        rounds=args.rounds, clients_per_round=args.clients_per_round,
        min_clients_per_round=args.min_clients_per_round,
        aggregate=args.aggregate, tree_fanout=args.tree_fanout,
        staleness_discount=args.staleness_discount,
        straggler=fed.StragglerModel(dropout_prob=args.dropout_prob,
                                     straggle_prob=args.straggle_prob,
                                     max_delay=args.max_delay),
        clock=args.clock, simtime=simtime, weight_by=args.weight_by,
        seed=args.seed, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        vectorized=args.population is not None)
    try:
        res = run_simulation(cfg, method=args.method, rounds=args.rounds,
                             clients_per_round=args.clients_per_round,
                             peak_lr=args.peak_lr, dataset=dataset,
                             seed=args.seed, aggregate=args.aggregate,
                             fed_cfg=fed_cfg if args.method == "fetchsgd"
                             else None, telemetry=telemetry,
                             health_every=args.health_every,
                             sketch_impl=args.sketch_impl)
    finally:
        telemetry.close()
    if args.metrics:
        print(f"telemetry: {args.metrics}")
    print(f"method={args.method} aggregate={args.aggregate} "
          f"clock={args.clock}")
    if not res.losses:
        print(f"nothing to do: checkpoint in {args.checkpoint_dir} already "
              f"covers all {args.rounds} rounds")
        return res
    for r, loss in enumerate(res.losses):
        rec = (res.extras.get("fed_records") or [None] * len(res.losses))[r]
        detail = (f"  fresh={rec.n_fresh} late={rec.n_late} "
                  f"dropped={rec.n_dropped}" if rec else "")
        if rec and rec.t_virtual is not None:
            detail += (f" t={rec.t_virtual:8.1f}s"
                       f" critical_path={rec.critical_path_s:6.1f}s"
                       f" in_flight={rec.n_straggling}")
        print(f"round {rec.round_idx if rec else r}: "
              f"loss {loss:.4f}{detail}")
    t = res.traffic
    print(f"traffic: up={t['upload_bytes']/1e6:.2f}MB "
          f"down={t['download_bytes']/1e6:.2f}MB "
          f"compression {t['total_x']:.1f}x")
    if res.extras.get("t_virtual") is not None:
        print(f"virtual wall-clock: {res.extras['t_virtual']:.1f}s for "
              f"{len(res.losses)} rounds "
              f"({res.extras['in_flight']} uploads still in flight)")
    assert np.isfinite(res.losses[-1]), \
        "non-finite final loss (diverged, or no client participated)"
    return res


@functools.lru_cache(maxsize=8)
def _true_topk_jit(lay, fs_cfg):
    @jax.jit
    def f(mom, err, params, gs, lr):
        mom = jax.tree.map(lambda m, g: fs_cfg.momentum * m + g, mom, gs)
        acc = jax.tree.map(lambda e, m: e + lr * m, err, mom)
        delta = TK.topk_dense(layout_lib.leaf_views(acc, lay), lay, fs_cfg.k)
        params = TK.apply_delta(params, lay, delta)
        err = TK.apply_delta(acc, lay, delta)   # acc - extracted
        # momentum factor masking on the dense momentum
        mask = TK.apply_delta(jax.tree.map(jnp.zeros_like, acc), lay,
                              SparseOnes(delta), scale=-1.0)
        mom = jax.tree.map(lambda m, ms: m * (1 - ms), mom, mask)
        return mom, err, params
    return f


if __name__ == "__main__":
    main()
