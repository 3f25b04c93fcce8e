#!/usr/bin/env python3
"""Smoke run of the FetchSGD mesh trainer on the TPU chips of this host.

    python chip_smoke.py             # one chip: the train step, end to end
    python chip_smoke.py --chips 4   # four chips: the sketch merge only

The default run builds the paper's own model, ``gpt2s-federated`` at full
width (12 layers, d_model 768, vocab 50257), through the normal entry
point (``launch.steps.make_train_step``) on the mesh of the devices
present, with a 5 x 2^14 sketch and k = 512.  It fails unless every sketch
op resolves to compiled Pallas.  It then

1. trains a few rounds on seeded ``ClassShardLM`` data and checks that the
   losses are finite and the parameters move;
2. checks the kernels on the chip: one real gradient of the model is
   sketched with compiled Pallas and with jnp, and one server step is run
   both ways on that table; the tables and states must agree within the
   tolerances below;
3. prints the device's peak memory.

``--chips 4`` runs only the four-chip phase: a data = 4, model = 1 mesh
with one client's batch on each chip, merged ``flat``, ``tree`` and
weighted ``flat``, against the single-device reference of the same cohort
(``F.step`` on the (weighted) mean gradient).

Everything runs in this one process.  Without a TPU it exits non-zero and
prints no result.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import fetchsgd as F, layout as layout_lib, topk  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.launch import mesh as mesh_lib, shapes, steps  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.xla_env import enable_compile_cache  # noqa: E402

ARCH = "gpt2s-federated"
SEQ_LEN = 1024
GLOBAL_BATCH = 8
ROWS, COLS, K = 5, 1 << 14, 512
LR = 0.1
ROUNDS = 3
SEED = 0
# Pallas vs jnp on the chip.  The encode sums ~10^4 values per cell in
# another order (f32: ~1e-6 relative); operands rounded to bf16 in the MXU
# would be off by ~1e-3.  Each check: |got - want| <= RTOL*|want| + ATOL
# with ATOL = ATOL_FRAC * max|want|.
RTOL, ATOL_FRAC = 1e-4, 1e-5
# four-chip phase, as in tests/test_distributed.py: flat and tree are the
# same mean; the reference may differ by one near-tie top-k swap
MERGE_TOL, REF_TOL = 1e-5, 0.15


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def compile_step(bundle):
    t0 = time.perf_counter()
    compiled = bundle.fn.lower(*bundle.inputs).compile()
    return compiled, time.perf_counter() - t0


def placed(compiled, *args):
    """``args`` put where the compiled executable expects them."""
    return jax.device_put(args, compiled.input_shardings[0])


def max_diff(a, b) -> float:
    return max(float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))
                     .max()) for x, y in zip(jax.tree.leaves(a),
                                             jax.tree.leaves(b)))


def check_close(name: str, got, want) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want)
    atol = ATOL_FRAC * float(np.abs(want).max())
    bad = int((err > RTOL * np.abs(want) + atol).sum())
    print(f"check {name}: max|diff| {err.max():.3e}  max|ref| "
          f"{np.abs(want).max():.3e}  cells over tolerance {bad}/{err.size}")
    if bad:
        fail(f"{name}: Pallas and jnp disagree beyond rtol={RTOL}, "
             f"atol={atol:.3e}")


def client_batch(ds, client: int, n: int) -> dict:
    cb = ds.client_batch(client)
    return {"tokens": cb["tokens"][:n], "labels": cb["labels"][:n]}


def grad_fn(cfg):
    return jax.jit(jax.grad(lambda p, b: transformer.loss_fn(p, b, cfg)[0]))


def build(cfg, mesh, batch: int, fs, **kw):
    shape = shapes.ShapeSpec("chip_smoke", "train", SEQ_LEN, batch)
    bundle = steps.make_train_step(cfg, shape, mesh, fs, **kw)
    compiled, secs = compile_step(bundle)
    return bundle, compiled, secs


def train_rounds(cfg, mesh, fs):
    paths = kernel_ops.resolve_ops(fs.impl, fs.rows, fs.cols)
    print(f"sketch ops: {paths}")
    bundle, compiled, secs = build(cfg, mesh, GLOBAL_BATCH, fs)
    print(f"step compile seconds: {secs:.3f}")
    ds = synthetic.ClassShardLM(vocab=cfg.vocab, seq_len=SEQ_LEN,
                                n_clients=256,
                                samples_per_client=GLOBAL_BATCH, seed=SEED)
    params0 = transformer.init_params(cfg, jax.random.PRNGKey(SEED))
    print(f"params: {transformer.param_count(params0):,}")
    params, opt = params0, F.init_state(fs)
    for r in range(ROUNDS):
        batch = client_batch(ds, r, GLOBAL_BATCH)
        args = placed(compiled, params, opt, batch, jnp.float32(LR))
        t0 = time.perf_counter()
        params, opt, m = compiled(*args)
        jax.block_until_ready((params, opt, m))
        dt = time.perf_counter() - t0
        loss = float(m["loss"])
        print(f"round {r}: loss {loss:.6f}  step seconds {dt:.6f} "
              f"(smoke run, not a benchmark)")
        if not np.isfinite(loss):
            fail(f"round {r} loss is {loss}")
    moved = max_diff(params, params0)
    print(f"max |params - params0| after {ROUNDS} rounds: {moved:.3e}")
    if not moved > 0:
        fail("the parameters did not change")
    return bundle.layout, params0, client_batch(ds, 0, GLOBAL_BATCH)


def kernel_check(cfg, layout, fs, params, batch) -> None:
    fs_ref = dataclasses.replace(fs, impl="jnp")
    grads = grad_fn(cfg)(params, batch)
    tables = [jax.jit(lambda g, f=f: F.sketch_grads(g, layout, f))(grads)
              for f in (fs, fs_ref)]
    check_close("sketch table", *tables)
    table = tables[1]
    outs = [jax.jit(lambda t, f=f: F.server_step(
        t, F.init_state(f), jnp.float32(LR), layout, f))(table)
        for f in (fs, fs_ref)]
    (d_k, s_k), (d_r, s_r) = outs
    check_close("momentum sketch", s_k.momentum_sketch, s_r.momentum_sketch)
    check_close("error sketch", s_k.error_sketch, s_r.error_sketch)
    ids = [np.asarray(topk.global_ids(d, layout)[1]) for d in (d_k, d_r)]
    same = int(np.isin(*ids).sum())
    print(f"check top-k ids: {same}/{d_r.k} shared")


def four_chip(cfg, mesh, fs) -> None:
    n = mesh.shape["data"]
    per = GLOBAL_BATCH // n if GLOBAL_BATCH >= n else 1
    ds = synthetic.ClassShardLM(vocab=cfg.vocab, seq_len=SEQ_LEN,
                                n_clients=256, samples_per_client=per,
                                seed=SEED)
    clients = [client_batch(ds, i, per) for i in range(n)]
    batch = {k: np.concatenate([c[k] for c in clients]) for k in clients[0]}
    weights = np.arange(1, n + 1, dtype=np.float32) / 2
    params = transformer.init_params(cfg, jax.random.PRNGKey(SEED))
    outs = {}
    for name, agg, weighted in (("flat", "flat", False),
                                ("tree", "tree", False),
                                ("flat weighted", "flat", True)):
        _, compiled, secs = build(cfg, mesh, per * n, fs, aggregate=agg,
                                  weighted=weighted)
        args = (params, F.init_state(fs), batch, jnp.float32(LR))
        args += (jnp.asarray(weights),) if weighted else ()
        p2, _, m = compiled(*placed(compiled, *args))
        loss = float(m["loss"])
        print(f"{name}: compile seconds {secs:.3f}  loss {loss:.6f}")
        if not np.isfinite(loss):
            fail(f"{name} loss is {loss}")
        outs[name] = p2
    tdiff = max_diff(outs["flat"], outs["tree"])
    print(f"flat vs tree: max|diff| {tdiff:.3e} (tolerance {MERGE_TOL})")
    if not tdiff < MERGE_TOL:
        fail("flat and tree merges disagree")
    # single-device reference of the same cohort
    layout = layout_lib.build_layout(params)
    grads = [grad_fn(cfg)(params, c) for c in clients]
    ref_step = jax.jit(lambda p, g: F.step(p, g, F.init_state(fs),
                                           jnp.float32(LR), layout, fs)[0])
    for name, w in (("flat", np.ones(n, np.float32)),
                    ("flat weighted", weights)):
        gmean = jax.tree.map(
            lambda *gs: sum(wi * g for wi, g in zip(w, gs)) / w.sum(), *grads)
        rdiff = max_diff(outs[name], ref_step(params, gmean))
        print(f"{name} vs single-device reference: max|diff| {rdiff:.3e} "
              f"(tolerance {REF_TOL})")
        if not rdiff < REF_TOL:
            fail(f"{name} merge disagrees with the reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip merge phase")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"have {len(devices)}")
    print(f"device {dev.device_kind} x {len(devices)}  compile cache {cache}")
    cfg = configs.get_config(ARCH)
    fs = F.FetchSGDConfig(rows=ROWS, cols=COLS, k=K, momentum=0.9)
    mesh = mesh_lib.make_production_mesh()
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name}  d_model {cfg.d_model}"
          f"  layers {cfg.n_layers}  vocab {cfg.vocab}")
    if args.chips == 4:
        four_chip(cfg, mesh, fs)
    else:
        paths = kernel_ops.resolve_ops(fs.impl, fs.rows, fs.cols)
        if set(paths.values()) != {"pallas:compiled"}:
            fail(f"sketch ops did not all resolve to compiled Pallas: "
                 f"{paths}")
        layout, params0, batch0 = train_rounds(cfg, mesh, fs)
        kernel_check(cfg, layout, fs, params0, batch0)
        peak = dev.memory_stats().get("peak_bytes_in_use")
        print(f"peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
