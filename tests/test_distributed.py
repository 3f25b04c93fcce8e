"""Distributed-step tests — run in subprocesses so the forced host-device
count never leaks into the rest of the suite (jax locks device count on
first init)."""

import subprocess
import sys
import textwrap

import pytest

# CPU collectives on forced host devices share one core here; keep meshes
# tiny and models smoke-sized.
TIMEOUT = 420


def run_sub(code: str):
    prog = textwrap.dedent(code)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os\n"
         "os.environ['XLA_FLAGS'] = "
         "'--xla_force_host_platform_device_count=4'\n"
         "import sys\nsys.path.insert(0, 'src')\n"
         "from repro.launch.mesh import make_mesh\n" + prog],
        capture_output=True, text=True, timeout=TIMEOUT, cwd=".")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.mark.slow
def test_train_step_runs_and_matches_single_host():
    """The shard_map FetchSGD step produces the same update as the
    single-process reference (same sketch hash identity)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.core import fetchsgd as F, layout as L
        from repro.launch import shapes, steps
        from repro.models import transformer
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = configs.get_smoke("internlm2-1.8b")
        fs = F.FetchSGDConfig(rows=3, cols=4096, k=64, momentum=0.9)
        bundle = steps.make_train_step(
            cfg, shapes.ShapeSpec("t", "train", 32, 4), mesh, fs)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        opt = F.init_state(fs)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": tok, "labels": tok}
        with mesh:
            p2, o2, m = bundle.fn(params, opt, batch, jnp.float32(0.1))
        # single-host reference
        lay = L.build_layout(params)
        (loss, _), grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, batch, cfg), has_aux=True)(params)
        p_ref, o_ref, _ = F.step(params, grads, F.init_state(fs), 0.1, lay, fs)
        diff = max(float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max())
                   for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p_ref)))
        print("LOSS", float(m["loss"]), "DIFF", diff)
        assert np.isfinite(float(m["loss"]))
        # near-tie top-k selections can differ between the sharded and
        # single-host sketches (bf16 carry rounding); one swapped
        # coordinate changes a param by ~lr*|estimate|
        assert diff < 0.15, diff
    """)
    assert "DIFF" in out


@pytest.mark.slow
def test_weighted_train_step_matches_weighted_reference():
    """make_train_step(weighted=True) on a real multi-shard mesh: the
    psum(w*t)/psum(w) merge must equal the single-host sketch of the
    identically weighted gradient mean, for flat and tree alike.  (The
    size-1-axis test in test_simtime.py degenerates to the identity; this
    exercises the P(axes) weight spec and both reduction topologies.)"""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.core import fetchsgd as F, layout as L
        from repro.launch import shapes, steps
        from repro.models import transformer
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = configs.get_smoke("internlm2-1.8b")
        fs = F.FetchSGDConfig(rows=3, cols=4096, k=64, momentum=0.9)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": tok, "labels": tok}
        w = jnp.asarray([0.5, 2.5], jnp.float32)   # one weight per data shard
        outs = {}
        for agg in ("flat", "tree"):
            bundle = steps.make_train_step(
                cfg, shapes.ShapeSpec("t", "train", 32, 4), mesh, fs,
                aggregate=agg, weighted=True)
            with mesh:
                p2, o2, m = bundle.fn(params, F.init_state(fs), batch,
                                      jnp.float32(0.1), w)
            assert np.isfinite(float(m["loss"]))
            outs[agg] = p2
        # weighted flat == weighted tree (same weighted mean, by linearity)
        tdiff = max(float(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)).max())
                    for a, b in zip(jax.tree.leaves(outs["flat"]),
                                    jax.tree.leaves(outs["tree"])))
        # single-host reference: weighted mean of per-shard gradients
        lay = L.build_layout(params)
        gs, ws = [], [0.5, 2.5]
        for i in range(2):
            shard = {k: v[2*i:2*i+2] for k, v in batch.items()}
            (_, _), g = jax.value_and_grad(
                lambda p: transformer.loss_fn(p, shard, cfg),
                has_aux=True)(params)
            gs.append(g)
        gmean = jax.tree.map(
            lambda a, b: (ws[0]*a + ws[1]*b) / (ws[0] + ws[1]), *gs)
        p_ref, _, _ = F.step(params, gmean, F.init_state(fs), 0.1, lay, fs)
        rdiff = max(float(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)).max())
                    for a, b in zip(jax.tree.leaves(outs["flat"]),
                                    jax.tree.leaves(p_ref)))
        print("TDIFF", tdiff, "RDIFF", rdiff)
        assert tdiff < 1e-5, tdiff
        # near-tie top-k swaps allowed, as in the unweighted parity test
        assert rdiff < 0.15, rdiff
    """)
    assert "RDIFF" in out


@pytest.mark.slow
def test_decode_and_prefill_compile_and_run():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.launch import shapes, steps
        from repro.models import transformer
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = configs.get_smoke("glm4-9b")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        bp = steps.make_prefill_step(cfg, shapes.ShapeSpec("p", "prefill", 32, 4), mesh)
        bd = steps.make_decode_step(cfg, shapes.ShapeSpec("d", "decode", 32, 4), mesh)
        cache = transformer.init_cache(cfg, 4, 32)
        batch = {"tokens": jnp.ones((4, 32), jnp.int32)}
        with mesh:
            logits, cache = bp.fn(params, batch, cache)
            logits2, cache = bd.fn(params, jnp.ones((4, 1), jnp.int32), cache)
        assert logits.shape == (4, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all()
        assert np.isfinite(np.asarray(logits2)).all()
        print("OK")
    """)


@pytest.mark.slow
def test_expert_parallel_all_to_all_matches_local():
    """EP MoE (all_to_all routing) must equal the single-device local MoE."""
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import configs
        from repro.models import moe
        cfg = dataclasses.replace(configs.get_smoke("jamba-v0.1-52b"),
                                  shard_experts_data=True, capacity_factor=4.0)
        mesh = make_mesh((4, 1), ("data", "model"))
        p = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        ref, _ = moe._moe_apply_local(p, x, cfg)

        E = cfg.n_experts
        def body(p_local, x_local):
            with moe.expert_parallel("data"):
                y, aux = moe.moe_apply(p_local, x_local, cfg)
            return y
        espec = {"router": P(), "w_gate": P("data"), "w_up": P("data"),
                 "w_down": P("data")}
        if "shared" in p:
            espec["shared"] = jax.tree.map(lambda _: P(), p["shared"])
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(espec, P("data")), out_specs=P("data"),
                    axis_names={"data"}, check_vma=False))
        with mesh:
            y = f(p, x)
        err = float(jnp.abs(y - ref).max()) / (float(jnp.abs(ref).max()) + 1e-6)
        print("REL_ERR", err)
        assert err < 2e-2, err
    """)


def test_compiled_pallas_with_model_axis_raises(monkeypatch):
    """A model axis > 1 leaves the sketch kernels to GSPMD, which cannot
    partition a Mosaic call: the build refuses instead of falling back."""
    from jax.sharding import AbstractMesh
    from repro import configs
    from repro.core import fetchsgd as F
    from repro.kernels import ops
    from repro.launch import shapes, steps
    monkeypatch.setattr(ops, "pallas_compile_supported", lambda: True)
    cfg = configs.get_smoke("gpt2s-federated")
    shape = shapes.ShapeSpec("t", "train", 32, 4)
    fs = F.FetchSGDConfig(rows=3, cols=4096, k=64)
    with pytest.raises(ValueError, match="cannot partition a Mosaic kernel"):
        steps.make_train_step(cfg, shape, AbstractMesh((2, 2), ("data", "model")), fs)
    assert steps.step_axes(AbstractMesh((4, 1), ("data", "model"))) == {
        "data", "model"}
    assert steps.step_axes(AbstractMesh((2, 2), ("data", "model"))) == {
        "data"}
