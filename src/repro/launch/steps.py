"""Distributed step builders: FetchSGD train, prefill, decode.

Every step is one ``jax.shard_map`` **manual over the batch/client axes**
(``pod``, ``data``) and **auto (GSPMD) over ``model``** — tensor-parallel
math inside each client cohort is untouched XLA, while FetchSGD's
aggregation boundary is explicit.  A mesh whose ``model`` axis is 1 is
manual over every axis, so no compiled Pallas (Mosaic) call is left to
GSPMD, which cannot partition one:

    local grad -> sketch (r x c) -> psum over (pod, data) -> server update

so the only data-axis collective in the optimizer path is the sketch table
(paper Sec. 3.2 mapped onto ICI collectives; the dense-gradient psum it
replaces is the ``aggregate='dense'`` baseline, kept for the roofline
comparison).

Expert-parallel archs (``cfg.shard_experts_data``) hold only their expert
slice per data shard; routing goes through all_to_all (moe.moe_apply_ep),
gradients of expert slices are sketched with shard-indexed global offsets,
and the sparse update is owner-masked on application.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fetchsgd as F
from repro.core import layout as layout_lib
from repro.fed import aggregator as fed_agg
from repro.models import moe, sharding, transformer
from repro.models.config import ArchConfig
from repro.obs import layers
from .shapes import ShapeSpec

CACHE_DTYPE = jnp.bfloat16


# -- plumbing --------------------------------------------------------------------

def manual_axes(mesh) -> tuple[str, ...]:
    """The client axes: the optimizer's collectives reduce over these."""
    return tuple(ax for ax in ("pod", "data") if ax in mesh.shape)


def step_axes(mesh) -> set[str]:
    """Mesh axes the step bodies are manual over: all of them when the
    ``model`` axis is 1, else the client axes (``model`` left to GSPMD)."""
    if mesh.shape.get("model", 1) == 1:
        return set(mesh.axis_names)
    return set(manual_axes(mesh))


def _manual_only(spec: P, axes: tuple[str, ...]) -> P:
    """Strip a PartitionSpec down to the manual mesh axes."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axes)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axes else None)
    return P(*out)


def _specs(tree_shardings, axes):
    return jax.tree.map(lambda s: _manual_only(s.spec, axes), tree_shardings,
                        is_leaf=lambda x: isinstance(x, NamedSharding))


def _sds(tree_structs, shardings):
    return jax.tree.map(
        lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype, sharding=sh),
        tree_structs, shardings)


@dataclasses.dataclass(frozen=True)
class StepBundle:
    """A lowered-ready step: fn + fully-sharded ShapeDtypeStruct inputs."""

    fn: Any                # jitted callable
    inputs: tuple          # ShapeDtypeStructs matching fn's signature
    layout: Any = None     # ParamLayout (train steps)


# -- input structs ---------------------------------------------------------------

def param_structs(cfg: ArchConfig, mesh):
    structs = jax.eval_shape(
        functools.partial(transformer.init_params, cfg),
        jax.random.PRNGKey(0))
    shardings = sharding.params_sharding(structs, cfg, mesh)
    return _sds(structs, shardings), shardings


def batch_structs(cfg: ArchConfig, shape: ShapeSpec, mesh):
    B = shape.global_batch
    S = shape.seq_len
    batch = {}
    if shape.kind == "decode":
        batch["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    else:
        s_text = S - (cfg.n_patches if cfg.frontend == "vision" else 0)
        batch["tokens"] = jax.ShapeDtypeStruct((B, s_text), jnp.int32)
        if shape.kind == "train":
            batch["labels"] = jax.ShapeDtypeStruct((B, s_text), jnp.int32)
        if cfg.frontend == "vision":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.is_encdec:
            batch["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.float32)
    shardings = sharding.batch_sharding(batch, mesh)
    return _sds(batch, shardings), shardings


def cache_structs(cfg: ArchConfig, shape: ShapeSpec, mesh):
    B = shape.global_batch
    structs = jax.eval_shape(
        functools.partial(transformer.init_cache, cfg, B, shape.seq_len,
                          CACHE_DTYPE))
    shardings = sharding.cache_sharding(structs, cfg, mesh)
    return _sds(structs, shardings), shardings


def _ep_info(cfg: ArchConfig, param_shardings, mesh):
    """(has_ep, data_shard_axis dict) from the parameter shardings."""
    if not cfg.shard_experts_data or "data" not in mesh.shape:
        return False, {}
    axes = {}

    def visit(kp, sh):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        for i, entry in enumerate(sh.spec):
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            if "data" in [n for n in names if n]:
                axes[path] = i
        return sh

    jax.tree_util.tree_map_with_path(visit, param_shardings)
    return bool(axes), axes


def build_layout(cfg: ArchConfig, mesh):
    """Global FetchSGD layout over the full parameter space."""
    structs = jax.eval_shape(
        functools.partial(transformer.init_params, cfg),
        jax.random.PRNGKey(0))
    _, shardings = param_structs(cfg, mesh)
    has_ep, ds_axes = _ep_info(cfg, shardings, mesh)
    ep = mesh.shape["data"] if has_ep else 1
    return layout_lib.build_layout(structs, data_shard_axis=ds_axes, ep=ep)


# -- train step ------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, shape: ShapeSpec, mesh,
                    fs_cfg: F.FetchSGDConfig, *,
                    aggregate: str = "sketch",
                    sketch_mode: str = "gathered",
                    weighted: bool = False,
                    donate: bool = False) -> StepBundle:
    """FetchSGD train step, parameterized by sketch aggregation policy.

    ``aggregate`` selects how client sketch tables merge (repro.fed):

    * ``'sketch'`` / ``'flat'`` — one pmean over all client axes;
    * ``'tree'``   — hierarchical per-axis reduction (intra-pod ICI first,
      then cross-pod), ``fed.aggregator.mesh_aggregate`` policy 'tree';
    * ``'async'``  — flat merge of the in-step cohort plus a host-injected
      buffer of staleness-discounted late tables.  The step takes three
      extra args ``(fresh_weight, inject_table, inject_weight)`` and
      returns the fresh aggregated table in ``metrics['table']`` so the
      host driver (``train.py`` + ``fed.AsyncBufferedAggregator``) can
      buffer delayed rounds;
    * ``'dense'``  — psum the full d-dim gradient (roofline baseline).

    ``weighted=True`` (sketch/tree only) appends one trailing step arg: a
    per-client-shard weight vector (one entry per manual-mesh shard), and
    the merge becomes the exact weighted mean ``psum(w*t)/psum(w)``
    (FedSKETCH-style, still just sketch linearity).

    Returns fn(params, opt_state, batch, lr[, fresh_w, inject, inject_w]
    [, weight]) -> (params, opt_state, metrics).
    """
    if aggregate == "flat":
        aggregate = "sketch"
    if aggregate not in ("sketch", "tree", "async", "dense"):
        raise ValueError(f"unknown aggregate policy {aggregate!r}")
    # fail loudly at build time (not mid-trace) if the configured sketch
    # impl cannot run here — e.g. compiled Pallas on a CPU backend
    from repro.kernels import ops as kernel_ops
    kernel_ops.require_impl(fs_cfg.impl)
    paths = kernel_ops.resolve_ops(fs_cfg.impl, fs_cfg.rows, fs_cfg.cols)
    if mesh.shape["model"] > 1 and "pallas:compiled" in paths.values():
        raise ValueError(
            f"compiled Pallas sketch kernels ({paths}) need a step that is "
            f"manual over every mesh axis, but model={mesh.shape['model']} "
            f"is left to GSPMD, which cannot partition a Mosaic kernel.  Use "
            f"a mesh with model=1 or sketch impl 'jnp'.")
    if weighted and aggregate not in ("sketch", "tree"):
        raise ValueError("weighted merging needs aggregate='sketch'|'tree' "
                         f"(got {aggregate!r})")
    if weighted and sketch_mode == "model_local":
        raise ValueError("weighted merging is not wired into the "
                         "model_local pipeline")
    axes = manual_axes(mesh)
    p_sds, p_shard = param_structs(cfg, mesh)
    b_sds, b_shard = batch_structs(cfg, shape, mesh)
    has_ep, ds_axes = _ep_info(cfg, p_shard, mesh)
    ep = mesh.shape["data"] if has_ep else 1
    p_structs = jax.eval_shape(
        functools.partial(transformer.init_params, cfg),
        jax.random.PRNGKey(0))
    view_perms, view_sh, ml_modes, ml_specs = sharding.layout_view_plan(
        p_structs, cfg, mesh)
    layout = layout_lib.build_layout(p_structs, data_shard_axis=ds_axes,
                                     view_perms=view_perms, ep=ep)

    p_manual = _specs(p_shard, axes)
    b_manual = _specs(b_shard, axes)
    ep_axis = "data" if has_ep else None

    act_sh = None
    if "model" not in step_axes(mesh) and cfg.d_model % mesh.shape["model"] == 0:
        act_sh = NamedSharding(mesh, P(None, None, "model"))

    def _loss_grads(params, batch):
        with moe.expert_parallel(ep_axis), \
                sharding.activation_sharding(act_sh), \
                jax.named_scope(layers.CLIENT_MODEL):
            return jax.value_and_grad(
                lambda p: transformer.loss_fn(p, batch, cfg)[0])(params)

    def _sketch(grads, sidx):
        with jax.named_scope(layers.SKETCH_ENCODE):
            return F.sketch_grads(grads, layout, fs_cfg, shard_idx=sidx,
                                  local=has_ep, view_shardings=view_sh)

    def _server_apply(params, opt_state, table, lr, sidx):
        delta, new_state = F.server_step(table, opt_state, lr, layout,
                                         fs_cfg)
        with jax.named_scope(layers.SPARSE_APPLY):
            new_params = F.apply_delta(params, layout, delta,
                                       shard_idx=sidx, local=has_ep,
                                       view_shardings=view_sh)
        return new_params, new_state

    def body(params, opt_state, batch, lr, *maybe_w):
        loss, grads = _loss_grads(params, batch)
        sidx = jax.lax.axis_index("data") if has_ep else None
        if aggregate in ("sketch", "tree"):
            # FetchSGD: the ONLY cross-client collective is (rows x cols);
            # 'tree' reduces it hierarchically, one link class per level.
            table = _sketch(grads, sidx)
            with jax.named_scope(layers.MERGE):
                table = fed_agg.mesh_aggregate(
                    table, axes,
                    policy="tree" if aggregate == "tree" else "flat",
                    weight=maybe_w[0][0] if maybe_w else None)
            new_params, new_state = _server_apply(params, opt_state, table,
                                                  lr, sidx)
        elif aggregate == "dense":
            # baseline: psum the full d-dim gradient (what FetchSGD avoids);
            # EP expert grads are shard-owned and stay local.
            def maybe_psum(kp, g):
                path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in kp)
                red = axes if path not in ds_axes else tuple(
                    a for a in axes if a != "data")
                return jax.lax.pmean(g, red) if red else g
            with jax.named_scope(layers.MERGE):
                grads = jax.tree_util.tree_map_with_path(maybe_psum, grads)
            table = _sketch(grads, sidx)
            new_params, new_state = _server_apply(params, opt_state, table,
                                                  lr, sidx)
        else:
            raise ValueError(aggregate)
        with jax.named_scope(layers.MERGE):
            metrics = {"loss": jax.lax.pmean(loss, axes)}
        return new_params, new_state, metrics

    def body_async(params, opt_state, batch, lr, fresh_w, inject_table,
                   inject_w):
        """Flat in-step merge + staleness-discounted host buffer injection.

        ``inject_table`` arrives as a discount-weighted *sum* of buffered
        tables (total weight ``inject_w``); ``fresh_w`` is 0 when the host
        marks this round's cohort as straggling (its table — returned in
        metrics — will be injected into a later round instead).  With an
        empty buffer and fresh_w=1 this reduces exactly to the flat policy.
        A round with zero total weight leaves params and optimizer state
        untouched (same "no new information" semantics as the
        Orchestrator's total_weight guard).
        """
        loss, grads = _loss_grads(params, batch)
        sidx = jax.lax.axis_index("data") if has_ep else None
        table = _sketch(grads, sidx)
        with jax.named_scope(layers.MERGE):
            fresh = fed_agg.mesh_aggregate(table, axes, policy="flat")
            total_w = fresh_w + inject_w
            merged = (fresh_w * fresh + inject_table) / jnp.maximum(total_w,
                                                                    1e-8)
        new_params, new_state = jax.lax.cond(
            total_w > 0,
            lambda ops: _server_apply(*ops, sidx),
            lambda ops: (ops[0], ops[1]),
            (params, opt_state, merged, lr))
        with jax.named_scope(layers.MERGE):
            metrics = {"loss": jax.lax.pmean(loss, axes), "table": fresh}
        return new_params, new_state, metrics

    opt_spec = jax.tree.map(lambda _: P(), jax.eval_shape(
        functools.partial(F.init_state, fs_cfg)))

    if aggregate == "sketch" and sketch_mode == "model_local":
        sm = _model_local_pipeline(
            cfg, mesh, axes, fs_cfg, layout, has_ep, ep_axis, act_sh,
            view_sh, ml_modes, ml_specs, p_manual, b_manual, opt_spec,
            p_structs)
    elif aggregate == "async":
        sm = jax.shard_map(
            body_async, mesh=mesh,
            in_specs=(p_manual, opt_spec, b_manual, P(), P(), P(), P()),
            out_specs=(p_manual, opt_spec, {"loss": P(), "table": P()}),
            axis_names=step_axes(mesh), check_vma=False)
    else:
        w_specs = (P(axes),) if weighted else ()
        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_manual, opt_spec, b_manual, P()) + w_specs,
            out_specs=(p_manual, opt_spec, {"loss": P()}),
            axis_names=step_axes(mesh), check_vma=False)
    # donation aliases params/opt in production (TPU); the CPU runtime
    # deadlocks on donated collective inputs, so tests run donate=False and
    # the dry-run (compile-only) sets donate=True to model real aliasing.
    fn = jax.jit(sm, donate_argnums=(0, 1)) if donate else jax.jit(sm)
    opt_sds = _sds(jax.eval_shape(functools.partial(F.init_state, fs_cfg)),
                   jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                jax.eval_shape(functools.partial(F.init_state,
                                                                 fs_cfg))))
    lr_sds = jax.ShapeDtypeStruct((), jnp.float32)
    inputs = (p_sds, opt_sds, b_sds, lr_sds)
    if aggregate == "async":
        inputs += (jax.ShapeDtypeStruct((), jnp.float32),
                   jax.ShapeDtypeStruct((fs_cfg.rows, fs_cfg.cols),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((), jnp.float32))
    if weighted:
        inputs += (jax.ShapeDtypeStruct((_meshprod(mesh, axes),),
                                        jnp.float32),)
    return StepBundle(fn=fn, inputs=inputs, layout=layout)


# -- vectorized federated cohort step --------------------------------------------

def make_cohort_fn(cfg: ArchConfig, layout, fs_cfg: F.FetchSGDConfig,
                   encode_fn=None):
    """One jitted call for a whole chunk of federated clients.

    Returns ``fn(params, tokens (B, ...), labels (B, ...)) -> (losses (B,),
    tables (B, rows, cols))`` — ``lax.map`` over the stacked client batches
    of exactly the per-client computation the event loop's scalar path
    runs: ``value_and_grad(loss_fn(remat=False))`` followed by the sketch
    encode.  ``lax.map`` applies the body per element with no cross-element
    reduction, so each client's (loss, table) is **bitwise identical** to a
    standalone jitted call — which is what lets ``fed.orchestrator``
    materialize lazy events in chunks without perturbing the per-object
    path's RoundRecord/checkpoint bytes (pinned in
    ``tests/test_population.py``).

    ``encode_fn`` must be the *same* (un-jitted) grads->table closure the
    caller uses for single-event materialization — the orchestrator passes
    its own so the chunked and scalar paths can never diverge; defaults to
    the reference ``F.sketch_grads``.
    """
    if encode_fn is None:
        def encode_fn(g):
            return F.sketch_grads(g, layout, fs_cfg)

    @jax.jit
    def cohort_fn(params, tokens, labels):
        def one(tl):
            t, l = tl
            with jax.named_scope(layers.CLIENT_MODEL):
                (loss, _), grads = jax.value_and_grad(
                    lambda p: transformer.loss_fn(
                        p, {"tokens": t, "labels": l}, cfg, remat=False),
                    has_aux=True)(params)
            with jax.named_scope(layers.SKETCH_ENCODE):
                return loss, encode_fn(grads)
        return jax.lax.map(one, (tokens, labels))

    return cohort_fn


# -- serve steps -----------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, shape: ShapeSpec, mesh,
                      donate: bool = False) -> StepBundle:
    axes = manual_axes(mesh)
    p_sds, p_shard = param_structs(cfg, mesh)
    b_sds, b_shard = batch_structs(cfg, shape, mesh)
    c_sds, c_shard = cache_structs(cfg, shape, mesh)
    has_ep, _ = _ep_info(cfg, p_shard, mesh)
    ep_axis = "data" if has_ep else None
    B = shape.global_batch
    logits_spec = (P(axes, None) if B % _meshprod(mesh, axes) == 0 and B > 1
                   else P(None, None))

    def body(params, batch, cache):
        with moe.expert_parallel(ep_axis):
            logits, new_cache = transformer.prefill(params, batch, cfg, cache)
        return logits, new_cache

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_specs(p_shard, axes), _specs(b_shard, axes),
                  _specs(c_shard, axes)),
        out_specs=(logits_spec, _specs(c_shard, axes)),
        axis_names=step_axes(mesh), check_vma=False)
    fn = jax.jit(sm, donate_argnums=(2,)) if donate else jax.jit(sm)
    return StepBundle(fn=fn, inputs=(p_sds, b_sds, c_sds))


def make_decode_step(cfg: ArchConfig, shape: ShapeSpec, mesh,
                     donate: bool = False) -> StepBundle:
    axes = manual_axes(mesh)
    p_sds, p_shard = param_structs(cfg, mesh)
    b_sds, b_shard = batch_structs(cfg, shape, mesh)
    c_sds, c_shard = cache_structs(cfg, shape, mesh)
    has_ep, _ = _ep_info(cfg, p_shard, mesh)
    ep_axis = "data" if has_ep else None
    B = shape.global_batch
    logits_spec = (P(axes, None) if B % _meshprod(mesh, axes) == 0 and B > 1
                   else P(None, None))

    def body(params, tokens, cache):
        with moe.expert_parallel(ep_axis):
            logits, new_cache = transformer.decode_step(params, tokens, cfg,
                                                        cache)
        return logits, new_cache

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_specs(p_shard, axes), _specs(b_shard, axes)["tokens"],
                  _specs(c_shard, axes)),
        out_specs=(logits_spec, _specs(c_shard, axes)),
        axis_names=step_axes(mesh), check_vma=False)
    fn = jax.jit(sm, donate_argnums=(2,)) if donate else jax.jit(sm)
    return StepBundle(fn=fn, inputs=(p_sds, b_sds["tokens"], c_sds))


def _meshprod(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _model_local_pipeline(cfg, mesh, axes, fs_cfg, layout, has_ep, ep_axis,
                          act_sh, view_sh, ml_modes, ml_specs, p_manual,
                          b_manual, opt_spec, p_structs):
    """Three sibling shard_maps: grads -> model-local sketch -> server/apply.

    A nested (model-inside-data) shard_map is rejected by the Shardy
    partitioner ("axis already bound"), so the model-local sketch runs as
    its own shard_map manual over (pod, data, model): per-shard gradients
    cross the boundary *stacked* over the client axes (a pure layout
    change — each shard's slice is placed, never gathered), EP expert
    slices keep their expert-dim 'data' placement and stack over 'pod'
    only.
    """
    from repro.core import model_local
    tdef = jax.tree_util.tree_structure(p_structs)
    ml_spec_tree = jax.tree_util.tree_unflatten(tdef, ml_specs)
    ml_plan = model_local.build_plan(layout, ml_modes,
                                     tp=mesh.shape["model"])
    # per-leaf: does the manual spec place 'data' on a tensor dim (EP leaf)?
    p_manual_leaves = jax.tree_util.tree_leaves(
        p_manual, is_leaf=lambda x: isinstance(x, P))
    is_ep_leaf = [any(e == "data" or (isinstance(e, tuple) and "data" in e)
                      for e in spec) for spec in p_manual_leaves]
    stack_axes = [tuple(a for a in axes if a == "pod") if ep else axes
                  for ep in is_ep_leaf]

    def grads_body(params, batch):
        with moe.expert_parallel(ep_axis), \
                sharding.activation_sharding(act_sh), \
                jax.named_scope(layers.CLIENT_MODEL):
            loss, grads = jax.value_and_grad(
                lambda p: transformer.loss_fn(p, batch, cfg)[0])(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        stacked = [g[None] for g in g_leaves]
        with jax.named_scope(layers.MERGE):
            loss = jax.lax.pmean(loss, axes)
        return loss, tuple(stacked)

    g_out_specs = tuple(
        P(sa if sa else None, *spec)
        for sa, spec in zip(stack_axes, p_manual_leaves))
    sm_grads = jax.shard_map(
        grads_body, mesh=mesh, in_specs=(p_manual, b_manual),
        out_specs=(P(), g_out_specs), axis_names=step_axes(mesh), check_vma=False)

    ml_spec_leaves = jax.tree_util.tree_leaves(
        ml_spec_tree, is_leaf=lambda x: isinstance(x, P))
    s_in_specs = tuple(
        P(sa if sa else None, *_merge_spec_entries(ml, dm, 8))
        for sa, ml, dm in zip(stack_axes, ml_spec_leaves, p_manual_leaves))

    def sketch_body(*g_stacked):
        g_leaves = [g[0] for g in g_stacked]
        grads = jax.tree_util.tree_unflatten(tdef, g_leaves)
        s_d = jax.lax.axis_index("data")
        s_m = jax.lax.axis_index("model")
        with jax.named_scope(layers.SKETCH_ENCODE):
            tbl = model_local.sketch_grads(grads, layout, ml_plan, fs_cfg,
                                           s_d, s_m)
            tbl = jax.lax.psum(tbl, ("model",))
        with jax.named_scope(layers.MERGE):
            return jax.lax.pmean(tbl, axes)

    sm_sketch = jax.shard_map(
        sketch_body, mesh=mesh, in_specs=s_in_specs, out_specs=P(),
        axis_names=set(axes) | {"model"}, check_vma=False)

    def server_body(params, opt_state, table, lr):
        sidx = jax.lax.axis_index("data") if has_ep else None
        delta, new_state = F.server_step(table, opt_state, lr, layout,
                                         fs_cfg)
        with jax.named_scope(layers.SPARSE_APPLY):
            new_params = F.apply_delta(params, layout, delta, shard_idx=sidx,
                                       local=has_ep, view_shardings=view_sh)
        return new_params, new_state

    sm_server = jax.shard_map(
        server_body, mesh=mesh,
        in_specs=(p_manual, opt_spec, P(), P()),
        out_specs=(p_manual, opt_spec),
        axis_names=step_axes(mesh), check_vma=False)

    def fn(params, opt_state, batch, lr):
        loss, g_stacked = sm_grads(params, batch)
        table = sm_sketch(*g_stacked)
        new_params, new_state = sm_server(params, opt_state, table, lr)
        return new_params, new_state, {"loss": loss}

    return fn


def _merge_spec_entries(model_spec: P, data_spec: P, pad: int):
    """Combine per-dim model-axis and manual-axis spec entries."""
    out = []
    n = max(len(model_spec), len(data_spec))
    me = list(model_spec) + [None] * (n - len(model_spec))
    de = list(data_spec) + [None] * (n - len(data_spec))
    for m, d in zip(me, de):
        names = []
        for e in (d, m):
            if e is None:
                continue
            if isinstance(e, tuple):
                names.extend(e)
            else:
                names.append(e)
        out.append(tuple(names) if len(names) > 1 else
                   (names[0] if names else None))
    return tuple(out)
