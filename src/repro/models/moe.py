"""Mixture-of-Experts FFN with capacity-based token dispatch.

Token-choice top-k routing with a fixed per-expert capacity
(``ceil(T * top_k / E) * capacity_factor``), one-hot dispatch/combine, and
the standard switch-transformer load-balance auxiliary loss.  Compute cost
is ``O(T * top_k * d * ff)`` (active params only), so the roofline's
MODEL_FLOPS/HLO ratio stays honest for the MoE giants — a dense
all-experts einsum would inflate HLO FLOPs by E/top_k (128x for llama4).

Shared experts (qwen2-moe) are a dense MLP of width
``n_shared * moe_d_ff`` applied to every token, added to the routed output.

Sharding: expert weight tensors are (E, d, ff); ``ff`` shards over
``model`` (tensor-parallel within each expert — works for any E, including
qwen2's 60), and E additionally shards over ``data`` when divisible
(``cfg.shard_experts_data``, ZeRO-style — used by llama4/jamba whose expert
stacks dominate parameter memory).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from . import layers
from .config import ArchConfig

# Expert-parallel context: set by the launch layer around shard_map bodies.
# When active (and cfg.shard_experts_data), expert weights are the shard-
# LOCAL slice (E_local = E / ep) and routing goes through all_to_all over
# the named mesh axis — DeepSpeed-MoE-style EP mapped onto jax collectives.
_EP_AXIS: list = [None]


@contextlib.contextmanager
def expert_parallel(axis_name: str | None):
    _EP_AXIS.append(axis_name)
    try:
        yield
    finally:
        _EP_AXIS.pop()


def ep_axis() -> str | None:
    return _EP_AXIS[-1]


def moe_init(key, cfg: ArchConfig) -> dict:
    d, E, ffe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": layers.normal(ks[0], (d, E), d ** -0.5, jnp.float32),
        "w_gate": layers.normal(ks[1], (E, d, ffe), d ** -0.5, dt),
        "w_up": layers.normal(ks[2], (E, d, ffe), d ** -0.5, dt),
        "w_down": layers.normal(ks[3], (E, ffe, d), ffe ** -0.5, dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.mlp_init(
            ks[4], d, cfg.n_shared_experts * ffe, "swiglu", dt)
    return p


def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig):
    """Dispatch to the expert-parallel path when the EP context is active."""
    if ep_axis() is not None and cfg.shard_experts_data:
        return moe_apply_ep(p, x, cfg, ep_axis())
    return _moe_apply_local(p, x, cfg)


def _moe_apply_local(p: dict, x: jax.Array, cfg: ArchConfig):
    """x: (B, S, d) -> (out, aux_loss)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                      # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # capacity & position-in-expert via cumsum over the flattened (T*K,)
    cap = int(max(K, round(T * K / E * cfg.capacity_factor)))
    cap = min(cap, T)
    ef = eidx.reshape(-1)                                     # (T*K,)
    onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)           # (T*K, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)               # pos before me
    mypos = jnp.take_along_axis(pos, ef[:, None], axis=1)[:, 0]
    keep = mypos < cap

    # dispatch: (E, cap, d) expert input buffers
    xe = jnp.repeat(xt, K, axis=0)                            # token per slot
    disp = jnp.zeros((E, cap, d), x.dtype)
    disp = disp.at[jnp.where(keep, ef, 0),
                   jnp.where(keep, mypos, 0)].add(
        jnp.where(keep[:, None], xe, 0).astype(x.dtype), mode="drop")

    # expert FFN (swiglu), ff sharded over model
    h = jnp.einsum("ecd,edf->ecf", disp, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", disp, p["w_up"])
    h = jax.nn.silu(h) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])        # (E, cap, d)

    # combine: gather each slot's output, weight by its gate
    got = out_e[jnp.where(keep, ef, 0), jnp.where(keep, mypos, 0)]
    got = jnp.where(keep[:, None], got, 0)
    y = (got.reshape(T, K, d) * gate[..., None].astype(x.dtype)).sum(axis=1)

    # load-balance aux (Switch): E * sum_e f_e * P_e
    frac = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(0, 1))
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * pmean) * cfg.router_aux_coef

    if "shared" in p:
        y = y + layers.mlp(p["shared"], xt, "swiglu")
    return y.reshape(B, S, d), aux


def moe_apply_ep(p: dict, x: jax.Array, cfg: ArchConfig, axis: str):
    """Expert-parallel MoE: experts sharded over the ``axis`` mesh shards.

    Inside a manual shard_map region: ``x`` is the shard-local token slice,
    expert weights ``p`` hold only the E_local = E/ep experts this shard
    owns.  Tokens route to *global* expert ids; dispatch buffers are
    exchanged with ``all_to_all`` (tokens travel to their expert's owner),
    experts run locally (FFN width still tensor-parallel over ``model``
    via GSPMD auto), and a reverse all_to_all brings outputs home.
    Autodiff works because all_to_all transposes to itself reversed.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    ep = jax.lax.axis_size(axis)
    E_loc = p["w_gate"].shape[0]           # local experts
    assert E_loc * ep == E, (E_loc, ep, E)
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # router replicated
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # capacity per (owner shard, local expert) on THIS shard's tokens
    cap = int(max(K, round(T * K / E * cfg.capacity_factor)))
    cap = min(cap, T)
    ef = eidx.reshape(-1)                                    # (T*K,) global ids
    onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    mypos = jnp.take_along_axis(pos, ef[:, None], axis=1)[:, 0]
    keep = mypos < cap

    owner = ef // E_loc
    e_loc = ef % E_loc
    xe = jnp.repeat(xt, K, axis=0)
    disp = jnp.zeros((ep, E_loc, cap, d), x.dtype)
    disp = disp.at[jnp.where(keep, owner, 0), jnp.where(keep, e_loc, 0),
                   jnp.where(keep, mypos, 0)].add(
        jnp.where(keep[:, None], xe, 0).astype(x.dtype), mode="drop")

    # exchange: dim0 indexes the destination shard; after the all_to_all it
    # indexes the source shard (each shard now holds every shard's tokens
    # for its own local experts)
    recv = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=0,
                              tiled=False)                   # (ep, E_loc, cap, d)
    ein = jnp.moveaxis(recv, 0, 1).reshape(E_loc, ep * cap, d)

    h = jnp.einsum("ecd,edf->ecf", ein, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", ein, p["w_up"])
    h = jax.nn.silu(h) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])       # (E_loc, ep*cap, d)

    back = jnp.moveaxis(out_e.reshape(E_loc, ep, cap, d), 1, 0)
    got_all = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                 tiled=False)                # (ep, E_loc, cap, d)

    got = got_all[jnp.where(keep, owner, 0), jnp.where(keep, e_loc, 0),
                  jnp.where(keep, mypos, 0)]
    got = jnp.where(keep[:, None], got, 0)
    y = (got.reshape(T, K, d) * gate[..., None].astype(x.dtype)).sum(axis=1)

    frac = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(0, 1))
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * pmean) * cfg.router_aux_coef

    if "shared" in p:
        y = y + layers.mlp(p["shared"], xt, "swiglu")
    return y.reshape(B, S, d), aux
