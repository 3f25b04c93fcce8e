"""Plain float32 reference of the dense decoder, independent of the program.

RMSNorm, rotary attention with grouped KV heads, a GELU or SwiGLU MLP and
an untied output head; its mean next-token loss, gradient and counts.  A
configuration names its model reference by ``"reference"``, and the
harness loads ``models/<reference>.py``.  Every such module gives:

* ``param_shapes(cfg) -> {leaf path: (shape, init std or None)}`` in the
  program's tree order (sorted paths); None is a norm scale of ones;
* ``loss_and_grad(p, tokens, labels, cfg, q=None) -> (mean loss, grads)``
  over the whole ``(B, S)`` batch, in blocks that fit on one chip; a
  module with terms over the batch computes them over the batch.  ``q``
  rounds the operands of every matmul, forward and backward
  (``reference.rounded_einsum``), so that the controls reach them;
* ``flops_per_token(cfg, seq_len) -> int``: forward and backward, over
  the weights a token actually multiplies (for sparse experts: the routed
  top-k and the shared experts, not every expert).

``cfg`` is the configuration's ``model`` object.  A module imports nothing
of the program; ``reference.init_params`` makes the weights from
``param_shapes``, and a module may import other modules of ``models/``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference import rounded_einsum


def param_shapes(cfg: dict) -> dict:
    """Leaf path -> (shape, init std; None for a norm scale of ones)."""
    d, L, V, ff = cfg["d_model"], cfg["n_layers"], cfg["vocab"], cfg["d_ff"]
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    out = {
        "embed/table": ((V, d), 0.02),
        "final_norm/scale": ((d,), None),
        "unembed/w": ((d, V), d ** -0.5),
        "units/m0/attn/wq": ((L, d, H, hd), d ** -0.5),
        "units/m0/attn/wk": ((L, d, KV, hd), d ** -0.5),
        "units/m0/attn/wv": ((L, d, KV, hd), d ** -0.5),
        "units/m0/attn/wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "units/m0/mlp/w_up": ((L, d, ff), d ** -0.5),
        "units/m0/mlp/w_down": ((L, ff, d), ff ** -0.5),
        "units/m0/norm1/scale": ((L, d), None),
        "units/m0/norm2/scale": ((L, d), None),
    }
    if cfg["act"] == "swiglu":
        out["units/m0/mlp/w_gate"] = ((L, d, ff), d ** -0.5)
    return dict(sorted(out.items()))


def non_embedding_params(cfg: dict) -> int:
    """N of 6·N·T: every weight but the input embedding (the head counts)."""
    return sum(math.prod(shape) for path, (shape, _) in
               param_shapes(cfg).items() if path != "embed/table")


def flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward: 6·N plus 12·L·S·(heads·head_dim) for the
    attention scores and mixing, counted over the full S x S square as the
    model computes it."""
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    return (6 * non_embedding_params(cfg)
            + 12 * cfg["n_layers"] * seq_len * cfg["n_heads"] * hd)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding, halves rotated (x: (S, H, hd))."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def seq_loss(p: dict, tokens, labels, cfg: dict, q=None):
    """Mean next-token cross entropy of one sequence (tokens: (S,)).

    ``q`` rounds the operands of every matmul, forward and backward, so
    that a lower precision can be put in (None: float32 as given)."""
    mm = jnp.einsum if q is None else functools.partial(rounded_einsum, q)

    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    x = p["embed"]["table"][tokens]
    S, d = x.shape
    hd = cfg.get("head_dim") or d // H
    mask = jnp.tril(jnp.ones((S, S), bool))
    u = p["units"]["m0"]
    for layer in range(cfg["n_layers"]):
        a = jax.tree.map(lambda w: w[layer], u)
        h = _rmsnorm(x, a["norm1"]["scale"], eps)
        qh = _rope(mm("sd,dhe->she", h, a["attn"]["wq"]), theta)
        k = _rope(mm("sd,dhe->she", h, a["attn"]["wk"]), theta)
        v = mm("sd,dhe->she", h, a["attn"]["wv"])
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = mm("qhe,khe->hqk", qh, k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = mm("hqk,khe->qhe", w, v)
        x = x + mm("qhe,hed->qd", o, a["attn"]["wo"])
        h = _rmsnorm(x, a["norm2"]["scale"], eps)
        mlp = a["mlp"]
        up = mm("sd,df->sf", h, mlp["w_up"])
        if cfg["act"] == "swiglu":
            g = jax.nn.silu(mm("sd,df->sf", h, mlp["w_gate"])) * up
        else:
            g = jax.nn.gelu(up, approximate=True)
        x = x + mm("sf,fd->sd", g, mlp["w_down"])
    h = _rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = mm("sd,dv->sv", h, p["unembed"]["w"])
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def loss_and_grad(p: dict, tokens, labels, cfg: dict, q=None):
    """Mean loss and gradient over a batch (B, S), one sequence at a time.

    Every sequence has the same length, so the batch mean is the mean of
    the sequences' means."""
    step = jax.value_and_grad(seq_loss)

    def body(acc, tl):
        loss, g = step(p, tl[0], tl[1], cfg, q)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
    (loss, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda x: x / n, g)
