"""Plain float32 reference of one FetchSGD round, independent of the program.

Everything here follows the published descriptions, written out in
straightforward ``jax.numpy`` at ``highest`` matmul precision.  The model
is the configuration's own: a module of ``models/`` that gives its weights'
shapes, its loss and gradient, and its counts (the contract is in
``models/decoder.py``).  This module holds what every model shares:

* the seeded float32 weights from the model's shapes, and the quantizers
  that the controls put into every model's matmuls;
* the Count Sketch of the gradient over the flat parameter vector, whose
  element ids run through the leaves in sorted-key order, row-major; the
  hash family is murmur3's fmix32 over the two 32-bit words of the id;
* FetchSGD's server (arXiv:2007.07682, Algorithm 1 with the paper's
  practical variant): momentum and error in sketch space, the top-k of the
  median-of-rows estimate, the hit cells zeroed in both sketches, and the
  k-sparse update subtracted from the weights.

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`init_params` with the benchmark's seed.  Work runs
in blocks (the model's, and one slice of ids at a time) so that it fits
next to nothing else on one chip.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 22          # ids per block of the sketch, estimate and top-k
U32 = jnp.uint32
_ROW_SEEDS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1,
              0xD3A2646C, 0xFD7046C5, 0xB55A4F09, 0x8F1BBCDC, 0xCA62C1D6)


# -- weights ------------------------------------------------------------------

def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def seed_key(seed: int):
    """A PRNG key for any whole seed, also one wider than 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def init_params(model, cfg: dict, key) -> dict:
    """Seeded float32 weights of ``model``'s leaves, as a nested dict."""
    flat = {}
    for i, (path, (shape, std)) in enumerate(
            model.param_shapes(cfg).items()):
        if std is None:
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            flat[path] = std * jax.random.normal(jax.random.fold_in(key, i),
                                                 shape, jnp.float32)
    return _nest(flat)


def leaves(tree: dict) -> list:
    """Leaves in sorted-key order (the order of the flat id space)."""
    return jax.tree_util.tree_leaves(tree)


# -- quantizers ---------------------------------------------------------------

def rounded_einsum(q, spec, a, b):
    """``einsum`` whose operands are rounded by ``q``, and whose gradients
    are einsums of rounded operands too."""
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, q(a), q(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        _, vjp = jax.vjp(functools.partial(jnp.einsum, spec), *map(q, res))
        return vjp(q(g))

    f.defvjp(fwd, bwd)
    return f(a, b)


def int8(a):
    """``a`` rounded to int8 with one absmax scale: matmul operands so
    rounded, with exact sums, are the step below bfloat16."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.round(a / s) * s


def bf16(a):
    """``a`` rounded to bfloat16: what one pass of the MXU (``default``
    precision) makes of a float32 operand."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


# -- hash family over 64-bit element ids ----------------------------------------

def _fmix32(h):
    h = h ^ (h >> U32(16))
    h = h * U32(0x85EBCA6B)
    h = h ^ (h >> U32(13))
    h = h * U32(0xC2B2AE35)
    return h ^ (h >> U32(16))


def _hash(lo, hi, seed: int):
    h = _fmix32(lo ^ U32(seed))
    return _fmix32(h ^ hi ^ U32((seed * 0x9E3779B9 + 1) & 0xFFFFFFFF))


def buckets_signs(lo, hi, row: int, cols: int, key: int = 0):
    """Bucket in [0, cols) and sign in {-1, +1} of each id, for one row."""
    b_seed = _ROW_SEEDS[row % 10] ^ ((key * 0x632BE59B) & 0xFFFFFFFF)
    s_seed = ((_ROW_SEEDS[(row + 3) % 10] * 0x9E3779B9)
              ^ (key * 0x85EBCA6B)) & 0xFFFFFFFF
    bucket = (_hash(lo, hi, b_seed) % U32(cols)).astype(jnp.int32)
    sign = jnp.where(_hash(lo, hi, s_seed) >> U32(31) == 0, 1.0, -1.0)
    return bucket, sign.astype(jnp.float32)


def _add(lo, hi, x):
    """(lo, hi) + x for uint32 words, with the carry."""
    s = lo + x
    return s, hi + (s < lo).astype(U32)


def _block(n: int) -> int:
    """Ids per block of a leaf of ``n`` elements."""
    return min(BLOCK, -(-n // 1024) * 1024)


def _block_ids(lo0, hi0, b, block: int):
    """Id words of block ``b`` of a leaf whose first id is (lo0, hi0)."""
    lo, hi = _add(lo0, hi0, b.astype(U32) * U32(block))
    return _add(lo, hi, jnp.arange(block, dtype=U32))


@functools.partial(jax.jit, static_argnames=("rows", "cols"))
def _sketch_leaf(table, flat, lo0, hi0, *, rows, cols):
    flat = flat.reshape(-1)
    block = _block(flat.shape[0])
    nb = -(-flat.shape[0] // block)
    blocks = jnp.pad(flat, (0, nb * block - flat.shape[0])).reshape(nb, block)

    def body(tbl, xs):
        b, vals = xs
        lo, hi = _block_ids(lo0, hi0, b, block)
        for j in range(rows):
            bucket, sign = buckets_signs(lo, hi, j, cols)
            tbl = tbl.at[j, bucket].add(sign * vals, mode="promise_in_bounds")
        return tbl, None

    table, _ = jax.lax.scan(body, table, (jnp.arange(nb), blocks))
    return table


@functools.partial(jax.jit, static_argnames=("n", "k"))
def _topk_leaf(table, lo0, hi0, *, n, k):
    """Per block of one leaf: the k largest |estimates| and their offsets."""
    rows, cols = table.shape
    block = _block(n)

    def body(b):
        lo, hi = _block_ids(lo0, hi0, b, block)
        est = []
        for j in range(rows):
            bucket, sign = buckets_signs(lo, hi, j, cols)
            est.append(sign * table[j].at[bucket].get(
                mode="promise_in_bounds"))
        est = median(est)
        off = b * block + jnp.arange(block)
        _, idx = jax.lax.top_k(jnp.where(off < n, jnp.abs(est), -1.0), k)
        return est[idx], off[idx]

    return jax.lax.map(body, jnp.arange(-(-n // block)))


def median(xs: list):
    """Elementwise median of an odd number of arrays: a min/max network
    that puts them in order (odd-even transposition)."""
    xs = list(xs)
    for p in range(len(xs)):
        for i in range(p % 2, len(xs) - 1, 2):
            xs[i], xs[i + 1] = (jnp.minimum(xs[i], xs[i + 1]),
                                jnp.maximum(xs[i], xs[i + 1]))
    return xs[len(xs) // 2]


def _words(gid: int):
    return U32(gid & 0xFFFFFFFF), U32(gid >> 32)


def _starts(sizes):
    return np.cumsum([0] + list(sizes[:-1])).tolist()


def sketch(grads: list, rows: int, cols: int):
    """The (rows, cols) Count Sketch of the flat gradient."""
    table = jnp.zeros((rows, cols), jnp.float32)
    for g, gid in zip(grads, _starts([g.size for g in grads])):
        table = _sketch_leaf(table, g, *_words(gid), rows=rows, cols=cols)
    return table


def topk(table, sizes: list, k: int):
    """Global ids (int64) and values of the k largest |median estimates|."""
    vals, ids = [], []
    for n, gid in zip(sizes, _starts(sizes)):
        v, off = _topk_leaf(table, *_words(gid), n=n, k=min(k, _block(n)))
        vals.append(v.reshape(-1))
        ids.append(np.asarray(off, np.int64).reshape(-1) + gid)
    vals = jnp.concatenate(vals)
    _, sel = jax.lax.top_k(jnp.abs(vals), k)
    sel = np.asarray(sel)
    return np.concatenate(ids)[sel], vals[sel]


def hit_mask(ids: np.ndarray, rows: int, cols: int):
    """(rows, cols) cells that any of the extracted ids hash into."""
    lo = jnp.asarray(ids & 0xFFFFFFFF, U32)
    hi = jnp.asarray(ids >> 32, U32)
    mask = jnp.zeros((rows, cols), bool)
    for j in range(rows):
        b, _ = buckets_signs(lo, hi, j, cols)
        mask = mask.at[j, b].set(True)
    return mask


@jax.jit
def _sub_at(w, idx, vals):
    flat = w.reshape(-1).at[idx].add(-vals, mode="drop")
    return flat.reshape(w.shape)


def apply_update(params: list, ids: np.ndarray, vals) -> list:
    """w <- w - Delta at the extracted global ids."""
    out, gid = [], 0
    for w in params:
        rel = ids - gid
        idx = np.where((rel >= 0) & (rel < w.size), rel, w.size)
        out.append(_sub_at(w, jnp.asarray(idx, jnp.int32), vals))
        gid += w.size
    return out


def fetchsgd_rounds(params: dict, batches: list, model, cfg: dict,
                    tr: dict, q=None, sketch_q=None, log=None) -> dict:
    """Run the reference through ``len(batches)`` rounds from ``params``.

    Each batch is the whole cohort's ``(tokens, labels)``; its mean gradient
    is the merge of the clients' sketches, by the sketch's linearity.
    ``model.loss_and_grad`` gives it, with ``q`` rounding the model's
    matmul operands; ``sketch_q`` rounds the values that the sketch's
    encode and estimate contract: the gradient, and the error sketch the
    estimate reads.
    Returns the losses, the momentum sketch after the first round and the
    weights after the last; ``log`` gets each phase's seconds."""
    rows, cols, k = tr["rows"], tr["cols"], tr["k"]
    if rows % 2 == 0:
        raise ValueError("the reference takes the median of an odd rows")
    if tr["error_mode"] != "zero":
        raise ValueError("the reference implements error_mode 'zero' only")
    treedef = jax.tree_util.tree_structure(params)
    cur = leaves(params)
    sizes = [w.size for w in cur]
    su = se = jnp.zeros((rows, cols), jnp.float32)
    grad_fn = jax.jit(functools.partial(model.loss_and_grad, cfg=cfg, q=q))
    losses, su_first = [], None
    clock = _Clock(log)
    with jax.default_matmul_precision("highest"):
        for tokens, labels in batches:
            p = jax.tree_util.tree_unflatten(treedef, cur)
            loss, g = grad_fn(p, jnp.asarray(tokens), jnp.asarray(labels))
            losses.append(float(loss))
            clock("loss and gradient")
            grads = leaves(g)
            if sketch_q is not None:
                grads = [sketch_q(x) for x in grads]
            table = sketch(grads, rows, cols)
            del g, grads
            su = tr["momentum"] * su + table
            se = tr["lr"] * su + se
            clock("sketch", table)
            ids, vals = topk(se if sketch_q is None else sketch_q(se),
                             sizes, k)
            clock("estimate and top-k", vals)
            mask = hit_mask(ids, rows, cols)
            se = jnp.where(mask, 0.0, se)
            if tr["momentum_masking"]:
                su = jnp.where(mask, 0.0, su)
            if su_first is None:
                su_first = np.asarray(su)
            cur = apply_update(cur, ids, vals)
            clock("mask and apply", cur)
    if log:
        log(f"reference seconds by phase {clock.sums}")
    return {"losses": losses, "su_first": su_first,
            "params": jax.tree_util.tree_unflatten(treedef, cur)}


class _Clock:
    """Seconds of each phase, waited for, summed over the rounds."""

    def __init__(self, log):
        self.log, self.t, self.sums = log, time.perf_counter(), {}

    def __call__(self, phase: str, wait=None):
        if self.log is None:
            return
        jax.block_until_ready(wait)
        now = time.perf_counter()
        self.sums[phase] = self.sums.get(phase, 0.0) + now - self.t
        self.t = now
