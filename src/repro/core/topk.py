"""Distributed-friendly Top-k over the flat parameter space.

FetchSGD's weight update is ``Delta = Top-k(U(S_e))`` — the k largest
|estimate| coordinates of the error-accumulation sketch, over all d global
element ids.  Rather than materializing the d-vector of estimates (d
reaches 4e11), the layout's uniform chunk groups are scanned: per-chunk
estimates reduce to per-chunk candidates, then one exact top-k over the
candidate pool selects the winners.

Exactness: when every chunk contributes ``k`` candidates (small layouts —
all tests and the paper-scale models), the result is exactly
Top-k(U(S_e)).  Layouts with many chunks cap the per-chunk candidate count
(``_chunk_k``) — the standard distributed top-k relaxation; a miss
requires more than cap of the global top-k to concentrate in one 64M-
element chunk.  The cap and its rationale are reported in DESIGN.md.

The result is a fixed-size sparse update — ``(chunk_id, local_idx,
value)`` triples — applied shard-locally: expert-parallel chunks carry an
``owner`` and only that data shard's slice is touched.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing
from . import layout as layout_lib
from repro.obs import layers

EXACT_CHUNK_LIMIT = 64   # <= this many chunks: keep per-chunk k exact


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseDelta:
    """k-sparse update over the global flat parameter space."""

    chunk_id: jax.Array   # (k,) int32 — index into layout.chunks
    local_idx: jax.Array  # (k,) int32 — element offset within the chunk
    values: jax.Array     # (k,) float32
    k: int = dataclasses.field(metadata=dict(static=True))


def _chunk_k(k: int, chunk_size: int, num_chunks: int) -> int:
    if num_chunks <= EXACT_CHUNK_LIMIT:
        return min(k, chunk_size)
    return min(k, chunk_size, max(512, (4 * k) // num_chunks))


def topk_from_sketch(table: jax.Array, layout: layout_lib.ParamLayout,
                     k: int, key: int = 0, *,
                     impl: str = "auto") -> SparseDelta:
    """Top-|.|-k of U(table) over the whole layout (scanned unsketch).

    ``impl`` selects the row-estimate kernel (``repro.kernels.ops``): the
    per-chunk U(.) gather is the decode hot spot, so the Pallas estimate
    kernel slots in here while the candidate ``lax.top_k`` stays XLA.

    Traced under the ``topk`` scope, the row estimate under ``unsketch``
    inside it (``repro.obs.layers``).
    """
    from repro.kernels import ops as kernel_ops
    with jax.named_scope(layers.TOPK):
        rows, cols = table.shape
        nall = layout.num_chunks
        cand_vals, cand_local, cand_chunk = [], [], []
        for g in layout.groups:
            size = g.n_rows * g.row_len
            kk = _chunk_k(k, size, nall)
            offs = [layout.chunks[ci].offset for ci in g.chunk_ids]
            lo_t, hi_t = hashing.offset_words(offs)
            cid_t = jnp.asarray(g.chunk_ids, jnp.int32)

            def body(off):
                lo, hi, cid = off
                with jax.named_scope(layers.UNSKETCH):
                    est = kernel_ops.sketch_estimate_words(
                        table, lo, hi, size, key, impl=impl)
                _, idx = jax.lax.top_k(jnp.abs(est), kk)
                return (est[idx], idx.astype(jnp.int32),
                        jnp.full((kk,), cid, jnp.int32))

            v, li, ci = jax.lax.map(body, (lo_t, hi_t, cid_t))
            cand_vals.append(v.reshape(-1))
            cand_local.append(li.reshape(-1))
            cand_chunk.append(ci.reshape(-1))
        vals = jnp.concatenate(cand_vals)
        local = jnp.concatenate(cand_local)
        chunk = jnp.concatenate(cand_chunk)
        k_eff = min(k, int(vals.shape[0]))
        _, sel = jax.lax.top_k(jnp.abs(vals), k_eff)
        return SparseDelta(chunk_id=chunk[sel], local_idx=local[sel],
                           values=vals[sel], k=k_eff)


def topk_dense(acc_views: list, layout: layout_lib.ParamLayout,
               k: int) -> SparseDelta:
    """Exact top-k of a *dense* accumulator (local top-k / true top-k)."""
    nall = layout.num_chunks
    cand_vals, cand_local, cand_chunk = [], [], []
    for g in layout.groups:
        size = g.n_rows * g.row_len
        kk = _chunk_k(k, size, nall)
        starts = jnp.asarray([layout.chunks[ci].row_start
                              for ci in g.chunk_ids], jnp.int32)
        cid_t = jnp.asarray(g.chunk_ids, jnp.int32)
        view = acc_views[g.leaf]

        def body(xs):
            rs, cid = xs
            vals = jax.lax.dynamic_slice_in_dim(
                view, rs, g.n_rows, axis=0).reshape(-1).astype(jnp.float32)
            _, idx = jax.lax.top_k(jnp.abs(vals), kk)
            return vals[idx], idx.astype(jnp.int32), jnp.full((kk,), cid,
                                                              jnp.int32)

        v, li, ci = jax.lax.map(body, (starts, cid_t))
        cand_vals.append(v.reshape(-1))
        cand_local.append(li.reshape(-1))
        cand_chunk.append(ci.reshape(-1))
    vals = jnp.concatenate(cand_vals)
    local = jnp.concatenate(cand_local)
    chunk = jnp.concatenate(cand_chunk)
    k_eff = min(k, int(vals.shape[0]))
    _, sel = jax.lax.top_k(jnp.abs(vals), k_eff)
    return SparseDelta(chunk_id=chunk[sel], local_idx=local[sel],
                       values=vals[sel], k=k_eff)


def apply_delta(params, layout: layout_lib.ParamLayout, delta: SparseDelta,
                scale=1.0, shard_idx=None, local: bool = False,
                view_shardings: list | None = None):
    """params <- params - scale * Delta (scatter-sub, scanned per group).

    ``local=True``: params are the shard-local views (EP leaves sliced);
    chunks owned by other shards are masked out via ``shard_idx``.
    ``view_shardings``: optional per-leaf NamedSharding of the 2-D views —
    constrains the scan carry so GSPMD keeps big leaves sharded.
    """
    views = layout_lib.leaf_views(params, layout, local=local)

    def constrain(leaf_idx, v):
        if view_shardings is not None and view_shardings[leaf_idx] is not None:
            return jax.lax.with_sharding_constraint(v,
                                                    view_shardings[leaf_idx])
        return v

    for g in layout.groups:
        chs = [layout.chunks[ci] for ci in g.chunk_ids]
        cid_t = jnp.asarray(g.chunk_ids, jnp.int32)
        starts = jnp.asarray([ch.lrs if local else ch.row_start
                              for ch in chs], jnp.int32)
        owners = jnp.asarray([-1 if ch.owner is None else ch.owner
                              for ch in chs], jnp.int32)
        row_len = g.row_len
        n_rows = g.n_rows

        def body(view, xs):
            # Scatter into a small REPLICATED dense chunk, then do a sharded
            # elementwise add: scattering straight into the (model-sharded)
            # view would force GSPMD to replicate the whole leaf.
            cid, rs, owner = xs
            mine = delta.chunk_id == cid
            if shard_idx is not None:
                mine &= (owner < 0) | (owner == shard_idx)
            vals = jnp.where(mine, delta.values, 0.0) * (-scale)
            idx = jnp.where(mine, delta.local_idx, 0)
            dense = jnp.zeros((n_rows * row_len,), jnp.float32)
            dense = dense.at[idx].add(vals, mode="drop")
            dense = dense.reshape(n_rows, row_len).astype(view.dtype)
            cur = jax.lax.dynamic_slice_in_dim(view, rs, n_rows, axis=0)
            new = jax.lax.dynamic_update_slice_in_dim(
                view, cur + dense, rs, axis=0)
            return constrain(g.leaf, new), None

        views[g.leaf], _ = jax.lax.scan(body, constrain(g.leaf, views[g.leaf]),
                                        (cid_t, starts, owners))
    return layout_lib.unview(views, layout, local=local)


def densify(delta: SparseDelta, layout: layout_lib.ParamLayout) -> jax.Array:
    """Materialize the sparse delta as the full flat d-vector (tests only)."""
    offs = np.asarray([ch.offset for ch in layout.chunks], np.int64)
    gidx = jnp.asarray(offs)[delta.chunk_id] + delta.local_idx
    flat = jnp.zeros((layout.total,), jnp.float32)
    return flat.at[gidx].add(delta.values)


def global_ids(delta: SparseDelta, layout: layout_lib.ParamLayout):
    """(hi, lo) uint32 word pairs of the extracted global element ids."""
    lo_t, hi_t = hashing.offset_words([ch.offset for ch in layout.chunks])
    lo = lo_t[delta.chunk_id] + delta.local_idx.astype(jnp.uint32)
    carry = (lo < lo_t[delta.chunk_id]).astype(jnp.uint32)
    hi = hi_t[delta.chunk_id] + carry
    return hi, lo
