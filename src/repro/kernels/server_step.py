"""Fused Pallas kernels for the FetchSGD server update.

``repro.core.fetchsgd.server_step`` is sketch algebra — merge, momentum,
error accumulation, top-k extraction bookkeeping — and as separate jnp
ops every phase round-trips the (rows, cols) table through HBM.  The two
kernels here fuse the phases around the top-k selection (which stays in
XLA: ``lax.top_k`` over per-chunk estimate candidates):

* :func:`momentum_error` — ``su' = rho * su + S_agg`` and
  ``se' = lr * su' + se`` in one call: five table reads/writes instead of
  eight, no intermediate tables materialized.
* :func:`topk_mask` — given the extracted ids, builds the hit-cell table
  **once** via the same MXU one-hot contraction as the encode kernel
  (``O^T @ L`` per sketch row, O = outer-index one-hot, L = lane one-hot)
  and applies error zeroing (paper Sec. 5) or sparse re-sketch
  subtraction (Alg. 1 line 14) *and* momentum factor masking in the same
  pass — the unfused path hashed the id set twice and swept the tables
  with two separate ``where``s.

Both kernels keep every table VMEM-resident across the grid (constant
out-block index maps), so the sketch never bounces through HBM between
phases.  ``momentum_error_jnp`` / ``topk_mask_jnp`` are the same algebra
as plain jnp — op-for-op what the unfused reference does, so the fused
jnp path is bitwise identical to it (pinned in
``tests/test_server_step.py``); the Pallas path is allclose-validated at
the edge shapes in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import count_sketch as cs
from repro.core import hashing
from repro.obs import layers

from .count_sketch import (BLOCK_ROWS, HIGHEST, LANES, SMEM_SPEC, U32,
                           lane_dense, onehots_t, scatter_tile)


# -- jnp reference algebra (bitwise = the unfused server_step) ---------------

def momentum_error_jnp(agg: jax.Array, su: jax.Array, se: jax.Array,
                       lr, momentum: float) -> tuple[jax.Array, jax.Array]:
    su2 = momentum * su + agg
    se2 = lr * su2 + se
    return su2, se2


def topk_mask_jnp(su: jax.Array, se: jax.Array, hi: jax.Array, lo: jax.Array,
                  values: jax.Array, key: int = 0, *, error_mode: str = "zero",
                  momentum_masking: bool = True
                  ) -> tuple[jax.Array, jax.Array]:
    rows, cols = su.shape
    mask = None
    if error_mode == "zero" or momentum_masking:
        # the one hit-mask serves both error zeroing and momentum masking —
        # the ids hash identically for both (same (hi, lo), same key)
        mask = cs.hit_mask_ids(hi, lo, rows, cols, key)
    if error_mode == "zero":
        se = jnp.where(mask, 0.0, se)
    else:
        se = se - cs.sketch_sparse(hi, lo, values, rows, cols, key)
    if momentum_masking:
        su = jnp.where(mask, 0.0, su)
    return su, se


# -- Pallas kernels ----------------------------------------------------------

def _momentum_error_kernel(lr_ref, agg_ref, su_ref, se_ref, su_out, se_out, *,
                           momentum: float):
    su = momentum * su_ref[...] + agg_ref[...]
    su_out[...] = su
    se_out[...] = lr_ref[0] * su + se_ref[...]


def momentum_error(agg: jax.Array, su: jax.Array, se: jax.Array, lr,
                   momentum: float, *, interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused ``(rho*su + agg, lr*(rho*su + agg) + se)`` — one Pallas call.

    Gridless: the dispatcher's VMEM gate (``ops._fused_ok``) admits only
    tables whose five live buffers fit on-chip, so no column blocking is
    needed.  ``lr`` may be a traced scalar (the train step's schedule).
    """
    rows, cols = agg.shape
    if cols % LANES != 0:
        raise ValueError(f"fused server step needs cols % {LANES} == 0, "
                         f"got {cols}")
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    out_sds = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_momentum_error_kernel, momentum=momentum),
        in_specs=[SMEM_SPEC, vmem, vmem, vmem],
        out_shape=(out_sds, out_sds),
        interpret=interpret,
        name=layers.MOMENTUM_ERROR_KERNEL,
    )(lr_arr, agg.astype(jnp.float32), su.astype(jnp.float32),
      se.astype(jnp.float32))


def _topk_mask_kernel(hi_ref, lo_ref, val_ref, su_ref, se_ref,
                      su_out, se_out, hit_out, delta_out, *, rows: int,
                      cols: int, key: int, k: int,
                      error_mode: str, momentum_masking: bool,
                      n_blocks: int):
    pid = pl.program_id(0)
    need_hit = error_mode == "zero" or momentum_masking
    need_delta = error_mode == "subtract"

    @pl.when(pid == 0)
    def _init():
        hit_out[...] = jnp.zeros_like(hit_out)
        delta_out[...] = jnp.zeros_like(delta_out)

    # padded id slots must not hash: zero their one-hot columns entirely
    shape = (BLOCK_ROWS, LANES)
    slot = (pid * BLOCK_ROWS * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    valid = (slot < k).astype(jnp.float32)
    hi = hi_ref[...]
    lo = lo_ref[...]
    v = val_ref[...].astype(jnp.float32)
    c_outer = cols // LANES
    for j in range(rows):
        idx = hashing.bucket_hash(lo, hi, j, cols, key)
        sv = hashing.sign_hash(lo, hi, j, key) * v
        hit = delta = None
        for r in range(BLOCK_ROWS):
            o_t, l_t = onehots_t(idx[r:r + 1, :], c_outer)
            o_t = o_t * valid[r:r + 1, :]                          # (C_o, 128)
            if need_hit:
                t = scatter_tile(o_t, l_t)
                hit = t if hit is None else hit + t
            if need_delta:
                t = scatter_tile(o_t, l_t * sv[r:r + 1, :], HIGHEST)
                delta = t if delta is None else delta + t
        if need_hit:
            hit_out[j, :, :] += hit
        if need_delta:
            delta_out[j, :, :] += delta

    @pl.when(pid == n_blocks - 1)
    def _apply():
        se = se_ref[...]
        if error_mode == "zero":
            se = jnp.where(hit_out[...] > 0, 0.0, se)
        else:
            se = se - delta_out[...]
        se_out[...] = se
        su = su_ref[...]
        if momentum_masking:
            su = jnp.where(hit_out[...] > 0, 0.0, su)
        su_out[...] = su


def topk_mask(su: jax.Array, se: jax.Array, hi: jax.Array, lo: jax.Array,
              values: jax.Array, key: int = 0, *, error_mode: str = "zero",
              momentum_masking: bool = True,
              interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused post-extraction update — one Pallas call over the id blocks.

    Accumulates the hit-count table (and, for ``error_mode='subtract'``,
    the S(Delta) table) across the grid in VMEM-resident out buffers, then
    the final grid step applies zeroing/subtraction to ``se`` and masking
    to ``su`` in place — the tables are read and written exactly once.
    The ids and values arrive lane-dense, as ``(k_pad / 128, 128)`` views.
    """
    rows, cols = su.shape
    if cols % LANES != 0:
        raise ValueError(f"fused server step needs cols % {LANES} == 0, "
                         f"got {cols}")
    if error_mode not in ("zero", "subtract"):
        raise ValueError(f"bad error_mode {error_mode}")
    k = hi.shape[0]
    if k == 0:
        # no extracted ids: nothing hits, nothing is subtracted.
        return su.astype(jnp.float32), se.astype(jnp.float32)
    hi2 = lane_dense(hi.astype(U32))
    lo2 = lane_dense(lo.astype(U32))
    v2 = lane_dense(values.astype(jnp.float32))
    n_blocks = hi2.shape[0] // BLOCK_ROWS
    c_outer = cols // LANES
    table_sds = jax.ShapeDtypeStruct((rows, c_outer, LANES), jnp.float32)
    table_spec = pl.BlockSpec((rows, c_outer, LANES), lambda i: (0, 0, 0))
    id_spec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    su_o, se_o, _, _ = pl.pallas_call(
        functools.partial(_topk_mask_kernel, rows=rows, cols=cols, key=key,
                          k=k, error_mode=error_mode,
                          momentum_masking=momentum_masking,
                          n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[id_spec, id_spec, id_spec, table_spec, table_spec],
        out_specs=(table_spec, table_spec, table_spec, table_spec),
        out_shape=(table_sds, table_sds, table_sds, table_sds),
        interpret=interpret,
        name=layers.TOPK_MASK_KERNEL,
    )(hi2, lo2, v2,
      su.astype(jnp.float32).reshape(rows, c_outer, LANES),
      se.astype(jnp.float32).reshape(rows, c_outer, LANES))
    return su_o.reshape(rows, cols), se_o.reshape(rows, cols)
