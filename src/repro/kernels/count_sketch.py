"""Pallas TPU kernels for Count Sketch encode / decode.

TPU adaptation: GPU count-sketch kernels rely on atomic scatter-add in
HBM; the TPU has no atomics, and per-element dynamic stores defeat the
VPU's 8x128 vector lanes.  We restructure both directions around the MXU:

* **layout**: a chunk of ``n`` values reaches the kernel as a lane-dense
  ``(rows_pad, 128)`` view, zero-padded to whole ``(BLOCK_ROWS, 128)``
  tiles.  The block shape is then fixed by the TPU's (8, 128) tile and
  not by XLA's tiling of the 1-D operand, which changes with ``n``.
* **encode**: split the bucket index as ``idx = (outer, lane) =
  (idx // 128, idx % 128)``.  For one 128-element row of the block build
  the transposed one-hot outer matrix ``O^T in {0,1}^(C_o x 128)`` and the
  transposed lane-masked value matrix ``VL^T in R^(128 x 128)``, whose
  column ``e`` is ``sign_e * v_e`` at row ``lane_e``; both keep the
  elements on lanes.  The row's contribution to sketch row ``j`` is the
  systolic matmul ``O^T @ VL in R^(C_o x 128)`` — a scatter expressed as
  dense contraction.  The (rows, C_o, 128) accumulator stays resident in
  VMEM across the grid (out-block index map is constant), so HBM sees each
  gradient element exactly once.
* **decode (estimate)**: the gather ``table[j, h_j(i)]`` becomes the same
  one-hot contraction the other way, ``T_j^T @ O^T`` masked by the lane
  one-hot and summed over sublanes, followed by a sort-free median of the
  rows (a min/max network: Mosaic has no sort).

Value contractions keep float32's precision.  The MXU multiplies bf16;
``HIGHEST`` splits each f32 operand into three bf16 pieces, ``a = a1 + a2
+ a3``, and sums the six bf16 passes a1b1, a1b2, a2b1, a1b3, a2b2, a3b1.
In the encode one operand is the 0/1 one-hot, which bf16 holds exactly:
its second and third pieces are zero, and three of those passes multiply
zeros.  So the encode splits only the values (``split_bf16``, exact) and
contracts each piece against the bf16 one-hot in one pass, accumulated in
f32: ``HIGHEST``'s own non-zero products, in half its passes.
``Precision.HIGH`` (a1b1, a1b2, a2b1) would not be this: it keeps two
pieces of the values, about 16 of their 24 bits.  The estimate and the
fused server kernels still contract at ``HIGHEST``.

Hashes (murmur-finalizer over 64-bit ids carried as two uint32 words) are
computed on the fly from ``iota`` — no index tables in HBM, matching
``repro.core.hashing`` bit-for-bit so sketches from the kernel and the jnp
path are interchangeable.

Validated in ``interpret=True`` mode on CPU against ``ref.py``; the
compiled kernels are compiled for a described v5e in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.obs import layers

LANES = 128
BLOCK_ROWS = 8            # sublanes of one f32 tile: 1024 elements per step
U32 = jnp.uint32
HIGHEST = jax.lax.Precision.HIGHEST
# Scalars (offset words, learning rate) live in SMEM, not in a vector tile.
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def lane_dense(x: jax.Array) -> jax.Array:
    """Flatten ``x`` and zero-pad it into a ``(k * BLOCK_ROWS, 128)`` view."""
    x = x.reshape(-1)
    tile = BLOCK_ROWS * LANES
    n_pad = (-x.shape[0]) % tile or (tile if x.shape[0] == 0 else 0)
    if n_pad:
        x = jnp.concatenate([x, jnp.zeros((n_pad,), x.dtype)])
    return x.reshape(-1, LANES)


def block_ids(off_lo, off_hi, start, shape):
    """uint32 (hi, lo) id words of a ``(R, 128)`` block whose element
    ``(r, l)`` is element ``start + r * 128 + l`` of the chunk."""
    r = jax.lax.broadcasted_iota(U32, shape, 0)
    lane = jax.lax.broadcasted_iota(U32, shape, 1)
    i = start.astype(U32) + r * U32(LANES) + lane
    lo = off_lo + i
    # NOTE: start fits in uint32 (chunks are capped at 2**28 elements), so a
    # single carry word is exact.
    hi = off_hi + (lo < off_lo).astype(U32)
    return hi, lo


def onehots_t(idx_row, c_outer: int):
    """Transposed one-hots of one 128-element row of bucket indices:
    ``(C_o, 128)`` outer and ``(128, 128)`` lane, elements on lanes."""
    outer = idx_row // LANES
    lane = idx_row % LANES
    o_t = (jax.lax.broadcasted_iota(jnp.int32, (c_outer, LANES), 0)
           == outer).astype(jnp.float32)
    l_t = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
           == lane).astype(jnp.float32)
    return o_t, l_t


def scatter_tile(o_t, vl_t, precision=None):
    """``O^T @ VL``: contract the element (lane) dim of both -> (C_o, 128)."""
    return jax.lax.dot_general(o_t, vl_t, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def median_rows(xs: list) -> jax.Array:
    """Elementwise median of a static list of arrays, without a sort.

    An odd-even transposition network of min/max puts the values in
    order; an even count averages the middle two like ``jnp.median``.
    """
    xs = list(xs)
    n = len(xs)
    for p in range(n):
        for i in range(p % 2, n - 1, 2):
            xs[i], xs[i + 1] = (jnp.minimum(xs[i], xs[i + 1]),
                                jnp.maximum(xs[i], xs[i + 1]))
    m = n // 2
    if n % 2:
        return xs[m]
    return (xs[m - 1] + xs[m]) * 0.5


def split_bf16(x: jax.Array) -> list:
    """Three bfloat16 pieces of float32 ``x`` whose float32 sum is ``x``.

    Each piece is the top 16 bits (sign, exponent, 7 mantissa bits) of
    what the pieces before it leave.  Truncating, not rounding, means no
    piece can round up past the largest bfloat16, so ``x`` near the float32
    limit splits too; the three pieces hold the 24-bit significand's top,
    middle and last 8 bits, so their sum is exact wherever they are normal
    numbers.  A piece below 2**-126 flushes to zero, as it does in any
    float32 arithmetic that flushes subnormals.
    """
    pieces = []
    for _ in range(3):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, U32) & U32(0xFFFF0000),
            jnp.float32)
        pieces.append(top.astype(jnp.bfloat16))
        x = x - top
    return pieces


def _encode_kernel(off_ref, values_ref, out_ref, *, rows: int, cols: int,
                   key: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    shape = (BLOCK_ROWS, LANES)
    hi, lo = block_ids(off_ref[0], off_ref[1], pid * BLOCK_ROWS * LANES,
                       shape)
    v = values_ref[...].astype(jnp.float32)
    c_outer = cols // LANES
    for j in range(rows):
        idx = hashing.bucket_hash(lo, hi, j, cols, key)
        pieces = [p.astype(jnp.float32)
                  for p in split_bf16(hashing.sign_hash(lo, hi, j, key) * v)]
        acc = None
        for r in range(BLOCK_ROWS):
            o_t, l_t = onehots_t(idx[r:r + 1, :], c_outer)
            o_t = o_t.astype(jnp.bfloat16)
            for p in pieces:
                tile = scatter_tile(o_t, (l_t * p[r:r + 1, :]).astype(
                    jnp.bfloat16))
                acc = tile if acc is None else acc + tile
        out_ref[j, :, :] += acc


def sketch_encode_words(values: jax.Array, off: jax.Array, rows: int,
                        cols: int, key: int = 0, *,
                        interpret: bool = False) -> jax.Array:
    """Pallas encode with a *traced* 64-bit base offset ``off = [lo, hi]``.

    Used by expert-parallel shards (the global offset of the local gradient
    slice depends on the on-device shard index) and by the scanned sketch
    path.  ``cols % 128 == 0``; values zero-padded to whole tiles (zero
    contributions are exact no-ops in the sketch).
    """
    if cols % LANES != 0:
        raise ValueError(f"Pallas encode needs cols % {LANES} == 0, got {cols}")
    v2 = lane_dense(values)
    c_outer = cols // LANES
    out = pl.pallas_call(
        functools.partial(_encode_kernel, rows=rows, cols=cols, key=key),
        grid=(v2.shape[0] // BLOCK_ROWS,),
        in_specs=[
            SMEM_SPEC,
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, c_outer, LANES), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c_outer, LANES), jnp.float32),
        interpret=interpret,
        name=layers.ENCODE_KERNEL,
    )(off.astype(U32), v2)
    return out.reshape(rows, cols)


@functools.partial(jax.jit,
                   static_argnames=("offset", "rows", "cols", "key",
                                    "interpret"))
def sketch_encode(values: jax.Array, offset: int, rows: int, cols: int,
                  key: int = 0, *, interpret: bool = False) -> jax.Array:
    """Pallas count-sketch encode of a 1-D chunk (static offset)."""
    off = jnp.array([offset & 0xFFFFFFFF, offset >> 32], dtype=U32)
    return sketch_encode_words(values, off, rows, cols, key,
                               interpret=interpret)


def _estimate_kernel(off_ref, table_t_ref, out_ref, *, rows: int, cols: int,
                     key: int):
    pid = pl.program_id(0)
    shape = (BLOCK_ROWS, LANES)
    hi, lo = block_ids(off_ref[0], off_ref[1], pid * BLOCK_ROWS * LANES,
                       shape)
    c_outer = cols // LANES
    idxs = [hashing.bucket_hash(lo, hi, j, cols, key) for j in range(rows)]
    sgns = [hashing.sign_hash(lo, hi, j, key) for j in range(rows)]
    for r in range(BLOCK_ROWS):
        ests = []
        for j in range(rows):
            o_t, l_t = onehots_t(idxs[j][r:r + 1, :], c_outer)
            cells = jax.lax.dot_general(
                table_t_ref[j, :, :], o_t, (((1,), (0,)), ((), ())),
                precision=HIGHEST,
                preferred_element_type=jnp.float32)              # (128, 128)
            ests.append(sgns[j][r:r + 1, :]
                        * jnp.sum(cells * l_t, axis=0, keepdims=True))
        out_ref[r:r + 1, :] = median_rows(ests)


def sketch_estimate_words(table: jax.Array, off: jax.Array, n: int,
                          key: int = 0, *,
                          interpret: bool = False) -> jax.Array:
    """Pallas decode with a *traced* 64-bit base offset ``off = [lo, hi]``.

    Used by the scanned unsketch (``repro.core.topk``): chunk offsets are
    selected on-device inside a ``lax.map``, so the base must stay traced.
    """
    rows, cols = table.shape
    if cols % LANES != 0:
        raise ValueError(f"Pallas estimate needs cols % {LANES} == 0, got {cols}")
    c_outer = cols // LANES
    n_blocks = max(1, -(-n // (BLOCK_ROWS * LANES)))
    # (rows, 128, C_o): lanes of the table on sublanes, for an NN matmul
    table_t = table.reshape(rows, c_outer, LANES).transpose(0, 2, 1)
    out = pl.pallas_call(
        functools.partial(_estimate_kernel, rows=rows, cols=cols, key=key),
        grid=(n_blocks,),
        in_specs=[
            SMEM_SPEC,
            pl.BlockSpec((rows, LANES, c_outer), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * BLOCK_ROWS, LANES),
                                       jnp.float32),
        interpret=interpret,
        name=layers.ESTIMATE_KERNEL,
    )(off.astype(U32), table_t.astype(jnp.float32))
    return out.reshape(-1)[:n]


@functools.partial(jax.jit,
                   static_argnames=("offset", "n", "key", "interpret"))
def sketch_estimate(table: jax.Array, offset: int, n: int, key: int = 0, *,
                    interpret: bool = False) -> jax.Array:
    """Pallas decode: median-of-rows estimates for ids offset..offset+n."""
    off = jnp.array([offset & 0xFFFFFFFF, offset >> 32], dtype=U32)
    return sketch_estimate_words(table, off, n, key, interpret=interpret)
