"""FetchSGD — Algorithm 1 of the paper, as a server-side JAX optimizer.

The division of labour follows the paper exactly:

* **clients** (data shards): compute a local stochastic gradient, sketch it
  (``sketch_grads``), upload only the (rows, cols) table.  No client state.
* **aggregator**: sums/means the client tables (a `psum` on the mesh — the
  linearity of the sketch makes this exact), then runs ``server_step``:

      S^t    = mean_i S(g_i^t)
      S_u^t  = rho * S_u^{t-1} + S^t            (momentum, in sketch space)
      S_e^t  = eta * S_u^t + S_e^{t-1}          (error feedback)
      Delta  = Top-k(U(S_e^t))
      S_e    = zero-hit-cells(S_e)   [paper's practical variant]
               or S_e - S(Delta)     [Algorithm 1, line 14]
      S_u    = zero-hit-cells(S_u)   [momentum factor masking, optional]
      w      <- w - Delta

Both error-update variants are implemented; the paper reports that zeroing
"stabilizes the optimization" and uses it in all experiments, so it is the
default here too.  Momentum factor masking (Lin et al., 2017) is on by
default, again matching Sec. 5.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import count_sketch as cs
from . import layout as layout_lib
from . import topk as topk_lib
from repro.kernels import ops as kernel_ops
from repro.obs import layers


@dataclasses.dataclass(frozen=True)
class FetchSGDConfig:
    """Static hyper-parameters of the optimizer."""

    rows: int = 5
    cols: int = 1 << 16
    k: int = 1000
    momentum: float = 0.9
    hash_key: int = 0
    error_mode: str = "zero"        # "zero" (paper practice) | "subtract" (Alg. 1)
    momentum_masking: bool = True
    # sketch kernel dispatch: auto | jnp (alias xla) | pallas (compiled) |
    # pallas-interpret (validation only) — see repro.kernels.ops
    impl: str = "auto"

    def __post_init__(self):
        if self.error_mode not in ("zero", "subtract"):
            raise ValueError(f"bad error_mode {self.error_mode}")
        kernel_ops.normalize_impl(self.impl)   # raise early on a bad name


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FetchSGDState:
    """Server state: everything lives in sketch space (r x c), never O(d)."""

    momentum_sketch: jax.Array  # S_u, (rows, cols)
    error_sketch: jax.Array     # S_e, (rows, cols)
    step: jax.Array             # int32 scalar


def init_state(cfg: FetchSGDConfig) -> FetchSGDState:
    z = jnp.zeros((cfg.rows, cfg.cols), jnp.float32)
    return FetchSGDState(momentum_sketch=z, error_sketch=z,
                         step=jnp.zeros((), jnp.int32))


def sketch_grads(grads, layout: layout_lib.ParamLayout, cfg: FetchSGDConfig,
                 shard_idx=None, local: bool = False,
                 view_shardings=None) -> jax.Array:
    """Client-side compression: S(g) for a gradient pytree.

    Linearity lets each chunk (and each model-parallel / expert-parallel
    slice) contribute an independent partial table; the sum over chunks
    (and the mesh psum over shards) *is* the sketch of the whole flat
    gradient.  Uniform local-chunk groups are scanned so HLO size is
    O(groups); expert-parallel chunks select their global offset from a
    static per-shard table by ``shard_idx`` (``lax.axis_index('data')``).
    """
    from . import hashing
    views = layout_lib.leaf_views(grads, layout, local=local)
    table = jnp.zeros((cfg.rows, cfg.cols), jnp.float32)
    # group local chunks by (leaf, n_rows, n_offsets) for uniform scanning
    groups: dict[tuple[int, int, int], list] = {}
    for lc in layout.local_chunks:
        groups.setdefault((lc.leaf, lc.n_rows, len(lc.offsets)), []).append(lc)
    for (leaf, n_rows, n_offs), lcs in sorted(groups.items()):
        row_len = lcs[0].row_len
        starts = jnp.asarray([lc.row_start for lc in lcs], jnp.int32)
        # (n_chunks, n_offs) offset word tables
        lo_t = jnp.asarray([[o & 0xFFFFFFFF for o in lc.offsets]
                            for lc in lcs], jnp.uint32)
        hi_t = jnp.asarray([[o >> 32 for o in lc.offsets] for lc in lcs],
                           jnp.uint32)
        view = views[leaf]
        if view_shardings is not None and view_shardings[leaf] is not None:
            view = jax.lax.with_sharding_constraint(view,
                                                    view_shardings[leaf])
        del row_len  # values are flattened; row_len implicit in the slice

        def body(tbl, xs):
            rs, lo_row, hi_row = xs
            vals = jax.lax.dynamic_slice_in_dim(
                view, rs, n_rows, axis=0).reshape(-1)
            # barrier: stops XLA hoisting convert(whole_view) out of the
            # scan (2x leaf memory for bf16 grads otherwise)
            vals = jax.lax.optimization_barrier(vals)
            if n_offs > 1:
                si = shard_idx if shard_idx is not None else 0
                lo, hi = lo_row[si], hi_row[si]
            else:
                lo, hi = lo_row[0], hi_row[0]
            tbl = tbl + kernel_ops.sketch_encode_words(
                vals, lo, hi, cfg.rows, cfg.cols, cfg.hash_key, impl=cfg.impl)
            return tbl, None

        table, _ = jax.lax.scan(body, table, (starts, lo_t, hi_t))
    return table


def unsketch_topk(table: jax.Array, layout: layout_lib.ParamLayout,
                  cfg: FetchSGDConfig) -> topk_lib.SparseDelta:
    """Delta = Top-k(U(table)) over the global flat space."""
    return topk_lib.topk_from_sketch(table, layout, cfg.k, cfg.hash_key,
                                     impl=cfg.impl)


def server_step(agg_table: jax.Array, state: FetchSGDState, lr: jax.Array,
                layout: layout_lib.ParamLayout, cfg: FetchSGDConfig
                ) -> tuple[topk_lib.SparseDelta, FetchSGDState]:
    """One aggregator update given the mean client sketch S^t — fused.

    The hot path: momentum + error accumulation fuse into one kernel call,
    the top-k row-estimates run through the selected sketch impl, and the
    post-extraction update (error zeroing / sparse re-sketch subtraction +
    momentum factor masking) is a second fused call that hashes the
    extracted ids once.  With ``cfg.impl`` resolving to Pallas the sketch
    tables stay VMEM-resident within each phase (``repro.kernels.
    server_step``); with ``jnp`` the same algebra runs as XLA ops and is
    bitwise identical to :func:`server_step_reference` (pinned in
    ``tests/test_server_step.py``).

    Traced under the ``server_state`` scope; the unsketch and top-k inside
    name their own (``repro.obs.layers``).
    """
    with jax.named_scope(layers.SERVER_STATE):
        su, se = kernel_ops.fused_momentum_error(
            agg_table, state.momentum_sketch, state.error_sketch, lr,
            cfg.momentum, impl=cfg.impl)
        delta = unsketch_topk(se, layout, cfg)
        hi, lo = topk_lib.global_ids(delta, layout)
        su, se = kernel_ops.fused_topk_mask(
            su, se, hi, lo, delta.values, cfg.hash_key,
            error_mode=cfg.error_mode,
            momentum_masking=cfg.momentum_masking, impl=cfg.impl)
        new_state = FetchSGDState(momentum_sketch=su, error_sketch=se,
                                  step=state.step + 1)
    return delta, new_state


def server_step_reference(agg_table: jax.Array, state: FetchSGDState,
                          lr: jax.Array, layout: layout_lib.ParamLayout,
                          cfg: FetchSGDConfig
                          ) -> tuple[topk_lib.SparseDelta, FetchSGDState]:
    """Unfused oracle: the update phase-by-phase as separate jnp ops.

    Kept as the parity target for the fused paths; the one hit-mask serves
    both error zeroing and momentum masking (the ids hash identically for
    both — computing it twice, as an earlier revision did, was pure waste).
    """
    su = cfg.momentum * state.momentum_sketch + agg_table
    se = lr * su + state.error_sketch
    delta = topk_lib.topk_from_sketch(se, layout, cfg.k, cfg.hash_key,
                                      impl="jnp")

    hi, lo = topk_lib.global_ids(delta, layout)
    mask = None
    if cfg.error_mode == "zero" or cfg.momentum_masking:
        mask = cs.hit_mask_ids(hi, lo, cfg.rows, cfg.cols, cfg.hash_key)
    if cfg.error_mode == "zero":
        se = jnp.where(mask, 0.0, se)
    else:
        se = se - cs.sketch_sparse(hi, lo, delta.values, cfg.rows, cfg.cols,
                                   cfg.hash_key)
    if cfg.momentum_masking:
        su = jnp.where(mask, 0.0, su)

    new_state = FetchSGDState(momentum_sketch=su, error_sketch=se,
                              step=state.step + 1)
    return delta, new_state


def apply_delta(params, layout: layout_lib.ParamLayout,
                delta: topk_lib.SparseDelta, shard_idx=None,
                local: bool = False, view_shardings=None):
    """w <- w - Delta (Delta already carries the learning rate)."""
    return topk_lib.apply_delta(params, layout, delta, scale=1.0,
                                shard_idx=shard_idx, local=local,
                                view_shardings=view_shardings)


def step(params, grads, state: FetchSGDState, lr, layout: layout_lib.ParamLayout,
         cfg: FetchSGDConfig):
    """Single-process convenience path: sketch + server update + apply.

    The distributed train step in ``repro.launch.train`` splits this into
    client-side ``sketch_grads`` (+ psum) and server-side ``server_step`` so
    the sketch is the only data-axis collective.
    """
    table = sketch_grads(grads, layout, cfg)
    delta, new_state = server_step(table, state, lr, layout, cfg)
    new_params = apply_delta(params, layout, delta)
    return new_params, new_state, delta


# -- communication accounting -------------------------------------------------

def upload_bytes(cfg: FetchSGDConfig) -> int:
    """Bytes uploaded per client per round: the sketch table."""
    return cfg.rows * cfg.cols * 4


def download_bytes(cfg: FetchSGDConfig) -> int:
    """Bytes downloaded per client per round: k (index, value) pairs.

    Matches the paper's accounting: only non-zero weight updates are
    counted, assuming a zero-overhead sparse encoding.
    """
    return cfg.k * 8


def tree_upload_bytes(cfg: FetchSGDConfig, n_clients: int,
                      fanout: int = 4) -> list[tuple[int, int]]:
    """Per-level (n_messages, bytes) for a ``fanout``-ary aggregation tree.

    Linearity lets client tables merge hierarchically: every node sends one
    (rows x cols) table to its parent, so level ``l`` carries one message
    per node at that level.  Total bytes exceed the flat sum
    ``n_clients * upload_bytes`` by the internal-node forwards, but no node
    ever receives more than ``fanout`` tables — the aggregator's fan-in
    becomes O(1) in the cohort size.  (``repro.fed.aggregator`` realizes
    this topology; this function is the closed-form cost.)
    """
    return tree_level_bytes(upload_bytes(cfg), n_clients, fanout)


def tree_level_bytes(table_bytes: int, n: int,
                     fanout: int = 4) -> list[tuple[int, int]]:
    """The raw level math behind ``tree_upload_bytes`` (any message size).

    Degenerate cohorts are exact: ``n == 1`` is a single client-to-root
    message (one level, same bytes as flat), ``n == 0`` is no messages at
    all — an empty list, not a phantom zero-message level.
    """
    if n <= 0:
        return []
    levels = []
    while n > 1:
        levels.append((n, n * table_bytes))
        n = -(-n // fanout)
    return levels or [(n, n * table_bytes)]
