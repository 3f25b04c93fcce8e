"""Dispatching wrappers around the Count Sketch kernels.

Every sketch op picks one of three implementations (``--sketch-impl``):

* ``jnp`` (alias ``xla``) — the XLA scatter/gather path
  (``repro.kernels.ref`` / ``repro.core.count_sketch``): always available,
  and the better choice for very wide sketches where the one-hot
  contraction's ``B x C_o`` materialization stops paying for itself;
* ``pallas`` — the **compiled** Pallas MXU kernel
  (``repro.kernels.count_sketch`` / ``repro.kernels.server_step``): the
  production hot path on the TPU backend.  Requires ``cols % 128 == 0``
  and a VMEM-resident table (``rows * cols * 4B <= ~8 MiB``).  TPU-only:
  the kernels accumulate across grid steps through a revisited output
  block, which is correct under Mosaic's sequential grid but races under
  GPU's parallel grid lowering.  Requesting it on any other backend
  raises :class:`ImplUnavailableError` — loudly, never a silent fallback;
* ``pallas-interpret`` — the same Pallas kernels run through the
  interpreter (``interpret=True``).  Validation-only: bit-identical hash
  semantics, ~27x slower than XLA on CPU.  Never selected automatically.

``auto`` resolves to ``pallas`` when the backend can compile it and the
shape qualifies, else ``jnp`` — the interpreter is *never* the hot path.

All paths are bit-compatible w.r.t. hash identity (same
``repro.core.hashing`` family), so sketches built by any can be merged.

Telemetry: ``set_telemetry(tele)`` arms wall-clock spans around *eager*
kernel dispatches (``kernel.encode[pallas]`` etc., device-synced via
``block_until_ready``).  Calls under a ``jit`` trace see tracer inputs
and are never timed — a span there would measure tracing, not compute —
so instrumentation cannot perturb compiled programs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs

from . import count_sketch as pallas_cs
from . import ref

# Above this table size the (rows, C_o, 128) accumulator no longer fits VMEM
# comfortably alongside the one-hot tiles; fall back to XLA scatter.
_PALLAS_MAX_TABLE_BYTES = 8 * 1024 * 1024

# The fused top-k mask kernel keeps up to 6 table-shaped buffers live
# (su/se in + out, hit + delta accumulators), so its VMEM budget per table
# is tighter than the single-accumulator encode kernel's.
_FUSED_MAX_TABLE_BYTES = 2 * 1024 * 1024

IMPLS = ("auto", "jnp", "pallas", "pallas-interpret")
_ALIASES = {"xla": "jnp"}

_TELE = obs.NOOP


class ImplUnavailableError(RuntimeError):
    """A requested sketch implementation cannot run on this backend."""


def set_telemetry(tele) -> None:
    """Route kernel-dispatch spans to ``tele`` (None resets to no-op)."""
    global _TELE
    _TELE = tele if tele is not None else obs.NOOP


def _span(name: str, operand):
    """A live span only for eager (non-traced) dispatches."""
    if _TELE.trace_enabled and not isinstance(operand, jax.core.Tracer):
        return _TELE.span(name)
    return obs.NULL_SPAN


def normalize_impl(impl: str) -> str:
    impl = _ALIASES.get(impl, impl)
    if impl not in IMPLS:
        raise ValueError(f"unknown sketch impl {impl!r}; choose from "
                         f"{IMPLS} (alias: xla -> jnp)")
    return impl


def pallas_compile_supported() -> bool:
    """Can this backend run our Pallas kernels compiled (non-interpret)?

    TPU only.  The encode and fused top-k kernels accumulate partial
    sums across grid steps into an output block with a constant index
    map (init at the first step, ``+=`` per step, apply at the last) —
    sound under Mosaic's *sequential* grid, but GPU lowering runs grid
    programs in parallel, so the cross-program accumulation would race
    and corrupt the sketch silently.  Don't add GPU here without first
    porting the kernels to a parallel-safe pattern.
    """
    return jax.default_backend() == "tpu"


def available_impls() -> tuple[str, ...]:
    """Concrete impls that can actually run here (excludes ``auto``)."""
    impls = ["jnp", "pallas-interpret"]
    if pallas_compile_supported():
        impls.append("pallas")
    return tuple(impls)


def require_impl(impl: str) -> str:
    """Normalize and verify ``impl`` runs on this backend, loudly.

    ``pallas`` on a CPU backend raises :class:`ImplUnavailableError` with
    the fix spelled out — a silent interpret fallback would report
    interpreter timings as the compiled hot path.
    """
    impl = normalize_impl(impl)
    if impl == "pallas" and not pallas_compile_supported():
        raise ImplUnavailableError(
            f"sketch impl 'pallas' (compiled) is unavailable on the "
            f"{jax.default_backend()!r} backend: these kernels rely on "
            f"TPU Mosaic's sequential grid for cross-step accumulation "
            f"(racy on GPU, uncompilable on CPU).  Use 'pallas-interpret' "
            f"for validation or 'jnp' for the XLA hot path.")
    return impl


def _pallas_ok(rows: int, cols: int) -> bool:
    return cols % 128 == 0 and rows * cols * 4 <= _PALLAS_MAX_TABLE_BYTES


def _fused_ok(rows: int, cols: int) -> bool:
    return cols % 128 == 0 and rows * cols * 4 <= _FUSED_MAX_TABLE_BYTES


def _check_pallas_shape(rows: int, cols: int, fused: bool) -> None:
    """Loud shape gate for an explicit ``pallas`` request.

    ``auto`` silently falls back to jnp on these shapes; an explicit
    request instead raises with the limit named — compiling anyway would
    surface as an opaque VMEM-overflow failure deep in Mosaic.
    """
    kind = "fused server-step" if fused else "count-sketch"
    if cols % 128 != 0:
        raise ImplUnavailableError(
            f"sketch impl 'pallas' needs cols % 128 == 0 for the {kind} "
            f"kernels, got cols={cols}.  Use 'jnp' for this shape.")
    limit = _FUSED_MAX_TABLE_BYTES if fused else _PALLAS_MAX_TABLE_BYTES
    nbytes = rows * cols * 4
    if nbytes > limit:
        raise ImplUnavailableError(
            f"sketch impl 'pallas' needs the ({rows}, {cols}) table "
            f"VMEM-resident, but {nbytes} bytes exceeds the {limit}-byte "
            f"budget for the {kind} kernels.  Use 'jnp' for this shape.")


def _resolve(impl: str, rows: int, cols: int,
             fused: bool = False) -> tuple[str, bool]:
    """(path, interpret) for one dispatch; path in {'jnp', 'pallas'}.

    ``auto`` never picks the interpreter: on backends without compiled
    Pallas the hot path is XLA, and interpret mode stays an explicit,
    validation-only choice.
    """
    impl = normalize_impl(impl)
    if impl == "auto":
        ok = _fused_ok(rows, cols) if fused else _pallas_ok(rows, cols)
        if ok and pallas_compile_supported():
            return "pallas", False
        return "jnp", False
    if impl == "jnp":
        return "jnp", False
    if impl == "pallas":
        require_impl(impl)
        _check_pallas_shape(rows, cols, fused)
        return "pallas", False
    return "pallas", True    # pallas-interpret


def _mode(path: str, interpret: bool) -> str:
    return "interpret" if (path == "pallas" and interpret) else "compiled"


def resolve_ops(impl: str, rows: int, cols: int) -> dict[str, str]:
    """``path:mode`` each sketch op of the train step resolves to here."""
    out = {}
    for op, fused in (("encode", False), ("estimate", False),
                      ("momentum_error", True), ("topk_mask", True)):
        path, interp = _resolve(impl, rows, cols, fused)
        out[op] = f"{path}:{_mode(path, interp)}"
    return out


def sketch_encode(values: jax.Array, offset: int, rows: int, cols: int,
                  key: int = 0, *, impl: str = "auto") -> jax.Array:
    """(rows, cols) sketch contribution of a chunk."""
    path, interp = _resolve(impl, rows, cols)
    with _span(f"kernel.encode[{path}:{_mode(path, interp)}]", values) as sp:
        if path == "pallas":
            return sp.sync(pallas_cs.sketch_encode(
                values, offset, rows, cols, key, interpret=interp))
        return sp.sync(ref.sketch_encode(values, offset, rows, cols, key))


def sketch_estimate(table: jax.Array, offset: int, n: int, key: int = 0, *,
                    impl: str = "auto") -> jax.Array:
    rows, cols = table.shape
    path, interp = _resolve(impl, rows, cols)
    with _span(f"kernel.estimate[{path}:{_mode(path, interp)}]", table) as sp:
        if path == "pallas":
            return sp.sync(pallas_cs.sketch_estimate(
                table, offset, n, key, interpret=interp))
        return sp.sync(ref.sketch_estimate(table, offset, n, key))


def sketch_encode_words(values: jax.Array, off_lo: jax.Array,
                        off_hi: jax.Array, rows: int, cols: int,
                        key: int = 0, *, impl: str = "auto") -> jax.Array:
    """Encode with a traced 64-bit base offset (EP shards, scanned chunks)."""
    from repro.core import count_sketch as core_cs
    path, interp = _resolve(impl, rows, cols)
    with _span(f"kernel.encode_words[{path}:{_mode(path, interp)}]",
               values) as sp:
        if path == "pallas":
            off = jnp.stack([off_lo, off_hi]).astype(jnp.uint32)
            return sp.sync(pallas_cs.sketch_encode_words(
                values, off, rows, cols, key, interpret=interp))
        return sp.sync(core_cs.sketch_chunk_dyn(values, off_lo, off_hi,
                                                rows, cols, key))


def sketch_estimate_words(table: jax.Array, off_lo: jax.Array,
                          off_hi: jax.Array, n: int, key: int = 0, *,
                          impl: str = "auto") -> jax.Array:
    """Estimate with a traced 64-bit base offset (scanned unsketch)."""
    from repro.core import count_sketch as core_cs
    rows, cols = table.shape
    path, interp = _resolve(impl, rows, cols)
    with _span(f"kernel.estimate_words[{path}:{_mode(path, interp)}]",
               table) as sp:
        if path == "pallas":
            off = jnp.stack([off_lo, off_hi]).astype(jnp.uint32)
            return sp.sync(pallas_cs.sketch_estimate_words(
                table, off, n, key, interpret=interp))
        return sp.sync(core_cs.estimate_chunk_dyn(table, off_lo, off_hi, n,
                                                  rows, cols, key))


# -- fused server-step phases -------------------------------------------------

def fused_momentum_error(agg: jax.Array, su: jax.Array, se: jax.Array,
                         lr, momentum: float, *,
                         impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """One pass: ``su' = momentum*su + agg``, ``se' = lr*su' + se``.

    The Pallas path keeps the (rows, cols) tables VMEM-resident across both
    updates — the 4 separate jnp ops it replaces round-trip three
    intermediate tables through HBM.
    """
    from . import server_step as fused
    rows, cols = agg.shape
    path, interp = _resolve(impl, rows, cols, fused=True)
    with _span(f"kernel.momentum_error[{path}:{_mode(path, interp)}]",
               agg) as sp:
        if path == "pallas":
            return sp.sync(fused.momentum_error(agg, su, se, lr, momentum,
                                                interpret=interp))
        return sp.sync(fused.momentum_error_jnp(agg, su, se, lr, momentum))


def fused_topk_mask(su: jax.Array, se: jax.Array, hi: jax.Array,
                    lo: jax.Array, values: jax.Array, key: int = 0, *,
                    error_mode: str = "zero", momentum_masking: bool = True,
                    impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """One pass over the extracted ids: error zeroing / sparse re-sketch
    subtraction plus momentum factor masking, hit cells computed once."""
    from . import server_step as fused
    rows, cols = su.shape
    path, interp = _resolve(impl, rows, cols, fused=True)
    with _span(f"kernel.topk_mask[{path}:{_mode(path, interp)}]", su) as sp:
        if path == "pallas":
            return sp.sync(fused.topk_mask(
                su, se, hi, lo, values, key, error_mode=error_mode,
                momentum_masking=momentum_masking, interpret=interp))
        return sp.sync(fused.topk_mask_jnp(
            su, se, hi, lo, values, key, error_mode=error_mode,
            momentum_masking=momentum_masking))
