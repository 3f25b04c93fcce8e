"""Pallas kernel allclose sweeps vs the pure-jnp oracle (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import count_sketch as pk
from repro.kernels import ops, ref

SHAPES = [(64,), (513,), (1000,), (4096,), (12345,)]
DTYPES = [jnp.float32, jnp.bfloat16]
TABLES = [(3, 256), (5, 1024), (1, 128), (7, 8192)]

# edge sweep: non-power-of-two lengths (incl. n < block and n == 1), cols
# that are 128-multiples but not powers of two, odd/even row counts beyond
# the happy sizes above
EDGE_SHAPES = [1, 127, 129, 3000]
EDGE_TABLES = [(2, 384), (9, 640), (4, 1920)]

# every Pallas-backed impl the dispatcher knows.  The compiled path only
# exists on TPU (the kernels need Mosaic's sequential grid for their
# cross-step accumulation); elsewhere the params skip cleanly instead of
# failing, so the same sweep pins compiled parity the moment it runs on
# capable hardware.
needs_compiled = pytest.mark.skipif(
    not ops.pallas_compile_supported(),
    reason=f"backend {jax.default_backend()!r} cannot compile Pallas "
           "(interpret-only)")
PALLAS_IMPLS = [
    pytest.param("pallas-interpret", id="interpret"),
    pytest.param("pallas", id="compiled", marks=needs_compiled),
]


@pytest.mark.parametrize("n", [s[0] for s in SHAPES])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("rows,cols", TABLES)
def test_encode_matches_ref(rng, n, dtype, rows, cols):
    v = jnp.asarray(rng.normal(size=n).astype(np.float32)).astype(dtype)
    out = pk.sketch_encode(v, 1234, rows, cols, key=1, interpret=True)
    want = ref.sketch_encode(v, 1234, rows, cols, key=1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [64, 1000, 4096])
@pytest.mark.parametrize("rows,cols", [(3, 256), (5, 1024)])
def test_estimate_matches_ref(rng, n, rows, cols):
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    tbl = ref.sketch_encode(v, 77, rows, cols, key=2)
    out = pk.sketch_estimate(tbl, 77, n, key=2, interpret=True)
    want = ref.sketch_estimate(tbl, 77, n, key=2)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", range(1, 10))
def test_median_rows_matches_jnp_median(rng, rows):
    """The kernel's sort-free median equals ``jnp.median`` over the rows,
    ties and repeated values included."""
    x = rng.integers(-3, 4, size=(rows, 512)).astype(np.float32)
    x[:, :256] = rng.normal(size=(rows, 256))
    got = pk.median_rows([jnp.asarray(r) for r in x])
    np.testing.assert_array_equal(got, jnp.median(jnp.asarray(x), axis=0))


@pytest.mark.parametrize("traced", [False, True], ids=["static", "words"])
@pytest.mark.parametrize("offset", [0, 2**31 - 5, 2**32 - 3, 2**41 + 99,
                                    (3 << 32) + 12345])
def test_encode_64bit_offsets(rng, offset, traced):
    """Hash identity must survive the 32-bit word boundary (d ~ 4e11), for a
    static offset and for the traced ``[lo, hi]`` words alike."""
    v = jnp.asarray(rng.normal(size=500).astype(np.float32))
    if traced:
        off = jnp.asarray([offset & 0xFFFFFFFF, offset >> 32], jnp.uint32)
        out = pk.sketch_encode_words(v, off, 3, 512, interpret=True)
    else:
        out = pk.sketch_encode(v, offset, 3, 512, interpret=True)
    want = ref.sketch_encode(v, offset, 3, 512)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


F32_MAX = float(np.finfo(np.float32).max)
F32_TINY = float(np.finfo(np.float32).tiny)           # 2**-126
SPLIT_BF16 = pk.split_bf16


def low_bit_values(rng, n, exponent, middle=False):
    """``±2**exponent * (1 + m * 2**-23)``, m in [64, 256): float32 values
    whose only set mantissa bits are the lowest eight.  With ``middle`` the
    top bit of the middle eight (2**-8) is set too, so that each of the
    three bf16 pieces holds some of the value."""
    m = rng.integers(64, 256, size=n)
    sign = rng.choice([-1.0, 1.0], size=n)
    frac = 1.0 + (2.0**-8 if middle else 0.0) + m * 2.0**-23
    return (sign * np.ldexp(frac, exponent)).astype(np.float32)


def split_case(rng, case):
    if case == "normals":
        return (rng.normal(size=4096)
                * np.exp(rng.normal(scale=8.0, size=4096))).astype(np.float32)
    if case == "zeros":
        return np.array([0.0, -0.0], np.float32)
    if case == "limits":
        # the largest finite value (rounded to bf16 it would be inf), the
        # smallest normal, and the least exponent at which the lowest
        # mantissa bit is still normal
        return np.array([F32_MAX, -F32_MAX, F32_TINY, -F32_TINY,
                         np.ldexp(1.0 + 255 * 2.0**-23, 127),
                         np.ldexp(1.0 + 255 * 2.0**-23, -103)], np.float32)
    return np.concatenate([low_bit_values(rng, 64, e, middle)
                           for e in (-103, -60, 0, 60, 127)
                           for middle in (False, True)])


@pytest.mark.parametrize("case", ["normals", "zeros", "limits", "low_bits"])
def test_split_bf16_is_exact(rng, case):
    x = split_case(rng, case)
    pieces = pk.split_bf16(jnp.asarray(x))
    assert len(pieces) == 3
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    back = sum(p.astype(jnp.float32) for p in pieces)
    np.testing.assert_array_equal(np.asarray(back), x)


def encode_low_bits(rng, exponent, middle):
    v = jnp.asarray(low_bit_values(rng, 1024, exponent, middle))
    off = jnp.asarray([777, 0], jnp.uint32)
    out = pk.sketch_encode_words(v, off, 3, 8192, key=5, interpret=True)
    return out, ref.sketch_encode(v, 777, 3, 8192, key=5)


@pytest.mark.parametrize("middle", [False, True], ids=["low8", "low8_mid"])
@pytest.mark.parametrize("exponent", [-100, 0, 100])
def test_encode_low_bits_matches_ref(rng, exponent, middle):
    """The encode keeps every bit of a float32 value: the third bf16 piece
    carries the lowest eight mantissa bits."""
    out, want = encode_low_bits(rng, exponent, middle)
    np.testing.assert_allclose(out, want, rtol=1e-6)


@pytest.mark.parametrize("exponent", [-100, 0, 100])
def test_two_piece_split_misses_low_bits(rng, monkeypatch, exponent):
    """A planted fault: two pieces of the values (what ``Precision.HIGH``
    keeps, about 16 bits) lose the lowest eight bits of values that use all
    three pieces, and the check above fails.  (Values with no middle bits
    need only two pieces: the second then takes the lowest eight.)"""
    monkeypatch.setattr(pk, "split_bf16", lambda x: SPLIT_BF16(x)[:2])
    out, want = encode_low_bits(rng, exponent, middle=True)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(out, want, rtol=1e-6)


def test_zero_padding_is_noop(rng):
    """Block padding must not perturb the sketch."""
    v = jnp.asarray(rng.normal(size=511).astype(np.float32))  # forces pad
    out = pk.sketch_encode(v, 0, 3, 256, interpret=True)
    want = ref.sketch_encode(v, 0, 3, 256)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_ops_dispatch(rng):
    v = jnp.asarray(rng.normal(size=256).astype(np.float32))
    a = ops.sketch_encode(v, 0, 3, 256, impl="pallas-interpret")
    b = ops.sketch_encode(v, 0, 3, 256, impl="xla")
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # non-128-multiple cols must fall back to jnp without error
    c = ops.sketch_encode(v, 0, 3, 300, impl="auto")
    assert c.shape == (3, 300)


def test_impl_normalization():
    assert ops.normalize_impl("xla") == "jnp"
    assert ops.normalize_impl("jnp") == "jnp"
    assert ops.normalize_impl("pallas-interpret") == "pallas-interpret"
    with pytest.raises(ValueError, match="unknown sketch impl"):
        ops.normalize_impl("cuda-graphs")


def test_available_impls_contract():
    avail = ops.available_impls()
    assert "jnp" in avail and "pallas-interpret" in avail
    assert ("pallas" in avail) == ops.pallas_compile_supported()
    for impl in avail:
        ops.require_impl(impl)          # must not raise
    ops.require_impl("auto")            # auto is always satisfiable


@pytest.mark.skipif(ops.pallas_compile_supported(),
                    reason="compiled Pallas exists here; nothing to refuse")
def test_compiled_pallas_unavailable_is_loud(rng):
    """Requesting the compiled impl on an interpret-only backend must fail
    fast with an actionable message — never silently fall back."""
    with pytest.raises(ops.ImplUnavailableError, match="pallas"):
        ops.require_impl("pallas")
    v = jnp.asarray(rng.normal(size=256).astype(np.float32))
    with pytest.raises(ops.ImplUnavailableError):
        ops.sketch_encode(v, 0, 3, 256, impl="pallas")


def test_explicit_pallas_shape_gate():
    """An explicit 'pallas' request on a shape the kernels can't take must
    raise the documented error up front, not compile into an opaque VMEM
    overflow.  (``auto`` silently falls back to jnp on these shapes.)"""
    ops._check_pallas_shape(3, 384, fused=False)        # qualifying: no raise
    with pytest.raises(ops.ImplUnavailableError, match="cols % 128"):
        ops._check_pallas_shape(3, 300, fused=False)
    with pytest.raises(ops.ImplUnavailableError, match="VMEM"):
        ops._check_pallas_shape(64, 65536, fused=False)     # 16 MiB > 8 MiB
    # the fused kernels keep more table buffers live, so their budget is
    # tighter: a 4 MiB table passes the encode gate but not the fused one
    ops._check_pallas_shape(8, 131072, fused=False)
    with pytest.raises(ops.ImplUnavailableError, match="fused server-step"):
        ops._check_pallas_shape(8, 131072, fused=True)


@needs_compiled
def test_explicit_pallas_bad_shape_is_loud_at_dispatch(rng):
    v = jnp.asarray(rng.normal(size=256).astype(np.float32))
    with pytest.raises(ops.ImplUnavailableError, match="cols % 128"):
        ops.sketch_encode(v, 0, 3, 300, impl="pallas")


def test_auto_never_picks_interpreter(rng):
    """``auto`` resolves to compiled Pallas or jnp — the interpreter is a
    validation tool (~27x slower than XLA) and must be explicit opt-in."""
    path, interpret = ops._resolve("auto", 3, 256)
    assert not interpret
    if not ops.pallas_compile_supported():
        assert path == "jnp"


def test_mergeability_across_impls(rng):
    """Sketches from the Pallas and XLA paths share hash identity."""
    g = rng.normal(size=1000).astype(np.float32)
    t1 = ops.sketch_encode(jnp.asarray(g[:500]), 0, 3, 512,
                           impl="pallas-interpret")
    t2 = ops.sketch_encode(jnp.asarray(g[500:]), 500, 3, 512, impl="xla")
    whole = ref.sketch_encode(jnp.asarray(g), 0, 3, 512)
    np.testing.assert_allclose(t1 + t2, whole, rtol=1e-5, atol=1e-4)


def test_estimate_words_dynamic_offset(rng):
    """Traced (lo, hi) offset estimate matches the static-offset kernel
    and the oracle — this is the variant the top-k readout drives."""
    n = 700
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    off = (3 << 32) + 12345
    tbl = ref.sketch_encode(v, off, 3, 512, key=6)
    lo = jnp.uint32(off & 0xFFFFFFFF)
    hi = jnp.uint32(off >> 32)
    for impl in ("jnp", "pallas-interpret"):
        out = ops.sketch_estimate_words(tbl, lo, hi, n, 6, impl=impl)
        want = ref.sketch_estimate(tbl, off, n, key=6)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"impl={impl}")


@pytest.mark.parametrize("n", EDGE_SHAPES)
@pytest.mark.parametrize("rows,cols", EDGE_TABLES)
def test_encode_edge_shapes(rng, n, rows, cols):
    """Pallas encode at the awkward sizes: n not a power of two (down to a
    single element, forcing near-total block padding), cols a 128-multiple
    that is not a power of two, odd row counts."""
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    out = pk.sketch_encode(v, 321, rows, cols, key=3, interpret=True)
    want = ref.sketch_encode(v, 321, rows, cols, key=3)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [1, 127, 3000])
@pytest.mark.parametrize("rows,cols", [(2, 384), (9, 640)])
def test_estimate_edge_shapes(rng, n, rows, cols):
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    tbl = ref.sketch_encode(v, 55, rows, cols, key=4)
    out = pk.sketch_estimate(tbl, 55, n, key=4, interpret=True)
    want = ref.sketch_estimate(tbl, 55, n, key=4)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
@pytest.mark.parametrize("n", EDGE_SHAPES)
@pytest.mark.parametrize("rows,cols", EDGE_TABLES)
def test_dispatch_edge_shapes(rng, impl, n, rows, cols):
    """The same awkward-size sweep through the ``ops`` dispatcher: the
    interpreter param always runs; the compiled param skips on backends
    that cannot lower Pallas and pins parity everywhere else."""
    v = jnp.asarray(rng.normal(size=n).astype(np.float32))
    tbl = ops.sketch_encode(v, 321, rows, cols, key=3, impl=impl)
    np.testing.assert_allclose(
        tbl, ref.sketch_encode(v, 321, rows, cols, key=3),
        rtol=1e-5, atol=1e-5)
    est = ops.sketch_estimate(tbl, 321, n, key=3, impl=impl)
    np.testing.assert_allclose(
        est, ref.sketch_estimate(tbl, 321, n, key=3),
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
@pytest.mark.parametrize("rows,cols", EDGE_TABLES)
@pytest.mark.parametrize("error_mode", ["zero", "subtract"])
def test_fused_server_kernels_edge_tables(rng, impl, rows, cols, error_mode):
    """Fused momentum/error and top-k hit-mask kernels vs the jnp fused
    path at the edge tables (odd rows, non-power-of-two 128-multiple
    cols), for both error feedback modes."""
    agg = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
    su = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
    se = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
    su_j, se_j = ops.fused_momentum_error(agg, su, se, 0.05, 0.9,
                                          impl="jnp")
    su_p, se_p = ops.fused_momentum_error(agg, su, se, 0.05, 0.9,
                                          impl=impl)
    np.testing.assert_allclose(su_p, su_j, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(se_p, se_j, rtol=1e-5, atol=1e-5)

    # a ragged top-k id set: k not a multiple of the kernel block, ids
    # straddling the 32-bit word boundary
    k = 13
    ids = np.unique(rng.integers(0, 2**33, size=k).astype(np.uint64))
    hi = jnp.asarray((ids >> 32).astype(np.uint32))
    lo = jnp.asarray((ids & 0xFFFFFFFF).astype(np.uint32))
    vals = jnp.asarray(rng.normal(size=ids.size).astype(np.float32))
    out_j = ops.fused_topk_mask(su_j, se_j, hi, lo, vals, 3,
                                error_mode=error_mode, impl="jnp")
    out_p = ops.fused_topk_mask(su_j, se_j, hi, lo, vals, 3,
                                error_mode=error_mode, impl=impl)
    for a, b in zip(out_p, out_j):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cols", [130, 300, 1000])
def test_non_lane_multiple_cols(rng, cols):
    """cols % 128 != 0: the raw Pallas kernels refuse loudly, and the ops
    dispatcher transparently falls back to the XLA path with identical
    hash identity (vs the oracle)."""
    v = jnp.asarray(rng.normal(size=500).astype(np.float32))
    with pytest.raises(ValueError, match="128"):
        pk.sketch_encode(v, 0, 3, cols, interpret=True)
    with pytest.raises(ValueError, match="128"):
        pk.sketch_estimate(jnp.zeros((3, cols)), 0, 500, interpret=True)
    out = ops.sketch_encode(v, 0, 3, cols, impl="auto")
    want = ref.sketch_encode(v, 0, 3, cols)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    est = ops.sketch_estimate(out, 0, 500, impl="auto")
    np.testing.assert_allclose(est, ref.sketch_estimate(want, 0, 500),
                               rtol=1e-5, atol=1e-5)
