"""The trace reduction, on a synthesised trace of two chips whose ops are
named as a TPU v5e trace names them: by their HLO text."""

import shutil
from pathlib import Path

import pytest

import counts
import harness
import tracing

HERE = Path(__file__).resolve().parents[1]
MS = 1e-3
TR = {"clients": 2, "seqs_per_client": 1, "seq_len": 4, "rows": 5,
      "cols": 256}
ENCODE = ('%closed_call.1 = f32[5,2,128]{2,1,0} custom-call(u32[2]{0} %a, '
          'f32[16,128]{1,0} %v), custom_call_target="tpu_custom_call"')
ESTIMATE = ('%closed_call.2 = f32[16,128]{1,0} custom-call(u32[2]{0} %a, '
            'f32[5,128,2]{2,1,0} %t), custom_call_target="tpu_custom_call"')
SORT = ('%sort.3 = (f32[2048]{0}, s32[2048]{0}) sort(f32[2048]{0} %x, '
        's32[2048]{0} %i), dimensions={0}')
MOMENTUM = ('%closed_call.4 = (f32[5,256]{1,0}, f32[5,256]{1,0}) '
            'custom-call(f32[1]{0} %lr, f32[5,256]{1,0} %g), '
            'custom_call_target="tpu_custom_call"')
MERGE = ('%all-reduce.5 = f32[5,256]{1,0} all-reduce(f32[5,256]{1,0} %t), '
         'replica_groups={{0,1}}')
MODEL = ('%fusion.6 = bf16[8,1024,768]{2,1,0} fusion(f32[8,1024,768]{2,1,0} '
         '%x), kind=kLoop')
TOKSORT = ('%sort.7 = (s32[64]{0}, s32[64]{0}) sort(s32[64]{0} %x, '
           's32[64]{0} %i), dimensions={0}')
WHILE = ('%while.8 = (s32[], f32[5,256]{1,0}) while((s32[], f32[5,256]{1,0})'
         ' %t), condition=%c, body=%b')


def op(dev, text, start_ms, end_ms):
    return (dev, text, start_ms * MS, end_ms * MS, text)


# two rounds of 10 ms; chip 1 mirrors chip 0 but for a few ops
OPS = [
    op(0, WHILE, 0, 8.5),                 # holds the ops below it
    op(0, ENCODE, 0, 3),
    op(0, ENCODE, 2, 4),                  # overlaps the first encode
    op(0, ESTIMATE, 5, 6),
    op(0, SORT, 6, 7),
    op(0, MOMENTUM, 7, 8),
    op(0, MERGE, 8, 8.5),
    op(0, MODEL, 12, 19),
    op(0, TOKSORT, 19, 19.5),
    op(1, ENCODE, 0, 4),
    op(1, MODEL, 12, 18),
]
SPANS = [("round.put_batch", 0.0, 1 * MS), ("round.dispatch", 1 * MS, 2 * MS),
         ("round.wait", 2 * MS, 9.5 * MS),
         ("round.put_batch", 9.5 * MS, 12 * MS),
         ("round.wait", 12 * MS, 20 * MS)]
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
       "d_ff": 16, "vocab": 10, "act": "gelu"}
DECODER = harness.model_module("decoder")


def ctx(ops=OPS):
    lo, hi = tracing.window(SPANS)
    ops = tracing.clip(ops, lo, hi)
    return {"ops": ops, "spans": SPANS, "window_s": hi - lo,
            "busy_s": tracing.busy_seconds(ops), "rounds": 2, "chips": 2,
            "round_s": 0.010, "peak": PEAK, "cfg": CFG, "model": DECODER,
            "tr": TR}


def per_round(ms):
    """Milliseconds of two chips over two rounds, per chip and round."""
    return pytest.approx(ms / 2 / 2)


def test_union_merges_overlaps_and_sorts():
    assert tracing.union([(5, 6), (0, 3), (2, 4), (4, 4.5)]) == [
        (0, 4.5), (5, 6)]


def test_busy_is_the_union_averaged_over_chips():
    c = ctx()
    # chip 0: [0, 8.5] + [12, 19.5] = 16 ms; chip 1: 4 + 6 = 10 ms
    assert c["busy_s"] == pytest.approx((16 + 10) / 2 * MS)
    assert c["window_s"] == pytest.approx(20 * MS)
    idle = harness.reader("idle_share")(c)
    assert idle == pytest.approx(100 * (1 - 13 / 20))


def test_layers_are_attributed_by_their_ops():
    c = ctx()
    # encode: chip 0 3 + 2 ms (overlapping ops count each), chip 1 4 ms
    assert harness.reader("encode_ms")(c) == per_round(9)
    assert harness.reader("unsketch_topk_ms")(c) == per_round(2)
    assert harness.reader("server_state_ms")(c) == per_round(1)
    # no reader names the all-reduce yet, so it is the remainder's
    rest = (c["busy_s"] / MS) / 2 - (9 + 2 + 1) / 2 / 2
    assert harness.reader("model_apply_ms")(c) == pytest.approx(rest)


def test_a_layer_with_no_op_reads_nothing():
    c = ctx([o for o in OPS if o[1] != MOMENTUM])
    assert harness.reader("server_state_ms")(c) is None
    c = ctx([o for o in OPS if o[1] not in (ENCODE, ESTIMATE)])
    assert harness.reader("encode_ms")(c) is None
    assert harness.reader("encode_roofline")(c) is None
    assert harness.reader("unsketch_topk_ms")(c) is None


def test_a_new_layer_reader_leaves_the_remainder(tmp_path):
    """A reader file that names its layer's ops takes them out of
    ``model_apply_ms`` without an edit to any other file."""
    metrics = tmp_path / "metrics"
    shutil.copytree(HERE / "metrics", metrics,
                    ignore=shutil.ignore_patterns("__pycache__"))
    c = ctx()
    before = harness.reader("model_apply_ms", root=tmp_path)(c)
    (metrics / "merge_ms.py").write_text(
        "def layer_pattern(tr):\n    return r'all-reduce\\('\n\n\n"
        "def read(ctx):\n    return None\n")
    after = harness.reader("model_apply_ms", root=tmp_path)(c)
    assert before - after == per_round(0.5)


def test_patterns_follow_the_traffic():
    c = dict(ctx(), tr=dict(TR, cols=512))     # other table: no match
    assert harness.reader("encode_ms")(c) is None
    assert harness.reader("server_state_ms")(c) is None


def test_encode_roofline_is_least_time_over_encode_time():
    c = ctx()
    least = counts.least_seconds(counts.encode_least(DECODER, CFG, TR), PEAK)
    assert harness.reader("encode_roofline")(c) == pytest.approx(
        100 * least / (9 / 2 / 2 * MS))


def test_mfu_counts_model_flops_over_round_time():
    flops = counts.model_flops_per_token(DECODER, CFG, 4) * 2 * 1 * 4
    assert harness.reader("mfu")(ctx()) == pytest.approx(
        100 * flops / (0.010 * 2 * 197e12))


def test_short_name_drops_layouts():
    assert tracing.short_name(SORT) == "sort (f32[2048], s32[2048])"
    assert tracing.short_name(WHILE) == "while (s32[], f32[5,256])"


def test_breakdown_orders_leaf_ops_and_labels_gaps():
    c = ctx()
    lo, hi = tracing.window(SPANS)
    b = tracing.breakdown(c["ops"], SPANS, lo, hi)
    names = [n for n, _ in b["device_ops"]]
    assert not any(n.startswith("while") for n in names)   # holds others
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert b["device_ops"][0] == ["fusion bf16[8,1024,768]",
                                  pytest.approx(6.5 * MS)]
    gaps = b["idle_gaps"]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # chip 0 is idle 8.5-12 ms, mostly while the next batch is put
    assert gaps[0] == ["round.put_batch", pytest.approx(3.5 * MS)]
    assert gaps[1] == ["round.wait", pytest.approx(0.5 * MS)]
    assert len(b["device_ops"]) <= 10 and len(gaps) <= 10
