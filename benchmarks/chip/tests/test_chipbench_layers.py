"""The readers of the layers the program names, on a synthesised trace of
two chips whose ops are named as a TPU v5e trace names them (by their HLO
text) and a map from instruction to layer built by hand."""

import sys

import pytest

import harness
import layer_map
import tracing
from test_chipbench_run import tiny_spec

MS = 1e-3
TR = {"clients": 2, "seqs_per_client": 1, "seq_len": 4, "rows": 5,
      "cols": 256}
WHILE = ('%while.1 = (s32[], f32[5,256]{1,0}) while((s32[], f32[5,256]{1,0})'
         ' %t), condition=%c, body=%b')
ENCODE = ('%fetchsgd_encode.2 = f32[5,2,128]{2,1,0} custom-call(u32[2]{0} '
          '%a, f32[16,128]{1,0} %v), custom_call_target="tpu_custom_call"')
PAD = '%pad_fusion.3 = f32[16,128]{1,0} fusion(f32[2000]{0} %g), kind=kLoop'
ESTIMATE = ('%fetchsgd_estimate.4 = f32[16,128]{1,0} custom-call(u32[2]{0} '
            '%a, f32[5,128,2]{2,1,0} %t), '
            'custom_call_target="tpu_custom_call"')
SORT = ('%sort.5 = (f32[2048]{0}, s32[2048]{0}) sort(f32[2048]{0} %x, '
        's32[2048]{0} %i), dimensions={0}')
MOMENTUM = ('%fetchsgd_momentum_error.6 = (f32[5,256]{1,0}, f32[5,256]{1,0}) '
            'custom-call(f32[1]{0} %lr, f32[5,256]{1,0} %g), '
            'custom_call_target="tpu_custom_call"')
FORWARD = ('%fusion.7 = bf16[8,1024,768]{2,1,0} fusion(f32[8,1024,768]{2,1,0}'
           ' %x), kind=kLoop')
BACKWARD = ('%fusion.8 = f32[768,768]{1,0} fusion(bf16[8,1024,768]{2,1,0} '
            '%x), kind=kOutput')
APPLY = '%scatter.9 = f32[2048]{0} scatter(f32[2048]{0} %w), to_apply=%add'
COPY = '%copy.10 = f32[5,256]{1,0} copy(f32[5,256]{1,0} %s)'
OP_LAYERS = {"while.1": "sketch_encode", "fetchsgd_encode.2": "sketch_encode",
             "pad_fusion.3": "sketch_encode",
             "fetchsgd_estimate.4": "unsketch", "sort.5": "topk",
             "fetchsgd_momentum_error.6": "server_state",
             "fusion.7": "forward", "fusion.8": "backward",
             "scatter.9": "sparse_apply"}
READERS = {"named_encode_ms": "sketch_encode",
           "named_unsketch_ms": "unsketch", "named_topk_ms": "topk",
           "named_server_ms": "server_state", "named_forward_ms": "forward",
           "named_backward_ms": "backward", "named_apply_ms": "sparse_apply"}


def op(dev, text, start_ms, end_ms):
    return (dev, text, start_ms * MS, end_ms * MS, text)


# two rounds of 10 ms; the while holds the encode and its input's padding,
# and runs 0.5 ms past them
OPS = [
    op(0, WHILE, 0, 4.5),
    op(0, PAD, 0, 1),
    op(0, ENCODE, 1, 4),
    op(0, ESTIMATE, 4.5, 6),
    op(0, SORT, 6, 7),
    op(0, MOMENTUM, 7, 7.5),
    op(0, COPY, 7.5, 8),                  # in no layer of the map
    op(0, FORWARD, 12, 15),
    op(0, BACKWARD, 15, 19),
    op(0, APPLY, 19, 19.5),
    op(1, ENCODE, 0, 4),
    op(1, FORWARD, 12, 16),
    op(1, BACKWARD, 15, 17),              # starts before the forward ends
]
# chip 0 ms by layer, and chip 1's
CHIP0 = {"sketch_encode": 4.5, "unsketch": 1.5, "topk": 1,
         "server_state": 0.5, "forward": 3, "backward": 4, "sparse_apply": 0.5,
         "unnamed": 0.5}
CHIP1 = {"sketch_encode": 4, "forward": 3, "backward": 2}
SPANS = [("round.put_batch", 0.0, 1 * MS), ("round.wait", 1 * MS, 20 * MS)]


def ctx(ops=OPS, op_layers=OP_LAYERS):
    lo, hi = tracing.window(SPANS)
    ops = tracing.clip(ops, lo, hi)
    return {"ops": ops, "spans": SPANS, "window_s": hi - lo,
            "busy_s": tracing.busy_seconds(ops), "rounds": 2, "chips": 2,
            "round_s": 0.010, "tr": TR, "op_layers": dict(op_layers)}


def per_round(layer):
    """Milliseconds of two chips over two rounds, per chip and round."""
    return pytest.approx((CHIP0.get(layer, 0) + CHIP1.get(layer, 0)) / 4)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_sums_its_layers_leaf_ops(name):
    assert harness.reader(name)(ctx()) == per_round(READERS[name])


def test_each_instant_counts_once_for_the_innermost_op():
    ops = [op(0, WHILE, 0, 10), op(0, PAD, 2, 4), op(0, ENCODE, 3, 6),
           op(0, SORT, 12, 13), op(1, SORT, 0, 1)]
    owned = layer_map.owned_seconds(ops)
    assert [x / MS for x in owned] == pytest.approx([6, 1, 3, 1, 1])
    assert sum(owned) == pytest.approx(2 * tracing.busy_seconds(ops))


def test_unnamed_is_what_the_map_leaves_out():
    assert harness.reader("unnamed_ms")(ctx()) == per_round("unnamed")
    assert harness.reader("unnamed_ms")(
        ctx(op_layers=dict(OP_LAYERS, **{"copy.10": "server_state"}))) == 0.0


def test_a_layer_with_no_op_reads_nothing():
    c = ctx([o for o in OPS if o[1] not in (ESTIMATE, MOMENTUM)])
    assert harness.reader("named_unsketch_ms")(c) is None
    assert harness.reader("named_server_ms")(c) is None
    assert harness.reader("named_topk_ms")(c) == per_round("topk")


def test_the_layers_and_unnamed_sum_to_busy():
    c = ctx()
    total = sum(harness.reader(name)(c) for name in [*READERS, "unnamed_ms"])
    assert total == pytest.approx(1e3 * c["busy_s"] / c["rounds"])


def test_a_program_that_names_no_layers_reads_nothing(monkeypatch):
    """The parent of the named layers has no ``repro.obs.layers``: every
    reader reads nothing, and none raises."""
    import repro.obs
    monkeypatch.delattr(repro.obs, "layers", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.layers", None)
    c = ctx()
    del c["op_layers"]
    for name in [*READERS, "unnamed_ms"]:
        assert harness.reader(name)(c) is None


def test_the_remainder_subtracts_the_same_patterns():
    """No reader of a named layer gives a ``layer_pattern``: what
    ``model_apply_ms`` subtracts is still the shape-matched readers'."""
    def module(name):
        return harness.reader(name).__globals__

    assert module("model_apply_ms")["layer_patterns"](TR) == [
        module(name)["layer_pattern"](TR)
        for name in ("encode_ms", "server_state_ms", "unsketch_topk_ms")]


def test_module_text_falls_back_to_the_executables_modules():
    class Module:
        def to_string(self):
            return "HloModule m"

    class Executable:
        def hlo_modules(self):
            return [Module(), Module()]

    class Compiled:
        def as_text(self):
            return None

        def runtime_executable(self):
            return Executable()

    assert layer_map.module_text(Compiled()) == "HloModule m\nHloModule m"


def test_the_map_comes_from_the_cells_compiled_step():
    """Off the chip, at a size a CPU test holds: the map of the harness's
    own program places the model, the sketch and the server."""
    spec = tiny_spec()
    c = {"config": spec["cfg"], "cfg": spec["cfg"]["model"],
         "model": spec["model"], "tr": spec["tr"], "chips": 1}
    names = layer_map.op_layers(c)
    assert c["op_layers"] is names
    assert set(names.values()) >= {"forward", "backward", "sketch_encode",
                                   "unsketch", "topk", "server_state",
                                   "sparse_apply"}
