"""The round's layers by the names the program gives them
(``repro.obs.layers``): op_name scopes and kernel names, read from a
compiled module's text.  The described-v5e compile of the whole step is in
``test_tpu_compile.py``, with the other compiles for a described chip."""

import re

import pytest

from repro import configs
from repro.core import fetchsgd as F
from repro.launch import mesh as mesh_lib
from repro.launch import shapes, steps
from repro.obs import layers

# a compiled module as XLA prints it: computations, then instructions with
# their metadata; the custom call's metadata is empty, as on a TPU
HLO = """\
HloModule jit_body, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), \
metadata={op_name="jit(body)/client_model/jvp()/mul"}
}

%compare.2 (p.0.lhs: f32[], p.0.rhs: f32[]) -> pred[] {
  %p.0.lhs = f32[] parameter(0)
  %p.0.rhs = f32[] parameter(1)
  ROOT %compare.3 = pred[] compare(%p.0.lhs, %p.0.rhs), direction=GT
}

ENTRY %main.9 (Arg_0.1: f32[8], Arg_1.2: s32[8], Arg_2.3: u32[2]) -> \
(f32[8], f32[5,2,128], f32[]) {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %Arg_1.2 = s32[8]{0} parameter(1), metadata={op_name="ids"}
  %Arg_2.3 = u32[2]{0} parameter(2), metadata={op_name="offset"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %rope_fusion.2 = f32[8]{0} fusion(), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="jit(body)/pow"}
  %fusion.4 = f32[8]{0} fusion(%copy-done.1, %rope_fusion.2), kind=kLoop, \
calls=%fused_computation.1, \
metadata={op_name="jit(body)/client_model/jvp()/mul" stack_frame_id=1}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="jit(body)/client_model/\
transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mul"}
  %sort.6 = (f32[8]{0}, s32[8]{0}) sort(%fusion.5, %Arg_1.2), \
dimensions={0}, to_apply=%compare.2, \
metadata={op_name="jit(body)/server_state/topk/while/body/top_k"}
  %fetchsgd_encode.7 = f32[5,2,128]{2,1,0:T(8,128)} custom-call(%Arg_2.3, \
%fusion.4), custom_call_target="tpu_custom_call", metadata={}
  %get-tuple-element.8 = f32[8]{0} get-tuple-element(%sort.6), index=0
  %add.9 = f32[] add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(body)/add"}
  ROOT %tuple.10 = (f32[8]{0}, f32[5,2,128]{2,1,0}, f32[]) \
tuple(%get-tuple-element.8, %fetchsgd_encode.7, %add.9)
}
"""


@pytest.mark.parametrize("op_name, layer", [
    ("jit(body)/client_model/jvp()/while/body/closed_call/dot_general",
     layers.FORWARD),
    ("jit(body)/client_model/transpose(jvp())/while/body/dot_general",
     layers.BACKWARD),
    ("jit(body)/client_model/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/mul", layers.BACKWARD),
    ("jit(body)/sketch_encode/while/body/closed_call/scatter-add",
     layers.SKETCH_ENCODE),
    ("jit(body)/merge/psum", layers.MERGE),
    ("jit(body)/server_state/gather", layers.SERVER_STATE),
    ("jit(body)/server_state/topk/while/body/closed_call/top_k",
     layers.TOPK),
    ("jit(body)/server_state/topk/while/body/closed_call/unsketch/gather",
     layers.UNSKETCH),
    ("jit(body)/sparse_apply/while/body/closed_call/scatter-add",
     layers.SPARSE_APPLY),
    ("jit(body)/pow", None),
    ("jit(body)/jit(topk_dense)/sort", None),      # a part, not a scope
    ("reduce_sum", None),
])
def test_a_layer_is_the_innermost_scope_of_the_op_name(op_name, layer):
    assert layers.layer_of(op_name) == layer


def test_op_layers_places_each_instruction_of_a_module():
    got = layers.op_layers(HLO)
    assert got["fusion.4"] == layers.FORWARD
    assert got["multiply.1"] == layers.FORWARD
    assert got["fusion.5"] == layers.BACKWARD
    assert got["sort.6"] == layers.TOPK
    assert got["compare.3"] == layers.TOPK            # by its caller
    assert got["fetchsgd_encode.7"] == layers.SKETCH_ENCODE
    # made without a scope: by the work it feeds, through a prefetch
    assert got["copy-start.1"] == got["copy-done.1"] == layers.FORWARD
    assert got["rope_fusion.2"] == layers.FORWARD
    # outside every scope, reading and feeding no work: unnamed
    assert "add.9" not in got
    assert not {"Arg_0.1", "p.0.lhs"} & set(got)     # parameters never


def test_a_kernel_name_places_its_custom_call():
    for kernel, layer in layers.KERNELS.items():
        assert layers.kernel_layer(f"{kernel}.12") == layer
        assert layers.kernel_layer(kernel) == layer
    assert layers.kernel_layer("closed_call.3") is None
    assert set(layers.KERNELS.values()) <= set(layers.LAYERS)


def test_instruction_names():
    assert layers.instruction_name(
        "  ROOT %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == "fusion.3"
    assert layers.instruction_name(
        "%fetchsgd_encode.1 = f32[5,2,128]{2,1,0} custom-call()") == \
        "fetchsgd_encode.1"
    assert layers.instruction_name("HloModule m, is_scheduled=true") is None


@pytest.fixture(scope="module")
def jnp_step_text():
    """The smoke step with every sketch op on its jnp twin, compiled for
    the CPU."""
    fs = F.FetchSGDConfig(rows=3, cols=512, k=16, impl="jnp")
    bundle = steps.make_train_step(
        configs.get_smoke("gpt2s-federated"),
        shapes.ShapeSpec("t", "train", 16, 2),
        mesh_lib.make_mesh((1, 1), ("data", "model")), fs)
    return bundle.fn.lower(*bundle.inputs).compile().as_text()


def _by_opcode(text, opcode):
    names = layers.op_layers(text)
    rx = re.compile(rf"^\s*(?:ROOT )?%([\w.-]+) = .*? {opcode}\(")
    return [names.get(m.group(1)) for m in map(rx.match, text.splitlines())
            if m]


def test_the_jnp_sketch_ops_map_to_encode_and_unsketch(jnp_step_text):
    scatters = _by_opcode(jnp_step_text, "scatter")
    gathers = _by_opcode(jnp_step_text, "gather")
    assert None not in scatters and None not in gathers
    # the encode's scatter-add per sketch row, the estimate's gather per row
    assert scatters.count(layers.SKETCH_ENCODE) >= 3
    assert gathers.count(layers.UNSKETCH) >= 3
    assert set(layers.op_layers(jnp_step_text).values()) >= set(
        layers.LAYERS) - {layers.MERGE}     # one device: no collective
