"""The main-path Pallas kernels compile for a described TPU v5e.

Nothing runs: each test lowers one kernel at the train path's real sketch
width (rows=5, cols=2^14, k=512) for one chip of a v5e that is described,
not attached, and asserts the Mosaic kernel is in the compiled program.
This catches what interpret mode cannot: block shapes that do not match
the operand's tiling, primitives Mosaic cannot lower (``sort``), scalars
in the wrong memory space.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import layout as layout_lib
from repro.kernels import count_sketch as pk
from repro.kernels import server_step as ss
from repro.models import transformer

ROWS, COLS, K = 5, 1 << 14, 512
ODD_LEN = 12345


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def gpt2s_chunk_len():
    """The largest chunk the gpt2s-federated layout sketches."""
    structs = jax.eval_shape(
        functools.partial(transformer.init_params,
                          configs.get_config("gpt2s-federated")),
        jax.random.PRNGKey(0))
    return max(c.size for c in layout_lib.build_layout(structs).chunks)


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return go


@pytest.fixture(params=["gpt2s_chunk", "odd"])
def n(request, gpt2s_chunk_len):
    return gpt2s_chunk_len if request.param == "gpt2s_chunk" else ODD_LEN


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_encode_compiles(compile_tpu, n, dtype):
    text = compile_tpu(
        lambda v, off: pk.sketch_encode_words(v, off, ROWS, COLS),
        ((n,), dtype), ((2,), jnp.uint32))
    # the kernel still makes the f32 (rows, cols/128, 128) table from the
    # offset words and a lane-dense (n, 128) view: the shapes by which the
    # chip benchmark's encode_ms finds it in a trace
    (line,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert re.search(rf"= f32\[{ROWS},{COLS // 128},128\]\S* custom-call\(",
                     line)
    assert re.search(r"operand_layout_constraints=\{u32\[2\]\S*, "
                     r"(f32|bf16)\[\d+,128\]", line)


def test_estimate_compiles(compile_tpu, n):
    compile_tpu(lambda t, off: pk.sketch_estimate_words(t, off, n),
                ((ROWS, COLS), jnp.float32), ((2,), jnp.uint32))


def test_momentum_error_compiles(compile_tpu):
    table = ((ROWS, COLS), jnp.float32)
    compile_tpu(lambda a, su, se, lr: ss.momentum_error(a, su, se, lr, 0.9),
                table, table, table, ((), jnp.float32))


@pytest.mark.parametrize("error_mode", ["zero", "subtract"])
def test_topk_mask_compiles(compile_tpu, error_mode):
    table = ((ROWS, COLS), jnp.float32)
    compile_tpu(lambda su, se, hi, lo, v: ss.topk_mask(
        su, se, hi, lo, v, error_mode=error_mode),
        table, table, ((K,), jnp.uint32), ((K,), jnp.uint32),
        ((K,), jnp.float32))


# -- the whole train step: its kernels and scopes name every layer -------------

@pytest.fixture(scope="module")
def step_text(topo, no_persistent_cache):
    """The smoke-sized ``make_train_step`` with all four sketch ops on
    compiled Pallas, compiled for one described chip.  The backend here is
    the CPU, so the step is told the chip can compile Pallas."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.core import fetchsgd as F
    from repro.kernels import ops as kernel_ops
    from repro.launch import shapes, steps
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    fs = F.FetchSGDConfig(rows=ROWS, cols=1 << 12, k=64, impl="pallas")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernel_ops, "pallas_compile_supported", lambda: True)
        bundle = steps.make_train_step(
            configs.get_smoke("gpt2s-federated"),
            shapes.ShapeSpec("t", "train", 32, 4), mesh, fs)
        return bundle.fn.lower(*bundle.inputs).compile().as_text()


def test_the_step_names_its_four_kernels(step_text):
    from repro.obs import layers
    kernels = [layers.instruction_name(line) for line in step_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    assert {k.split(".")[0] for k in kernels} == set(layers.KERNELS)


def test_every_instruction_of_the_step_has_a_layer(step_text):
    from repro.obs import layers
    names = layers.op_layers(step_text)
    unplaced = [line.strip()[:120] for line in step_text.splitlines()
                if layers.instruction_name(line) not in (None, *names)
                and not re.search(r" (parameter|constant|tuple|"
                                  r"get-tuple-element|bitcast)\(", line)]
    assert unplaced == []
    assert set(names.values()) >= set(layers.LAYERS) - {layers.MERGE}
