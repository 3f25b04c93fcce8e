"""Each configuration brings its model reference as a file: a cell whose
files all live under a fresh root runs off the chip to ``correct``; a
wrong model file there is not correct; a configuration that names no
model file, or a missing one, is refused; and the committed configurations
count what they counted when the decoder was the only model."""

import json
import time
from pathlib import Path

import pytest

import counts
import harness
from test_chipbench_run import BENCH, LIMITS, SEED, TINY_MODEL, TINY_TRAFFIC

HERE = Path(__file__).resolve().parents[1]
# gpt2s.silo1.c14's limits, but loss_gap's: at the tiny size on the CPU a
# sound run reads 1.44e-3, the wrong model of the test below 1.17e-2
TINY_LIMITS = {"limits": dict(LIMITS["limits"], loss_gap=4e-3)}
REEXPORT = f"""
import sys
sys.path.insert(0, {str(HERE / "models")!r})
from decoder import flops_per_token, loss_and_grad, param_shapes  # noqa
"""
NO_LAST_MLP = f"""
import sys
sys.path.insert(0, {str(HERE / "models")!r})
import decoder
from decoder import flops_per_token, param_shapes  # noqa


def loss_and_grad(p, tokens, labels, cfg, q=None):
    # the last layer's down projection zeroed: its MLP adds nothing
    m0 = p["units"]["m0"]
    w = m0["mlp"]["w_down"].at[-1].set(0.0)
    p = dict(p, units={{"m0": dict(m0, mlp=dict(m0["mlp"], w_down=w))}})
    return decoder.loss_and_grad(p, tokens, labels, cfg, q)
"""


def cell_root(root: Path, model_source: str | None,
              reference: str | None = "tiny_dense") -> dict:
    """A cell ``tiny.silo1`` with every file of its own under ``root``."""
    for sub in ("configs", "models", "traffic", "limits"):
        (root / sub).mkdir(exist_ok=True)
    cfg = {"name": "tiny", "program_arch": "internlm2-1.8b",
           "model": TINY_MODEL}
    if reference is not None:
        cfg["reference"] = reference
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    if model_source is not None:
        (root / "models" / "tiny_dense.py").write_text(model_source)
    (root / "traffic" / "silo1.tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "limits" / "tiny.silo1.json").write_text(json.dumps(TINY_LIMITS))
    return {"workloads": [{"name": "tiny.silo1", "config": "tiny",
                           "traffic": "silo1.tiny", "chips": 1}],
            "end_to_end": BENCH["end_to_end"], "per_layer": []}


def run_in(root: Path, model_source: str) -> dict:
    bench = cell_root(root, model_source)
    spec = harness.load_cell("tiny.silo1", bench, root=root)
    return harness.run(spec, bench, SEED, 0.2, False, time.perf_counter(),
                       require_chip=False, cache=False)


def test_a_cell_added_as_files_alone_is_correct(tmp_path):
    result = run_in(tmp_path, REEXPORT)
    assert result["correct"] is True, result["compared"]


def test_the_check_reads_the_configurations_own_model(tmp_path):
    result = run_in(tmp_path, NO_LAST_MLP)
    assert result["correct"] is False
    assert result["compared"]["loss_gap"]["value"] > TINY_LIMITS["limits"][
        "loss_gap"]


@pytest.mark.parametrize("reference,source,names", [
    (None, REEXPORT, "configs/tiny.json"),
    ("tiny_dense", None, "models/tiny_dense.py"),
], ids=["no_reference_key", "missing_model_file"])
def test_a_configuration_without_its_model_file_is_refused(
        tmp_path, reference, source, names):
    bench = cell_root(tmp_path, source, reference)
    with pytest.raises((KeyError, FileNotFoundError), match=names):
        harness.load_cell("tiny.silo1", bench, root=tmp_path)


@pytest.mark.parametrize("cell,d,per_token", [
    # per token: 6·N + 12·L·S·(H·hd) at S = 1024 (test_chipbench_counts)
    ("gpt2s.silo1.c14", 162_148_608, 6 * 123_551_232 + 12 * 12 * 1024 * 768),
    ("internlm2-l4.silo1.c14", 630_736_896,
     6 * 441_206_784 + 12 * 4 * 1024 * 2048),
])
def test_committed_configurations_count_as_before(cell, d, per_token):
    spec = harness.load_cell(cell, BENCH)
    assert spec["cfg"]["reference"] == "decoder"
    model, m = spec["model"], spec["cfg"]["model"]
    assert counts.param_count(model, m) == d
    assert counts.model_flops_per_token(model, m, 1024) == per_token
