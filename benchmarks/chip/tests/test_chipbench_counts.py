"""The benchmark's counts against hand counts, and its files against each
other: every cell, configuration, traffic, limit and metric is found."""

import json
import math
import re
from pathlib import Path

import pytest

import counts
import harness

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((HERE.parents[1] / c["file"]).read_text())
           for c in BENCH["configs"]}
GPT2S, INTERN = CONFIGS["gpt2s-federated"], CONFIGS["internlm2-1.8b-l4"]
DECODER = harness.model_module("decoder")


@pytest.mark.parametrize("cfg,d", [(GPT2S, 162_148_608),
                                   (INTERN, 630_736_896)])
def test_param_count_matches_hand_count(cfg, d):
    m = cfg["model"]
    assert counts.param_count(DECODER, m) == d
    shapes = DECODER.param_shapes(m).values()
    assert sum(math.prod(s) for s, _ in shapes) == d


def test_gpt2s_model_flops_by_hand():
    m = GPT2S["model"]
    # 12 layers of 4·768² attention + 2·768·3072 MLP + 2·768 norms, the
    # head 768·50257 and the final norm 768: N = 123,551,232
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072 + 2 * 768) + 768 * 50257 + 768
    assert DECODER.non_embedding_params(m) == n == 123_551_232
    per_token = 6 * n + 12 * 12 * 1024 * 768
    assert counts.model_flops_per_token(DECODER, m, 1024) == per_token
    # one client of 8 x 1024 tokens: about 7.0e12 per round
    assert per_token * 8192 == pytest.approx(7.0e12, rel=0.01)


def test_internlm2_model_flops_by_hand():
    m = INTERN["model"]
    # 4 layers of (2·16 + 2·8)·128·2048 attention + 3·2048·8192 SwiGLU +
    # 2·2048 norms, the head 2048·92544 and the final norm
    layer = 48 * 128 * 2048 + 3 * 2048 * 8192 + 2 * 2048
    assert layer == 62_918_656
    n = 4 * layer + 2048 * 92544 + 2048
    assert DECODER.non_embedding_params(m) == n
    per_token = counts.model_flops_per_token(DECODER, m, 1024)
    assert per_token * 8192 == pytest.approx(
        (6 * n + 12 * 4 * 1024 * 2048) * 8192)


@pytest.mark.parametrize("cfg,d", [(GPT2S, 162_148_608),
                                   (INTERN, 630_736_896)])
def test_encode_least_work(cfg, d):
    tr = {"rows": 5, "cols": 16384}
    work = counts.encode_least(DECODER, cfg["model"], tr)
    assert work == {"bytes": 4 * d + 5 * 16384 * 4, "ops": 5 * d}
    peak = counts.peaks("TPU v5 lite")
    assert counts.least_seconds(work, peak) == pytest.approx(
        (4 * d + 5 * 16384 * 4) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("source")


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    spec = harness.load_cell(cell, BENCH)
    assert spec["cfg"]["name"] == spec["cell"]["config"]
    assert set(spec["limits"]["limits"]) <= {
        "loss_gap", "sketch_gap", "sketch_diff", "change_gap", "change_diff"}
    assert spec["tr"]["clients"] == spec["cell"]["chips"]


def test_every_metric_names_cells_that_exist_and_has_a_reader():
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_config_file_is_what_the_program_runs(name):
    cfg = CONFIGS[name]
    pc = harness.program_config(cfg)
    for key, value in cfg["model"].items():
        assert getattr(pc, key) == value, key
    source = {c["name"]: c for c in BENCH["configs"]}[name]
    assert source["reduced"] == cfg["reduced"]


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(name.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds["setup_s"] <= 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
