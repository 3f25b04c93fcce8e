"""The harness off the chip: it refuses the CPU, finds a new cell and
metric by name, and its check fails the control and each planted fault.

The runs here are the harness's own path at a size a CPU test can hold,
held to the limits of ``gpt2s.silo1.c14``."""

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

import harness
import reference
import traffic

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LIMITS = json.loads((HERE / "limits" / "gpt2s.silo1.c14.json").read_text())
TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256, act="swiglu",
                  norm_eps=1e-5, rope_theta=10000.0, tie_embeddings=False,
                  param_dtype="float32", attn_chunk=16, loss_chunk=16)
TINY_TRAFFIC = dict(clients=1, seqs_per_client=4, seq_len=32, n_classes=10,
                    follow_prob=0.8, pool_rounds=2, check_rounds=3, rows=5,
                    cols=1024, k=32, lr=0.1, momentum=0.9, error_mode="zero",
                    momentum_masking=True, merge="flat", sketch_impl="auto")
DECODER = harness.model_module("decoder")
SEED = 2 ** 31 + 7
FAULTS = ("unchanged", "half_batch", "sign_flip", "moved_ids")


def tiny_spec(chips=1):
    tr = dict(TINY_TRAFFIC, clients=chips)
    return {"cell": {"name": "gpt2s.silo1.c14", "chips": chips},
            "cfg": {"program_arch": "internlm2-1.8b", "reference": "decoder",
                    "model": TINY_MODEL},
            "model": DECODER, "tr": tr, "limits": LIMITS}


def test_command_off_the_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gpt2s.silo1.c14", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "limits", "metrics", "models"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "program_arch": "gpt2s-federated",
         "reference": "tiny_lm", "model": TINY_MODEL}))
    (tmp_path / "models" / "tiny_lm.py").write_text(
        "def param_shapes(cfg):\n    return {}\n")
    (tmp_path / "traffic" / "silo1.tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (tmp_path / "limits" / "tiny.silo1.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.5}}))
    (tmp_path / "metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    bench = {"workloads": [{"name": "tiny.silo1", "config": "tiny",
                            "traffic": "silo1.tiny", "chips": 1}],
             "per_layer": [{"name": "rounds_seen",
                            "workloads": ["tiny.silo1"]}]}
    spec = harness.load_cell("tiny.silo1", bench, root=tmp_path)
    assert spec["tr"]["cols"] == 1024 and spec["cfg"]["name"] == "tiny"
    assert spec["model"].param_shapes(TINY_MODEL) == {}
    assert [m["name"] for m in harness.metric_entries(
        bench, "tiny.silo1", "per_layer")] == ["rounds_seen"]
    assert harness.reader("rounds_seen", root=tmp_path)({"rounds": 3}) == 3.0


def test_traffic_is_fixed_by_the_seed():
    a = traffic.rounds(TINY_TRAFFIC, 256, SEED)
    b = traffic.rounds(TINY_TRAFFIC, 256, SEED)
    c = traffic.rounds(TINY_TRAFFIC, 256, SEED + 1)
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    rows = [r[0][i].tobytes() for r in a for i in range(4)]
    assert len(set(rows)) == len(rows)          # every row differs


def run(fault=None):
    return harness.run(tiny_spec(), BENCH, SEED, 0.2, False,
                       time.perf_counter(), require_chip=False, fault=fault,
                       cache=False)


def test_a_sound_run_reads_below_every_fault():
    sound = run()
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"round_s", "tokens_per_s",
                                     "peak_hbm_bytes", "setup_s"}
    assert list(sound)[-1] == "compared"
    for fault in FAULTS:
        bad = run(fault)["compared"]
        assert any(bad[k]["value"] > 3 * sound["compared"][k]["value"]
                   for k in bad), fault


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(fault):
    assert run(fault)["correct"] is False


def test_the_control_is_not_correct():
    spec = tiny_spec()
    check = traffic.rounds(spec["tr"], 256, SEED)[:3]
    ref = harness.reference_readings(DECODER, spec["cfg"], spec["tr"], SEED,
                                     check)
    ctl = harness.reference_readings(DECODER, spec["cfg"], spec["tr"], SEED,
                                     check, q=reference.int8)
    ok, compared = harness.judge(harness.compare(ctl, ref), LIMITS)
    assert not ok, compared


def test_the_sketch_control_hides_under_the_model_rounding():
    """The sketch's encode and estimate in one bfloat16 MXU pass, the step
    below its stated ``highest``, move the momentum sketch less than the
    program's own bfloat16 model does: the check passes that control, the
    gap that PERF.md names."""
    spec = tiny_spec()
    check = traffic.rounds(spec["tr"], 256, SEED)[:3]
    ref = harness.reference_readings(DECODER, spec["cfg"], spec["tr"], SEED,
                                     check)
    ctl = harness.compare(harness.reference_readings(
        DECODER, spec["cfg"], spec["tr"], SEED, check,
        sketch_q=reference.bf16), ref)
    prog = harness.Program(DECODER, spec["cfg"], spec["tr"], 1, None)
    prog.start(SEED)
    sound = harness.compare(harness.checked_rounds(prog, check), ref)
    assert 1e-4 < ctl["sketch_diff"] < sound["sketch_diff"]
    assert harness.judge(ctl, LIMITS)[0]


def test_the_merge_left_out_is_not_correct():
    """Four CPU devices in a child process, the all-reduce of the sketch
    replaced by the chip's own table."""
    code = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(HERE)!r}, {str(ROOT / 'src')!r},
                        {str(HERE / 'tests')!r}]
        import harness, test_chipbench_run as t
        r = harness.run(t.tiny_spec(4), t.BENCH, t.SEED, 0.2, False,
                        time.perf_counter(), require_chip=False,
                        fault=sys.argv[1] or None, cache=False)
        print(json.dumps(r))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = {}
    for fault in ("", "no_exchange"):
        p = subprocess.run([sys.executable, "-c", code, fault], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        out[fault] = json.loads(p.stdout.splitlines()[-1])
    assert out["no_exchange"]["correct"] is False
    sound, bad = out[""]["compared"], out["no_exchange"]["compared"]
    assert any(bad[k]["value"] > 3 * sound[k]["value"] for k in bad)
