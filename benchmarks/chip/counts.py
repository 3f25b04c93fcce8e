"""Operation and byte counts, and the chip's peaks, kept with the benchmark.

Counts come from the configuration and the traffic alone, never from the
program, so that no change to the program can move them.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str, root: Path = HERE) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads((root / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add them with their source")
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer: attention, MLP and two norm scales."""
    d, ff, hd = cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    attn = d * hd * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    mlp = (3 if cfg["act"] == "swiglu" else 2) * d * ff
    return attn + mlp + 2 * d


def param_count(cfg: dict) -> int:
    """d: every weight, the embedding and the untied output head included."""
    d, V = cfg["d_model"], cfg["vocab"]
    return cfg["n_layers"] * layer_params(cfg) + 2 * V * d + d


def non_embedding_params(cfg: dict) -> int:
    """N of 6·N·T: every weight but the input embedding (the head counts)."""
    return param_count(cfg) - cfg["vocab"] * cfg["d_model"]


def model_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward: 6·N plus 12·L·S·(heads·head_dim) for the
    attention scores and mixing, counted over the full S x S square as the
    model computes it."""
    attn_width = cfg["n_heads"] * head_dim(cfg)
    return (6 * non_embedding_params(cfg)
            + 12 * cfg["n_layers"] * seq_len * attn_width)


def encode_least(cfg: dict, tr: dict, grad_bytes: int = 4) -> dict:
    """What any sketch encode of one client's gradient must do: read d
    gradient values, write the (rows, cols) f32 table, and make rows·d
    additions."""
    d = param_count(cfg)
    return {"bytes": d * grad_bytes + tr["rows"] * tr["cols"] * 4,
            "ops": tr["rows"] * d}


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline's least time: the larger of bytes over bandwidth and
    operations over peak."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["ops"] / peak["bf16_flops"])
