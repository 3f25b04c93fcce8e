"""Operation and byte counts, and the chip's peaks, kept with the benchmark.

Counts come from the configuration, its model reference (``models/``) and
the traffic alone, never from the program, so that no change to the
program can move them.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str, root: Path = HERE) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads((root / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add them with their source")
    return table[device_kind]


def param_count(model, cfg: dict) -> int:
    """d: every weight of ``model``'s ``param_shapes``, which are the
    weights one chip holds."""
    return sum(math.prod(shape)
               for shape, _ in model.param_shapes(cfg).values())


def model_flops_per_token(model, cfg: dict, seq_len: int) -> int:
    """Forward and backward operations per token, as ``model`` counts
    them: over the weights a token multiplies."""
    return model.flops_per_token(cfg, seq_len)


def encode_least(model, cfg: dict, tr: dict, grad_bytes: int = 4) -> dict:
    """What any sketch encode of one client's gradient must do: read d
    gradient values, write the (rows, cols) f32 table, and make rows·d
    additions."""
    d = param_count(model, cfg)
    return {"bytes": d * grad_bytes + tr["rows"] * tr["cols"] * 4,
            "ops": tr["rows"] * d}


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline's least time: the larger of bytes over bandwidth and
    operations over peak."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["ops"] / peak["bf16_flops"])
