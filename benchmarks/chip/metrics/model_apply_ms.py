"""``model_apply_ms``: device time per round that no other layer names.

Layer: client model and sparse apply (``models/transformer``,
``core.topk.apply_delta``).  Moves ``round_s``.  The device's busy
milliseconds per round, less the ops of every other layer: each reader in
this directory that names its layer's ops gives their regular expression
as ``layer_pattern(tr)``, so a reader added here takes its ops out of
this remainder by its file alone.
"""

import importlib.util
from pathlib import Path

import tracing

HERE = Path(__file__).resolve().parent


def layer_patterns(tr: dict) -> list:
    """``layer_pattern(tr)`` of every other reader in this directory."""
    out = []
    for path in sorted(HERE.glob("*.py")):
        if path.stem == Path(__file__).stem:
            continue
        spec = importlib.util.spec_from_file_location(
            f"layer_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, "layer_pattern"):
            out.append(mod.layer_pattern(tr))
    return out


def read(ctx):
    patterns = layer_patterns(ctx["tr"])
    named = (tracing.matching_seconds(ctx["ops"], "|".join(patterns))
             if patterns else 0.0)
    return 1e3 * (ctx["busy_s"] - named) / ctx["rounds"]
