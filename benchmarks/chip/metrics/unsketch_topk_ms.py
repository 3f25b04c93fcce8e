"""``unsketch_topk_ms``: device time per round of the estimate of every
coordinate from the error sketch and of the top-k over the estimates.

Layer: unsketch and top-k (``core.topk.topk_from_sketch``).  Moves
``round_s``.  The estimate is the custom call that turns the id offset and
the transposed ``(rows, 128, cols/128)`` table into a lane-dense ``(n,
128)`` block of estimates; XLA lowers each ``lax.top_k`` over a chunk to a
sort of ``(f32[n], s32[n])`` pairs.  Nothing where no estimate kernel ran
(the jnp path, whose gathers carry no name).
"""

import tracing

SORT = r"= \(f32\[\d+\]\S*, s32\[\d+\]\S*\) sort\("


def pattern(tr: dict) -> str:
    rows, co = tr["rows"], tr["cols"] // 128
    return (rf"= f32\[\d+,128\]\S* custom-call\(u32\[2\]\S* \S+, "
            rf"f32\[{rows},128,{co}\]")


def layer_pattern(tr: dict) -> str:
    return pattern(tr) + "|" + SORT


def read(ctx):
    if tracing.ms_per_round(ctx, pattern(ctx["tr"])) is None:
        return None
    return tracing.ms_per_round(ctx, layer_pattern(ctx["tr"]))
