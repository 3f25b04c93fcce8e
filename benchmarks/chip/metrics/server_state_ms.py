"""``server_state_ms``: device time per round of FetchSGD's server state:
momentum and error in sketch space, and the hit mask of the extracted ids.

Layer: server state (``kernels.server_step``).  Moves ``round_s``.  The
momentum and error kernel is the custom call from the learning rate and
three ``(rows, cols)`` tables to two; the hit-mask kernel the custom call
from the ids' words to four ``(rows, cols/128, 128)`` tables.  Nothing
where neither ran (the jnp twins carry no name).
"""

import tracing


def pattern(tr: dict) -> str:
    rows, cols, co = tr["rows"], tr["cols"], tr["cols"] // 128
    table = rf"f32\[{rows},{cols}\]\S*"
    tile = rf"f32\[{rows},{co},128\]\S*"
    return (rf"= \({table}, {table}\) custom-call\(f32\[1\]"
            rf"|= \({tile}, {tile}, {tile}, {tile}\) custom-call\(u32\[")


layer_pattern = pattern


def read(ctx):
    return tracing.ms_per_round(ctx, pattern(ctx["tr"]))
