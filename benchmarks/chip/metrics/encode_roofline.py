"""``encode_roofline``: the sketch encode's share of its roofline.

Layer: sketch encode.  Moves ``round_s``.  The least time any encode of
one client's gradient could take (``counts.encode_least``: read d gradient
values, d from the configuration's model reference, write the f32 table,
make rows·d additions, at the chip's peaks) over the encode's device time
per round on a chip; nothing where ``encode_ms`` finds no op.
"""

import counts
import encode_ms


def read(ctx):
    ms = encode_ms.read(ctx)
    if ms is None:
        return None
    work = counts.encode_least(ctx["model"], ctx["cfg"], ctx["tr"])
    least = counts.least_seconds(work, ctx["peak"])
    return 100.0 * least / (ms / 1e3)
