"""``named_unsketch_ms``: device time per round of the row estimate of every
coordinate from the error sketch, by the program's names: the ``unsketch``
scope (``core.topk.topk_from_sketch``, around the estimate and its jnp twin)
and the ``fetchsgd_estimate`` kernel.

Layer: unsketch and top-k. Moves ``round_s``. Read through ``layer_map``;
nothing where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "unsketch")
