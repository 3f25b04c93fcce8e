"""``named_topk_ms``: device time per round of the top-k over the estimates:
each chunk's candidates and the final selection, by the program's names: the
``topk`` scope (``core.topk.topk_from_sketch``) outside the ``unsketch``
scope inside it.

Layer: unsketch and top-k. Moves ``round_s``. Read through ``layer_map``;
nothing where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "topk")
