"""``named_apply_ms``: device time per round of the sparse apply of the top-k
update to the weights, by the program's names: the ``sparse_apply`` scope
(``launch.steps``, around ``core.fetchsgd.apply_delta``).

Layer: sparse apply. Moves ``round_s``. Read through ``layer_map``; nothing
where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "sparse_apply")
