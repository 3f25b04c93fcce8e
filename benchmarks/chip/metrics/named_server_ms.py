"""``named_server_ms``: device time per round of FetchSGD's server state:
momentum and error in sketch space, the extracted ids and their hit mask, by
the program's names: the ``server_state`` scope
(``core.fetchsgd.server_step``) outside the unsketch and top-k inside it,
and the ``fetchsgd_momentum_error`` and ``fetchsgd_topk_mask`` kernels.

Layer: server state. Moves ``round_s``. Read through ``layer_map``; nothing
where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "server_state")
