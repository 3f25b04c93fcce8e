"""``named_backward_ms``: device time per round of the client model's backward
pass, with the forward ops a remat recomputes, by the program's names: the
ops of the ``client_model`` scope whose path holds ``transpose(jvp(``.

Layer: client model. Moves ``round_s``. Read through ``layer_map``; nothing
where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "backward")
