"""``named_encode_ms``: device time per round of the sketch encode, by the
program's names: the ``sketch_encode`` scope (``launch.steps``, around
``core.fetchsgd.sketch_grads`` and its jnp twin) and the ``fetchsgd_encode``
kernel.

Layer: sketch encode. Moves ``round_s``. Read through ``layer_map``; nothing
where no such op ran or the program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, "sketch_encode")
