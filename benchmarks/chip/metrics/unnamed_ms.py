"""``unnamed_ms``: device time per round of the ops that no layer of the
program names: neither a scope of ``repro.obs.layers`` nor a kernel name
places their instruction, nor the work they read or feed.

Layer: device. Moves ``round_s``. With the named layers it sums to the
device's busy time per round. Read through ``layer_map``; nothing where the
program names no layers.
"""

import layer_map


def read(ctx):
    return layer_map.read(ctx, layer_map.UNNAMED)
