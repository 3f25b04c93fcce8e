"""``encode_ms``: device time per round of the client's sketch encode.

Layer: sketch encode (``core.fetchsgd.sketch_grads`` ->
``kernels.count_sketch``).  Moves ``round_s``.  The trace names no kernel:
a Pallas call is a ``tpu_custom_call`` whose HLO carries no kernel name.
The encode is the custom call that turns the 64-bit id offset and a
lane-dense ``(n, 128)`` value block into the ``(rows, cols/128, 128)``
table.  Nothing where no such op ran (the jnp path).
"""

import tracing


def pattern(tr: dict) -> str:
    rows, co = tr["rows"], tr["cols"] // 128
    return (rf"= f32\[{rows},{co},128\]\S* custom-call\(u32\[2\]\S* \S+, "
            rf"f32\[\d+,128\]")


layer_pattern = pattern


def read(ctx):
    return tracing.ms_per_round(ctx, pattern(ctx["tr"]))
