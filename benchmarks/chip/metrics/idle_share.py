"""``idle_share``: the share of the traced window in which no op ran on the
device, averaged over the chips.

Layer: device.  Moves ``round_s``.  Busy time is the union of the device
ops' intervals inside the window, so overlapping ops count once.
"""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
