"""``mfu``: the whole step's share of the chips' peak bf16 FLOP/s.

Layer: device.  Moves ``round_s``.  The model's forward and backward
operations per round (``counts.model_flops_per_token`` of the
configuration's model reference, times every client's tokens), over the
traced run's round time (host clock) times the chips times the peak.
The sketch's operations are never counted: a faster sketch shows as a
higher share of the same model work.
"""

import counts


def read(ctx):
    tr = ctx["tr"]
    tokens = tr["clients"] * tr["seqs_per_client"] * tr["seq_len"]
    flops = counts.model_flops_per_token(ctx["model"], ctx["cfg"],
                                         tr["seq_len"]) * tokens
    peak = ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * flops / (ctx["round_s"] * peak)
