"""``kda_device_share``: the share of the device's busy time that goes to
Kimi Delta Attention: operations under the program's scopes ``l<k>.kda``
(projections, convolutions, gates, the output's norm and projection) and
``l<k>.kda.chunk`` (the chunked rule), by stable name from the reduced trace,
over the busy seconds; mean over the cell's devices. A program without the
scopes gives nothing to read."""

import hybrid_lm_flops


def read(ctx):
    if ctx["peaks"] is None:
        return None
    mine, busy = hybrid_lm_flops.scoped_seconds(ctx["reduced"], r"kda|kda\.chunk")
    if not mine or not busy:
        return None
    return 100.0 * mine / busy
