"""``moe_device_share``: the share of the device's busy time that goes to the
routed experts' path: operations under the program's scopes ``l<k>.router``,
``l<k>.dispatch``, ``l<k>.experts`` and ``l<k>.combine`` (the shared experts
are not in it), by stable name from the reduced trace, over the busy seconds;
mean over the cell's devices. A program without the scopes gives nothing to
read."""

import lm_flops


def read(ctx):
    if ctx["peaks"] is None:
        return None
    mine, busy = lm_flops.scoped_seconds(ctx["reduced"], "router|dispatch|experts|combine")
    if not mine or not busy:
        return None
    return 100.0 * mine / busy
