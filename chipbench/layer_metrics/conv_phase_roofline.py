"""``conv_phase_roofline``: the same share as ``conv_roofline``, for a
convolution that the program computes once per position of the 2x2 pooling
window behind it (``dcnn_tpu/nn/sequential.py``: four stride-2 products under
the scope ``<layer>.phase``, forward and weight gradient alike), which
``conv_roofline`` does not match: the least time the chip could take for the
layer's products over the device time of the events that carry them.

An event is a phase of the configuration's convolution ``<layer>`` when the
last ``jvp(...)`` of its scope is ``<layer>.phase``; it is a weight gradient
where the weight-shaped array is the instruction's result. Each event is
credited a quarter of the whole product's least time
(``flops.conv_min_seconds``): a quarter of its FLOPs, and a quarter of the
least bytes, which read the input once for the layer and not once per phase.
So the layer's least time is counted once over the four events that carry
it, and the share cannot pass 100%; what XLA fused into a phase's event (the
batch-norm statistics, the optimizer's update) adds to its time and makes it
read low. A program without such a scope (every program before the rewrite,
ResNet-50) gives nothing to read."""

import re

import flops

SUFFIX = ".phase"
PHASES = 4          # the positions of a 2x2 window
_SHAPE = re.compile(r"(?:bf16|f32|f16)\[(\d+),(\d+),(\d+),(\d+)\]")


def read(ctx):
    r, peaks = ctx["reduced"], ctx["peaks"]
    if not r or peaks is None or not r.get("conv_events"):
        return None
    convs = flops.conv_layers(ctx["cfg"])
    batch = int(ctx["traffic"].get("program_batch", ctx["cfg"]["batch_size"]))
    least = spent = 0.0
    events = {"fwd": 0, "wgrad": 0}
    for text, scope, seconds in r["conv_events"]:
        blocks = re.findall(r"jvp\(([^()]+)\)", scope)
        if not blocks or not blocks[-1].endswith(SUFFIX):
            continue
        g = convs.get(blocks[-1][:-len(SUFFIX)])
        if g is None:
            continue
        want = sorted((g["cout"], g["cin"], g["k"], g["k"]))
        head = text.split(" fusion(")[0].split(" convolution(")[0]
        kind = ("wgrad" if any(sorted(map(int, s)) == want for s in _SHAPE.findall(head))
                else "fwd")
        least += flops.conv_min_seconds(kind, batch, g, peaks)[0] / PHASES
        spent += seconds
        events[kind] += 1
    if not spent:
        return None
    ctx["log"](f"chipbench conv_phase_roofline: least {least:.6f} s of {spent:.6f} s spent "
               f"in {events['fwd']} forward and {events['wgrad']} weight-gradient phase events")
    return 100.0 * least / spent
