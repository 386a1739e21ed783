"""``conv_roofline``: for all convolution events of the trace (forward,
input-gradient and weight-gradient products), the least time the chip could
take, sum of max(FLOPs / peak FLOP/s, bytes / peak bytes/s) from the
benchmark's own shape functions (``flops.py``), over the sum of their device
time.

An event is matched to a convolution of the configuration by its scope (the
block's name from ``jax.named_scope``) and the weight shape in the
instruction's text; the product is a weight gradient where the weight-shaped
array is the instruction's result, and otherwise reads the weights (forward
and input gradient cost the same by this count). Events that match nothing
are left out of both sums. The bytes are the least the algorithm needs, each
operand read once and the result written once, so the share cannot pass
100%; neighbours that XLA fused into a convolution's event (bias, batch-norm
statistics, the optimizer's update of that weight) add to its time and make
it read low. The reader logs how much of the least time is bound by bytes."""

import re

import flops

_SHAPE = re.compile(r"(?:bf16|f32|f16)\[(\d+),(\d+),(\d+),(\d+)\]")


def _block(scope):
    m = re.findall(r"jvp\(([^()]+)\)", scope)
    return m[-1] if m else ""


def read(ctx):
    r, peaks = ctx["reduced"], ctx["peaks"]
    if not r or peaks is None or not r.get("conv_events"):
        return None
    convs = flops.conv_layers(ctx["cfg"])
    batch = int(ctx["traffic"].get("program_batch", ctx["cfg"]["batch_size"]))
    least = spent = by_bytes = 0.0
    for text, scope, seconds in r["conv_events"]:
        block = _block(scope)
        mine = {n: g for n, g in convs.items()
                if n == block or n.startswith(block + "/")} if block else {}
        head = text.split(" fusion(")[0].split(" convolution(")[0]
        found = None
        for name, g in mine.items():
            want = sorted((g["cout"], g["cin"], g["k"], g["k"]))
            in_head = any(sorted(map(int, s)) == want for s in _SHAPE.findall(head))
            in_all = any(sorted(map(int, s)) == want for s in _SHAPE.findall(text))
            if in_all:
                found = (g, "wgrad" if in_head else "fwd")
                if in_head:
                    break
        if found is None:
            continue
        t, bound = flops.conv_min_seconds(found[1], batch, found[0], peaks)
        least += t
        spent += seconds
        if bound == "bytes":
            by_bytes += t
    if not spent:
        return None
    ctx["log"](f"chipbench conv_roofline: least {least:.6f} s of {spent:.6f} s "
               f"spent in matched convolution events; {100 * by_bytes / least:.1f}% "
               f"of the least time is bound by bytes, the rest by FLOPs")
    return 100.0 * least / spent
