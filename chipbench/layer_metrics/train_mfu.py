"""``train_mfu``: the whole step's share of the chips' peak — images of the
window x 3 x forward FLOPs per image (the benchmark's own count from the
configuration's layer shapes, ``flops.py``) over window x chips x the bf16
peak, over the traced stretch of the window (the rest of a traced run's
window holds the profiler's stop, which no untraced run pays). Recomputed
operations are not counted."""

import flops


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if peaks is None or not w.traced_images:
        return None
    done = w.traced_images * flops.train_flops_per_image(ctx["cfg"])
    return 100.0 * done / (w.traced_s * ctx["chips"] * peaks["bf16_flops_per_s"])
