"""``kda_chunk_roofline``: for the chunked gated delta rule of the traced
steps (the events under the program's scopes ``l<k>.kda.chunk``, forward,
recomputed forward and backward), the least time the chip could take, KDA
layers x steps x ``hybrid_lm_flops.kda_chunk_min_seconds`` (the algorithm's
operations three times over for training, or q, k, v, g, beta, o and their
cotangents moved once each), over their device time. The recomputed forward
adds to the time, not to the work. A program without the scope, or a
configuration without KDA layers, gives nothing to read."""

import hybrid_lm_flops


def read(ctx):
    w, peaks, cfg = ctx["window"], ctx["peaks"], ctx["cfg"]
    if peaks is None or "seq_len" not in cfg or "linear_attn_config" not in cfg:
        return None
    spent, _ = hybrid_lm_flops.scoped_seconds(ctx["reduced"], r"kda\.chunk")
    if not spent or not w.traced_images:
        return None
    batch = int(cfg["batch_size"])
    steps = w.traced_images / batch
    one, bound = hybrid_lm_flops.kda_chunk_min_seconds(cfg, batch, peaks)
    least = hybrid_lm_flops.kda_layers(cfg) * steps * one
    ctx["log"](f"chipbench kda_chunk_roofline: least {least:.6f} s of {spent:.6f} s "
               f"spent under l<k>.kda.chunk over {steps:g} steps; bound by {bound}")
    return 100.0 * least / spent
