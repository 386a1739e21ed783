"""``expert_gmm_roofline``: for the held experts' grouped products of the
traced steps (the events under the program's scopes ``l<k>.experts``:
gate, up and down products, their two transposes each, and the activation
between them), the least time the chip could take for the (token, expert)
pairs those steps really computed, ``lm_flops.expert_min_seconds``, over
their device time. The pairs are the program's counter
``moe_pairs_held_total``, which the driver reads epoch by epoch; without it,
or without the scope, there is nothing to read. Recomputed forward products
add to the time, not to the work."""

import lm_flops


def read(ctx):
    w, peaks, cfg = ctx["window"], ctx["peaks"], ctx["cfg"]
    by_epoch = ctx["counters"].get("moe_pairs_held_by_epoch")
    if peaks is None or not by_epoch or "seq_len" not in cfg or not w.traced_images:
        return None
    spent, _ = lm_flops.scoped_seconds(ctx["reduced"], "experts")
    if not spent:
        return None
    steps = w.traced_images / int(cfg["batch_size"])
    epochs = w.traced_images * len(by_epoch) // w.images
    pairs = sum(by_epoch[:epochs])
    if not pairs:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least, bound = lm_flops.expert_min_seconds(cfg, pairs, layers * steps, peaks)
    ctx["log"](f"chipbench expert_gmm_roofline: {pairs} held pairs in {epochs} traced "
               f"epochs ({pairs / (layers * steps):.0f} a layer and step); least "
               f"{least:.6f} s of {spent:.6f} s under l<k>.experts; bound by {bound}")
    return 100.0 * least / spent
