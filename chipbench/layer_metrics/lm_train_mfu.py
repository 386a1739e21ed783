"""``lm_train_mfu``: the whole step's share of the chips' peak in a cell
whose window counts sequences: sequences of the traced stretch x ``seq_len``
tokens x 3 x the forward operations a token of the cut model
(``lm_flops.py``: the held parameters' products, causal scores exactly, routed
experts at an even spread; recomputation not counted) over traced seconds x
chips x the bf16 peak. A configuration that is no language model gives
nothing to read."""

import lm_flops


def read(ctx):
    w, peaks, cfg = ctx["window"], ctx["peaks"], ctx["cfg"]
    if peaks is None or not w.traced_images or "seq_len" not in cfg:
        return None
    done = w.traced_images * lm_flops.train_flops_per_sequence(cfg)
    return 100.0 * done / (w.traced_s * ctx["chips"] * peaks["bf16_flops_per_s"])
