"""``hybrid_lm_train_mfu``: the whole step's share of the chips' peak in a
cell that trains a hybrid decoder (Kimi Delta Attention beside latent
attention; the configuration has ``linear_attn_config``) and whose window
counts sequences: sequences of the traced stretch x ``seq_len`` tokens x 3 x
the forward operations a token of the cut model (``hybrid_lm_flops.py``)
over traced seconds x chips x the bf16 peak; recomputation not counted. Any
other configuration gives nothing to read."""

import hybrid_lm_flops


def read(ctx):
    w, peaks, cfg = ctx["window"], ctx["peaks"], ctx["cfg"]
    if (peaks is None or not w.traced_images or "seq_len" not in cfg
            or "linear_attn_config" not in cfg):
        return None
    done = w.traced_images * hybrid_lm_flops.train_flops_per_sequence(cfg)
    return 100.0 * done / (w.traced_s * ctx["chips"] * peaks["bf16_flops_per_s"])
