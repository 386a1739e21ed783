"""``mla_flash_roofline``: for the flash attention kernels of the traced
steps (forward, dq and dkv events under the program's scopes
``l<k>.attn.flash``), the least time the chip could take, layers x steps x
``lm_flops.flash_min_seconds`` (causal half, q k^T at 192, p v at 128, each
operand once), over their device time. The kernel's companions in the scope
(the backward's rowsum(dO * O)) add to the time and make it read low. A
program without the scope gives nothing to read."""

import lm_flops


def read(ctx):
    w, peaks, cfg = ctx["window"], ctx["peaks"], ctx["cfg"]
    if peaks is None or "seq_len" not in cfg:
        return None
    spent, _ = lm_flops.scoped_seconds(ctx["reduced"], r"attn\.flash")
    if not spent or not w.traced_images:
        return None
    batch = int(cfg["batch_size"])
    steps = w.traced_images / batch
    one, bound = lm_flops.flash_min_seconds(cfg, batch, peaks)
    least = cfg["num_hidden_layers"] * steps * one
    ctx["log"](f"chipbench mla_flash_roofline: least {least:.6f} s of {spent:.6f} s "
               f"spent under l<k>.attn.flash over {steps:g} steps; bound by {bound}")
    return 100.0 * least / spent
