"""``peak_hbm_gb``: the most that was not free on the fullest device at a step
boundary inside the window: ``memory_stats()``'s ``bytes_in_use`` (arrays) plus
``bytes_reserved`` (the loaded programs' temporaries), read together in one
call (``run.memory_now``). The same number as ``device.memory_peak_bytes``."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
