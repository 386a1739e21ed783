"""``device_idle_share``: 1 - the union of device-operation intervals over
the traced stretch of the window, mean over the cell's devices."""


def read(ctx):
    r = ctx["reduced"]
    if not r or not r.get("devices") or not r["window_s"]:
        return None
    return 100.0 * (1.0 - r["busy_s_mean"] / r["window_s"])
