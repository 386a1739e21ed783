"""``loop_gap_share``: device-idle time of the trace that falls outside the
feed-wait spans (dispatch, ``float(loss)``, scheduler, epoch turn-over),
over the traced stretch of the window."""


def read(ctx):
    r = ctx["reduced"]
    if not r or not r.get("devices") or "next_batch" not in r.get("spans", {}):
        return None
    idle = r["idle_by_span"]
    outside = sum(s for what, s in idle.items() if what != "next_batch")
    return 100.0 * outside / r["window_s"]
