"""``feed_wait_share``: the time the train loop spends inside the loader's
``__next__`` (the harness's ``next_batch`` spans round the wrapped loader)
over the traced stretch of the window."""


def read(ctx):
    r = ctx["reduced"]
    if not r or "next_batch" not in r.get("spans", {}) or not r["window_s"]:
        return None
    return 100.0 * r["spans"]["next_batch"] / r["window_s"]
