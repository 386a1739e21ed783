"""``data_device_share``: the share of the device's busy time that goes to
the input path on the device: operations under the program's scopes ``data``
(batch gather from the resident split, decode, augmentation, one-hot) and
``shuffle`` (the epoch's permutation), by stable name from the reduced
trace, over the busy seconds; mean over the cell's devices. An operation
that XLA fused into a layer's event keeps that layer's name and is not
counted here. A program without the scopes gives nothing to read."""

SCOPES = ("data/", "shuffle/")


def share(ctx, scopes):
    r = ctx["reduced"]
    if ctx["peaks"] is None or not r or not r.get("devices"):
        return None
    shares, found = [], False
    for d in r["devices"].values():
        mine = sum(s for name, s in d["ops"].items() if name.startswith(scopes))
        found = found or mine > 0
        if d["busy_s"]:
            shares.append(100.0 * mine / d["busy_s"])
    if not found or not shares:
        return None
    return sum(shares) / len(shares)


def read(ctx):
    return share(ctx, SCOPES)
