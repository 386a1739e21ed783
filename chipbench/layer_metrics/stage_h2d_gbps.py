"""``stage_h2d_gbps``: the rate at which the program staged its resident
splits into device memory during set-up: the program's own counters
``data_stage_bytes_total`` / ``data_stage_seconds_total``
(``DeviceDataset.__init__``, to the staged array's fence), in GB/s.
A program without those counters gives nothing to read."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    from dcnn_tpu.obs import get_registry

    snap = get_registry().snapshot()
    staged, seconds = snap.get("data_stage_bytes_total"), snap.get("data_stage_seconds_total")
    if not staged or not seconds:
        return None
    return staged / seconds / 1e9
