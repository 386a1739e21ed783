"""``feed_h2d_gbps`` (kept, not listed: the fed cell is not in
``BENCHMARK.json``): the rate of the producer thread's puts to the device:
the program's own counters ``feed_put_bytes_total`` /
``feed_put_seconds_total`` (``PrefetchLoader``: ``device_put`` and the device
transform's dispatch, not fenced, so a put that returns before its copy ends
reads high), in GB/s, over the whole process. A program without those
counters gives nothing to read."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    from dcnn_tpu.obs import get_registry

    snap = get_registry().snapshot()
    put, seconds = snap.get("feed_put_bytes_total"), snap.get("feed_put_seconds_total")
    if not put or not seconds:
        return None
    return put / seconds / 1e9
