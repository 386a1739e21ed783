"""``feed_prep_ms`` (kept, not listed: the fed cell is not in
``BENCHMARK.json``): the producer thread's host preparation per batch: the
program's own counters ``feed_prep_seconds_total`` (the inner loader's
``__next__``, the host transform, chunk stacking) over ``feed_batches_total``
(``PrefetchLoader``), in milliseconds, over the whole process (set-up's
checked and warm-up steps included). A program without those counters gives
nothing to read."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    from dcnn_tpu.obs import get_registry

    snap = get_registry().snapshot()
    seconds, batches = snap.get("feed_prep_seconds_total"), snap.get("feed_batches_total")
    if not seconds or not batches:
        return None
    return 1e3 * seconds / batches
