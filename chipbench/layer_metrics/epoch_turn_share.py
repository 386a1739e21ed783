"""``epoch_turn_share``: the share of the window the host spent turning from
one resident epoch to the next, from the program's always-on dispatch log
(``dcnn_tpu.obs.dispatch_log``: one entry an epoch, ``t_call``, ``t_returned``,
``t_fenced``, ``t_published`` on ``time.perf_counter``, the window's clock).
A turn runs from the fence of one epoch (``float(mean_loss)`` back on the
host) to the next epoch's program call having returned, so it holds the
model's ``publish_state``, whatever the caller does between two
``train_epoch`` calls (here the harness's boundary, memory reading and epoch
key) and the dispatch call itself. Counted: the entries called inside the
window; a turn that began before the window opened counts from the opening.
The value is the sum of those turns over the window's elapsed seconds, x 100.
In a traced run the harness stops the profiler at a boundary inside the window
(``window.t_trace_end``), which takes seconds: the turn that holds that instant
is left out of the sum and of the elapsed seconds, and the log line says so.

It should read at or under ``device_idle_share``: what the idle share holds
beyond it the host cannot see (the runtime's launch, the fence's return). The
log line gives the turn's three parts and, for a run that lost an epoch, the
longest fence and the longest turn beside their medians: a slow epoch whose
seconds lie in the fence is the device's or the runtime's, one whose seconds
lie in the turn is the host's. A program without the log gives nothing to
read."""


def read(ctx):
    w = ctx["window"]
    if ctx["peaks"] is None or w is None or w.t_open is None or w.t_close is None:
        return None
    from dcnn_tpu import obs

    log = getattr(obs, "dispatch_log", None)
    if log is None:
        return None
    entries = log()
    stopped = getattr(w, "t_trace_end", None)
    turns, fences, profiler = [], [], 0.0   # (publish, between, dispatch) seconds a turn
    for prev, e in zip([None] + entries, entries):
        if not w.t_open <= e.t_call < w.t_close:
            continue
        fences.append(e.t_fenced - e.t_returned)
        if prev is not None:
            start = max(prev.t_fenced, w.t_open)
            if stopped is not None and start <= stopped <= e.t_returned:
                profiler = e.t_returned - start
                continue
            published = max(prev.t_published, start)
            turns.append((published - start, e.t_call - published, e.t_returned - e.t_call))
    if not turns:
        return None
    whole = sorted(sum(t) for t in turns)
    ms = [1e3 * sum(t[i] for t in turns) / len(turns) for i in range(3)]
    ctx["log"](f"chipbench epoch_turn_share: {len(turns)} turns, mean {sum(ms):.2f} ms a turn "
               f"(publish {ms[0]:.2f}, between train_epoch calls {ms[1]:.2f}, the dispatch "
               f"call {ms[2]:.2f}); median {1e3 * whole[len(whole) // 2]:.2f}, longest "
               f"{1e3 * whole[-1]:.2f}; fences: median {sorted(fences)[len(fences) // 2]:.4f} s, "
               f"longest {max(fences):.4f} s (epoch {fences.index(max(fences)) + 1} of "
               f"{len(fences)} in the window)"
               + (f"; left out: the turn of {1e3 * profiler:.2f} ms in which the harness "
                  f"stopped the profiler" if profiler else ""))
    return 100.0 * sum(whole) / (w.elapsed - profiler)
