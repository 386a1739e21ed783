"""``first_dispatch_s``: the seconds of set-up from the first call of the
resident epoch's program to its loss on the host: tracing, lowering, the
compile or the load from the persistent cache, the program's load onto the
device, and the first epoch run. From the program's always-on dispatch log
(``dcnn_tpu.obs.dispatch_log``): ``t_fenced - t_call`` of the entries marked
``first`` that were fenced before the window opened. The log line takes off
what ``compile_log()`` names inside that stretch (``trace``, ``lower``,
``backend_compile``; the union of their intervals ``[stamp - seconds,
stamp]``): what is left is the program's load and its first run. A program
without the log gives nothing to read."""


def read(ctx):
    w = ctx["window"]
    if ctx["peaks"] is None or w is None or w.t_open is None:
        return None
    from dcnn_tpu import obs

    log = getattr(obs, "dispatch_log", None)
    if log is None:
        return None
    firsts = [(e.t_call, e.t_fenced) for e in log() if e.first and e.t_fenced < w.t_open]
    if not firsts:
        return None
    from dcnn_tpu.data.transfer import union_seconds
    from dcnn_tpu.obs.xla import compile_log

    compiles = compile_log()

    def inside(*kinds):
        return union_seconds([(max(stamp - s, a), min(stamp, b))
                              for stamp, s, what in compiles if what in kinds
                              for a, b in firsts if stamp - s < b and stamp > a])
    total = sum(b - a for a, b in firsts)
    named = inside("trace", "lower", "backend_compile")
    ctx["log"](f"chipbench first_dispatch_s: {len(firsts)} first dispatch(es), {total:.3f} s: "
               f"tracing {inside('trace'):.3f}, lowering {inside('lower'):.3f}, backend "
               f"compiles and cache loads {inside('backend_compile'):.3f}; the program's "
               f"load and its first run {total - named:.3f}")
    return total
