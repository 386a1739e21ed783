"""``compile_s``: the seconds of set-up spent in XLA backend compiles, loads
from the persistent compile cache included: the program's own log of JAX's
compile events (``dcnn_tpu.obs.xla.compile_log``), summed over the
``backend_compile`` entries stamped before the window opened. JAX takes that
duration round its cache lookup, so a cache hit's load time is inside it and
the ``cache_load`` entries are not added again. Not the totals at the end of
the run: the harness compiles its float32 reference after the window, in the
same process. The log and the window both stamp with ``time.perf_counter``.
A program without the log gives nothing to read."""


def read(ctx):
    w = ctx["window"]
    if ctx["peaks"] is None or w is None or w.t_open is None:
        return None
    from dcnn_tpu.obs import xla

    log = getattr(xla, "compile_log", None)
    if log is None:
        return None
    before = [e for e in log() if e[0] < w.t_open]
    if not before:
        return None
    loads = sum(s for _, s, what in before if what == "cache_load")
    total = sum(s for _, s, what in before if what == "backend_compile")
    ctx["log"](f"chipbench compile_s: {sum(1 for e in before if e[2] == 'backend_compile')} "
               f"backend compiles before the window, {total:.3f} s, of which "
               f"{loads:.3f} s were loads from the persistent cache "
               f"({sum(1 for e in before if e[2] == 'cache_hit')} hits, "
               f"{sum(1 for e in before if e[2] == 'cache_miss')} entries written)")
    return total
