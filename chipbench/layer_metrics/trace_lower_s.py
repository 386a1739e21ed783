"""``trace_lower_s``: the seconds of set-up spent tracing jitted functions to
jaxprs and lowering them to MLIR modules, which ``compile_s`` (the backend's
part) does not hold: the ``trace`` and ``lower`` entries of the program's
``compile_log()`` (``dcnn_tpu.obs.xla``, JAX's own
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events) stamped
before the window opened. An entry covers ``[stamp - seconds, stamp]`` and a
function traced inside another's trace lies inside it, so the value is the
union of the intervals, not their sum. Not the counters' totals at the end of
the run: the harness traces its float32 reference after the window, in the
same process. A program whose log has no such entries gives nothing to
read."""


def read(ctx):
    w = ctx["window"]
    if ctx["peaks"] is None or w is None or w.t_open is None:
        return None
    from dcnn_tpu.obs import xla

    log = getattr(xla, "compile_log", None)
    if log is None:
        return None
    before = [e for e in log() if e[2] in ("trace", "lower") and e[0] < w.t_open]
    if not before:
        return None
    from dcnn_tpu.data.transfer import union_seconds

    def said(kind):
        of = [(s, stamp) for stamp, s, what in before if what == kind]
        if not of:
            return f"{kind} nothing"
        longest, ended = max(of)
        return (f"{kind} {union_seconds([(stamp - s, stamp) for s, stamp in of]):.3f} s in "
                f"{len(of)} entries, the longest {longest:.3f} s ending {w.t_open - ended:.1f} s "
                f"before the window")
    ctx["log"](f"chipbench trace_lower_s: before the window, {said('trace')}; {said('lower')}")
    return union_seconds([(stamp - s, stamp) for stamp, s, _ in before])
