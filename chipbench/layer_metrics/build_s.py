"""``build_s``: the seconds of set-up in which the program constructs itself:
the ``setup.*`` phases of its always-on phase log (``dcnn_tpu.obs.phase_log``:
``setup.config`` in ``examples/common.setup``, ``setup.model`` in
``create_model``, ``setup.trainer`` in ``Trainer.__init__``, ``setup.state``
in ``create_train_state``) that ended before the window opened; the union of
their intervals, which is their sum unless one phase holds another. The
harness's own part of its build (data and weights drawn from the seed, the
reference's files) is in no phase, and staging has its own metric
(``stage_h2d_gbps``). A program without the log gives nothing to read."""


def read(ctx):
    w = ctx["window"]
    if ctx["peaks"] is None or w is None or w.t_open is None:
        return None
    from dcnn_tpu import obs

    log = getattr(obs, "phase_log", None)
    if log is None:
        return None
    phases = [(name, t0, t1) for name, t0, t1 in log()
              if name.startswith("setup.") and t1 < w.t_open]
    if not phases:
        return None
    from dcnn_tpu.data.transfer import union_seconds

    ctx["log"]("chipbench build_s: " + ", ".join(f"{name} {t1 - t0:.3f} s"
                                                 for name, t0, t1 in phases))
    return union_seconds([(t0, t1) for _, t0, t1 in phases])
