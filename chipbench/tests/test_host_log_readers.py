"""The readers of the program's host logs (PR 37): ``epoch_turn_share`` and
``first_dispatch_s`` over ``dcnn_tpu.obs.dispatch_log``, ``trace_lower_s``
over ``compile_log``'s ``trace`` and ``lower`` entries, ``build_s`` over
``phase_log``. Each on a made-up log and window: the value, ``None`` for a
rehearsal, ``None`` against a program without the log, and entries outside
the window left out. ``tests/test_trainer_timeline.py`` collects these too, so
that tier-1 runs them."""

import importlib.util
import os
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(peaks=PEAKS, t_open=100.0, t_close=130.0, said=None, t_trace_end=None):
    window = types.SimpleNamespace(t_open=t_open, t_close=t_close, t_trace_end=t_trace_end,
                                   elapsed=None if t_close is None else t_close - t_open)
    return {"reduced": {}, "peaks": peaks, "window": window, "cfg": {}, "traffic": {},
            "chips": 1, "memory_peak_bytes": 0, "counters": {},
            "log": (lambda *a: None) if said is None else said.append}


def dispatches():
    """The warm-up epoch (a first dispatch of 9 s, then the harness's own
    10 s before the window opens at 100), three epochs of 10 s in the window,
    and one after it."""
    from dcnn_tpu.obs import Dispatch
    return [Dispatch(80.0, 84.0, 89.0, 89.5, 8, True),
            Dispatch(100.2, 100.3, 110.0, 110.1, 8, False),     # from the opening: 0.3
            Dispatch(110.4, 110.6, 120.0, 120.1, 8, False),     # 0.1 + 0.3 + 0.2
            Dispatch(120.2, 120.4, 129.9, 130.0, 8, False),     # 0.1 + 0.1 + 0.2
            Dispatch(131.0, 131.5, 140.0, 140.0, 8, False)]     # after the window


COMPILES = [(70.0, 0.5, "trace"), (70.2, 0.1, "lower"), (71.0, 0.7, "backend_compile"),
            (81.0, 0.4, "trace"),                     # inside the next one: counts once
            (82.0, 2.0, "trace"), (82.5, 0.5, "lower"), (83.9, 1.2, "backend_compile"),
            (150.0, 3.0, "trace"), (160.0, 30.0, "backend_compile")]   # the reference

PHASES = [("setup.config", 60.0, 61.0), ("setup.model", 61.5, 63.0),
          ("setup.trainer", 63.0, 63.25), ("data.other", 64.0, 70.0),
          ("setup.model", 140.0, 150.0)]              # after the window


# reader -> (the log's home, its name there, the entries, the value in the window [100, 130])
CASES = {
    "epoch_turn_share": ("dcnn_tpu.obs", "dispatch_log", dispatches, 100 * 1.3 / 30),
    "first_dispatch_s": ("dcnn_tpu.obs", "dispatch_log", dispatches, 9.0),
    "trace_lower_s": ("dcnn_tpu.obs.xla", "compile_log", lambda: COMPILES, 0.5 + 0.1 + 2.0 + 0.5),
    "build_s": ("dcnn_tpu.obs", "phase_log", lambda: PHASES, 1.0 + 1.5 + 0.25),
}


@pytest.fixture(params=sorted(CASES))
def case(request, monkeypatch):
    home, attr, entries, want = CASES[request.param]
    module = importlib.import_module(home)
    monkeypatch.setattr(module, attr, lambda: list(entries()), raising=False)
    return request.param, module, attr, want


def test_reader_gives_the_value_of_its_window(case):
    name, _, _, want = case
    assert reader(name)(ctx()) == pytest.approx(want)


def test_reader_gives_nothing_in_a_rehearsal(case):
    assert reader(case[0])(ctx(peaks=None)) is None


def test_reader_leaves_out_what_lies_outside_the_window(case):
    # a window before every entry: nothing to read
    assert reader(case[0])(ctx(t_open=5.0, t_close=6.0)) is None


def test_reader_gives_nothing_without_the_log(case, monkeypatch):
    name, module, attr, _ = case
    if name == "trace_lower_s":
        # the parent has the log, without these entries
        monkeypatch.setattr(module, attr, lambda: [e for e in COMPILES
                                                   if e[2] == "backend_compile"])
    else:
        monkeypatch.delattr(module, attr)
    assert reader(name)(ctx()) is None


def test_turn_share_says_where_a_lost_epoch_lies(monkeypatch):
    import dcnn_tpu.obs as obs
    monkeypatch.setattr(obs, "dispatch_log", dispatches)
    said = []
    assert reader("epoch_turn_share")(ctx(said=said)) == pytest.approx(100 * 1.3 / 30)
    line, = said
    assert "3 turns" in line and "mean 433.33 ms a turn" in line
    assert "publish 66.67, between train_epoch calls 200.00, the dispatch call 166.67" in line
    assert "longest 9.7000 s (epoch 1 of 3" in line
    # an open window has no elapsed time yet
    assert reader("epoch_turn_share")(ctx(t_close=None)) is None
    # a traced run: the harness stops the profiler in the second turn (110.0 to 110.6),
    # which leaves the sum and the elapsed seconds
    said.clear()
    assert reader("epoch_turn_share")(ctx(said=said, t_trace_end=110.05)) \
        == pytest.approx(100 * 0.7 / 29.4)
    assert "2 turns" in said[0] and "left out: the turn of 600.00 ms" in said[0]


def test_first_dispatch_takes_off_what_the_compile_log_names(monkeypatch):
    import dcnn_tpu.obs as obs
    from dcnn_tpu.obs import xla
    monkeypatch.setattr(obs, "dispatch_log", dispatches)
    monkeypatch.setattr(xla, "compile_log", lambda: list(COMPILES))
    said = []
    assert reader("first_dispatch_s")(ctx(said=said)) == pytest.approx(9.0)
    line, = said
    # the nested trace counts once, and the entries before the call not at all
    assert "tracing 2.000, lowering 0.500, backend compiles and cache loads 1.200" in line
    assert "its first run 5.300" in line


def test_compile_s_reads_as_before_beside_the_new_entries(monkeypatch):
    from dcnn_tpu.obs import xla
    old = [e for e in COMPILES if e[2] == "backend_compile"]
    monkeypatch.setattr(xla, "compile_log", lambda: old)
    before = reader("compile_s")(ctx())
    monkeypatch.setattr(xla, "compile_log", lambda: list(COMPILES))
    assert reader("compile_s")(ctx()) == before == pytest.approx(0.7 + 1.2)
