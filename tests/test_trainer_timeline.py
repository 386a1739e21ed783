"""The trainer loop measured from inside (ISSUE 37): the resident epoch's
child spans, the always-on dispatch and phase logs, the listener's ``trace``
and ``lower`` entries, ``gaps``' self time, and the four readers of the
benchmark that read them (their cases live with the readers, in
``chipbench/tests/test_host_log_readers.py``, and are collected here too so
that tier-1 runs them). Sleep-free; every model is a few hundred
parameters."""

import importlib.util
import os
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcnn_tpu.obs import (Dispatch, configure, dispatch_log, get_registry,
                          log_dispatch, phase, phase_log)
from dcnn_tpu.obs import xla as obs_xla
from dcnn_tpu.obs.trace import device_gaps, format_gaps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chipbench_host_log_readers",
    os.path.join(REPO, "chipbench", "tests", "test_host_log_readers.py"))
_readers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_readers)
globals().update({k: v for k, v in vars(_readers).items()
                  if k.startswith("test_") or k == "case"})


@pytest.fixture
def ring():
    t = configure(enabled=True)
    t.clear()
    yield t
    configure(enabled=False)
    t.clear()


TRAIN_COUNTERS = ("train_dispatches_total", "train_dispatch_seconds_total",
                  "train_fence_seconds_total", "train_turn_seconds_total")


def _counters():
    snap = get_registry().snapshot()
    return {k: snap.get(k, 0) for k in TRAIN_COUNTERS}


def _two_epochs(publishes: bool):
    """A trainer of its own over a 16-image split: two resident epochs.
    Returns the log's entries and the counters' growth."""
    from dcnn_tpu.data import DeviceDataset
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import AdamW
    from dcnn_tpu.train.trainer import Trainer, create_train_state

    model = (SequentialBuilder("t37").input((3, 8, 8)).conv2d(4, 3, 1, 1)
             .activation("relu").flatten().dense(5).build())
    published = []
    if publishes:
        def publish_state(state):
            published.append(time.perf_counter())
            return state
        model.publish_state = publish_state
    opt = AdamW(1e-3)
    trainer = Trainer(model, opt, "crossentropy")
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    ds = DeviceDataset(np.zeros((16, 3, 8, 8), np.uint8), np.arange(16) % 5,
                       5, batch_size=4)
    t0, before = time.perf_counter(), _counters()
    for epoch in (1, 2):
        ts, loss, _ = trainer.train_epoch(ts, ds, jax.random.PRNGKey(1), epoch)
        assert np.isfinite(loss)
    grown = {k: v - before[k] for k, v in _counters().items()}
    return [e for e in dispatch_log() if e.t_call >= t0], grown, published


# ----------------------------------------------- (a) the log and its counters

@pytest.mark.parametrize("publishes", [False, True])
def test_two_resident_epochs_give_two_log_entries(publishes):
    (a, b), grown, published = _two_epochs(publishes)
    for e in (a, b):
        assert e.t_call <= e.t_returned <= e.t_fenced <= e.t_published
        assert e.steps == 4
    assert (a.first, b.first) == (True, False)
    assert a.t_published <= b.t_call
    if publishes:
        assert a.t_fenced < published[0] <= a.t_published
    else:
        assert (a.t_published, b.t_published) == (a.t_fenced, b.t_fenced)
    # the counters keep step with the log, the turn with the stamps
    assert grown["train_dispatches_total"] == 2
    assert grown["train_dispatch_seconds_total"] == pytest.approx(
        (a.t_returned - a.t_call) + (b.t_returned - b.t_call))
    assert grown["train_fence_seconds_total"] == pytest.approx(
        (a.t_fenced - a.t_returned) + (b.t_fenced - b.t_returned))
    assert grown["train_turn_seconds_total"] == pytest.approx(
        b.t_returned - a.t_fenced)
    assert b.t_returned - a.t_fenced > 0


@pytest.mark.parametrize("publishes", [False, True])
def test_publish_span_only_for_a_model_that_publishes(ring, publishes):
    _two_epochs(publishes)
    names = [e["name"] for e in ring.events()]
    assert names.count("train.publish") == (2 if publishes else 0)
    assert names.count("train.dispatch") == names.count("train.fence") == 2


def test_dispatch_log_is_bounded():
    from dcnn_tpu.obs import hostlog
    e = Dispatch(1.0, 2.0, 3.0, 3.0, 8, False)
    for _ in range(hostlog._LOG_CAP + 5):
        log_dispatch(e)
    assert len(dispatch_log()) == hostlog._LOG_CAP


# ------------------------------------------------------ (b) the ring's view

def test_children_carry_the_epochs_span_and_the_turn_lies_between(ring):
    _two_epochs(publishes=True)
    by_name = {}
    for e in ring.events():
        by_name.setdefault(e["name"], []).append(e)
    first, second = by_name["train.resident_epoch"]
    for parent, i in ((first, 0), (second, 1)):
        for child in ("train.dispatch", "train.fence"):
            ev = by_name[child][i]
            assert ev["args"]["parent_id"] == parent["args"]["span_id"]
            assert ev["track"] == "train"
            assert parent["ts_s"] <= ev["ts_s"]
            assert ev["ts_s"] + ev["dur_s"] <= parent["ts_s"] + parent["dur_s"] + 1e-9
    d1, d2 = by_name["train.dispatch"]
    assert d1["args"]["first"] is True and d2["args"]["first"] is False
    assert d1["args"]["steps"] == 4 and d2["args"]["epoch"] == 2
    # publish follows the fence, outside the epoch's span: no parent
    p1 = by_name["train.publish"][0]
    assert "parent_id" not in p1["args"]
    f1 = by_name["train.fence"][0]
    assert f1["ts_s"] + f1["dur_s"] <= p1["ts_s"]
    # one turn, from the first fence's end to the second dispatch's end
    turn, = by_name["train.turn"]
    end = lambda e: e["ts_s"] + e["dur_s"]                      # noqa: E731
    assert end(f1) <= turn["ts_s"] <= p1["ts_s"]
    assert end(turn) == pytest.approx(end(d2), abs=1e-3)
    assert turn["ts_s"] <= p1["ts_s"] and end(p1) <= d2["ts_s"] <= end(turn)


# ------------------------------------------------------- (c) the listener

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _listeners_clock(monkeypatch, *stamps):
    """The listener stamps with ``time.perf_counter``: give its module a
    clock of its own, and leave the process's alone."""
    clock = iter(stamps)
    monkeypatch.setattr(obs_xla, "time", types.SimpleNamespace(
        perf_counter=lambda: next(clock)))


def _union(entries):
    from dcnn_tpu.data.transfer import union_seconds
    return union_seconds([(stamp - s, stamp) for stamp, s, _ in entries])


def test_listener_files_jaxs_trace_and_lower_events():
    obs_xla.install_compile_listener()
    reg = get_registry()
    t0 = time.perf_counter()
    before = {k: reg.counter(f"compile_{k}_seconds_total").value
              for k in ("trace", "lower")}
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum() + jnp.where(a > 0, a, 0).sum())
    f(jnp.ones((9, 9))).block_until_ready()
    new = [e for e in obs_xla.compile_log() if e[0] >= t0]
    kinds = [e[2] for e in new]
    assert "trace" in kinds and "lower" in kinds and "backend_compile" in kinds
    # the order JAX works in: the jaxpr, the module, the executable
    assert kinds.index("trace") < kinds.index("lower") < kinds.index("backend_compile")
    for kind in ("trace", "lower"):
        grown = reg.counter(f"compile_{kind}_seconds_total").value - before[kind]
        assert 0 < grown == pytest.approx(
            sum(e[1] for e in new if e[2] == kind))


def test_a_trace_inside_anothers_counts_once(monkeypatch):
    """A function traced inside another's trace fires first: the outer entry
    takes its place in the log, the counter counts the seconds once, and a
    reader's union reads the same whether or not the inner one was kept."""
    _listeners_clock(monkeypatch, 10.0, 10.5, 12.0, 20.0, 21.0)
    reg = get_registry()
    before = reg.counter("compile_trace_seconds_total").value
    n = len(obs_xla.compile_log())
    obs_xla._on_compile_event(TRACE, 0.25)         # [9.75, 10.0]: inner
    obs_xla._on_compile_event(TRACE, 0.25)         # [10.25, 10.5]: inner
    obs_xla._on_compile_event(TRACE, 3.0)          # [9.0, 12.0]: holds both
    obs_xla._on_compile_event(LOWER, 0.5)          # [19.5, 20.0]
    obs_xla._on_compile_event(TRACE, 0.5)          # [20.5, 21.0]: on its own
    new = obs_xla.compile_log()[n:]
    assert new == [(12.0, 3.0, "trace"), (20.0, 0.5, "lower"),
                   (21.0, 0.5, "trace")]
    assert reg.counter("compile_trace_seconds_total").value - before \
        == pytest.approx(3.5)
    kept = new + [(10.0, 0.25, "trace"), (10.5, 0.25, "trace")]
    assert _union(new) == _union(kept) == pytest.approx(4.0)


def test_compile_s_reads_the_backend_kind_alone(monkeypatch):
    """``compile_s`` sums ``backend_compile`` entries: the listener's new
    kinds beside them change nothing (the reader's own case, on the real
    listener's log)."""
    _listeners_clock(monkeypatch, 50.0, 51.0, 52.0)
    n = len(obs_xla.compile_log())
    obs_xla._on_compile_event(TRACE, 0.75)
    obs_xla._on_compile_event(LOWER, 0.5)
    obs_xla._on_compile_event(BACKEND, 0.25)
    new = obs_xla.compile_log()[n:]
    assert [e[2] for e in new] == ["trace", "lower", "backend_compile"]
    read = _readers.reader("compile_s")
    monkeypatch.setattr(obs_xla, "compile_log", lambda: new)
    with_them = read(_readers.ctx())
    monkeypatch.setattr(obs_xla, "compile_log",
                        lambda: [e for e in new if e[2] == "backend_compile"])
    assert with_them == read(_readers.ctx()) == 0.25


# ---------------------------------------------------- (d) gaps' self time

def test_gaps_self_idle_adds_up_over_a_threads_spans():
    s = 1e9
    # busy 0-4 and 5-9; idle 4-5 (the turn) and 9-12
    ops = {"/device:TPU:0": [(0 * s, 4 * s), (5 * s, 9 * s)]}
    main = "MainThread#0"
    spans = [("dcnn:train.resident_epoch", main, 0 * s, 4.25 * s),
             ("dcnn:train.dispatch", main, 0 * s, 0.5 * s),
             ("dcnn:train.fence", main, 0.5 * s, 4.25 * s),
             ("dcnn:train.publish", main, 4.25 * s, 4.5 * s),
             ("dcnn:train.resident_epoch", main, 4.75 * s, 9.5 * s),
             ("dcnn:train.dispatch", main, 4.75 * s, 5.25 * s),
             ("dcnn:train.fence", main, 5.25 * s, 9.25 * s),
             ("dcnn:train.publish", main, 10 * s, 12 * s)]
    g = device_gaps(ops, spans)
    assert g["window_s"] == 12 and g["idle_s"] == 4
    rows = {r["span"]: r for r in g["rows"]}
    # a parent's idle_s holds its children's ...
    assert rows["dcnn:train.resident_epoch"]["idle_s"] == 1.0
    assert rows["dcnn:train.fence"]["idle_s"] == 0.5
    # ... its self time is what they leave: 9.25 to 9.5
    assert rows["dcnn:train.resident_epoch"]["self_idle_s"] == 0.25
    assert rows["dcnn:train.dispatch"]["self_idle_s"] == 0.25    # 4.75 to 5
    assert rows["dcnn:train.fence"]["self_idle_s"] == 0.5        # 4-4.25, 9-9.25
    assert rows["dcnn:train.publish"]["self_idle_s"] == 2.25
    assert g["none_idle_s"] == 0.75                              # 4.5-4.75, 9.5-10
    assert sum(r["self_idle_s"] for r in g["rows"]) + g["none_idle_s"] \
        == g["idle_s"]
    table = format_gaps(g)
    assert "self_idle_s" in table and "dcnn:train.fence" in table
    # a second thread's span lies over the same seconds once more
    g2 = device_gaps(ops, spans + [("dcnn:feed.next", "producer#1", 9 * s, 11 * s)])
    rows2 = {r["span"]: r for r in g2["rows"]}
    assert rows2["dcnn:feed.next"]["self_idle_s"] == 2.0
    assert rows2["dcnn:train.publish"]["self_idle_s"] == 2.25
    assert g2["none_idle_s"] == 0.25                             # 4.5-4.75


# ------------------------------------------------------- set-up's phases

def test_phase_logs_and_spans_as_block_and_as_decorator(ring):
    t0 = time.perf_counter()

    @phase("setup.t37_decorated")
    def build(x):
        return x + 1

    with phase("setup.t37_block"):
        assert build(1) == 2
    with pytest.raises(KeyError):
        with phase("setup.t37_raises"):
            raise KeyError("x")
    mine = [p for p in phase_log() if p[1] >= t0]
    assert [p[0] for p in mine] == ["setup.t37_decorated", "setup.t37_block",
                                    "setup.t37_raises"]
    (_, a0, a1), (_, b0, b1), _ = mine
    assert b0 <= a0 <= a1 <= b1                     # the inner one closed first
    evs = {e["name"]: e for e in ring.events()}
    assert evs["setup.t37_decorated"]["args"]["parent_id"] \
        == evs["setup.t37_block"]["args"]["span_id"]
    assert evs["setup.t37_raises"]["args"]["error"] == "KeyError"
    assert evs["setup.t37_block"]["track"] == "setup"


def test_the_programs_own_phases_are_named():
    from dcnn_tpu.models import create_model
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.train.trainer import Trainer, create_train_state

    t0 = time.perf_counter()
    model = create_model("mnist_cnn")
    opt = SGD(0.1)
    Trainer(model, opt, "crossentropy")
    jax.eval_shape(lambda k: create_train_state(model, opt, k),
                   jax.random.PRNGKey(0))
    names = [p[0] for p in phase_log() if p[1] >= t0]
    assert names == ["setup.model", "setup.trainer", "setup.state"]
