"""One timeline for the training path (ISSUE 25): the tracer's spans on the
profiler's clock, the compile listener, the feed's spans and counters, the
device-side phase scopes in the step, and the ``gaps`` table.

Sleep-free; the one real ``jax.profiler`` capture (CPU backend, about 0.2 s)
is kept to one test, and the rest of the mirror is checked against a
stand-in for the annotation class.
"""

import glob
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcnn_tpu.obs import Tracer, configure, get_registry
from dcnn_tpu.obs import tracer as tracer_mod
from dcnn_tpu.obs import xla as obs_xla
from dcnn_tpu.obs.trace import device_gaps, format_gaps, main as trace_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def global_tracer_enabled():
    t = configure(enabled=True)
    t.clear()
    yield t
    configure(enabled=False)
    t.clear()


class FakeAnnotation:
    """Stand-in for ``jax.profiler.TraceAnnotation``: records what the tracer
    does to it, and on which thread."""

    log: list = []

    def __init__(self, name, **metadata):
        self.name, self.metadata = name, dict(metadata)
        FakeAnnotation.log.append(("init", name, dict(metadata)))

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name, threading.get_ident()))
        return False

    def set_metadata(self, **metadata):
        FakeAnnotation.log.append(("set", self.name, dict(metadata)))


@pytest.fixture
def fake_annotation(monkeypatch):
    FakeAnnotation.log = []
    monkeypatch.setattr(tracer_mod, "_ANNOTATION",
                        tracer_mod._span_annotation(FakeAnnotation))
    return FakeAnnotation.log


# ------------------------------------------------------------ one clock

def test_span_lies_in_a_running_capture_and_in_the_ring(
        tmp_path, global_tracer_enabled):
    from dcnn_tpu.train.profiling import trace

    tracer = global_tracer_enabled
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with trace(str(tmp_path / "xprof")) as run_dir:
        with tracer.span("t25.outer", track="train", epoch=3):
            with tracer.span("t25.inner", batch=7) as s:
                s.set(bytes=12)
                f(x).block_until_ready()
    path, = glob.glob(os.path.join(run_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dcnn:t25."):
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"dcnn:t25.outer", "dcnn:t25.inner"}
    o, i = found["dcnn:t25.outer"], found["dcnn:t25.inner"]
    assert o[0] <= i[0] and i[0] + i[1] <= o[0] + o[1]      # nested, one clock
    assert o[2]["epoch"] == 3
    assert i[2]["batch"] == 7 and i[2]["bytes"] == 12
    # and still in the ring, chained
    evs = {e["name"]: e for e in tracer.events()}
    assert evs["t25.inner"]["args"]["parent_id"] \
        == evs["t25.outer"]["args"]["span_id"]
    assert evs["t25.inner"]["args"]["bytes"] == 12
    # the capture as the CLI reads it: spans, but no device plane on the CPU
    assert trace_main(["gaps", path]) == 0


@pytest.mark.parametrize("ring", [True, False])
def test_span_opens_and_closes_the_annotation(fake_annotation, ring):
    t = Tracer(enabled=ring)
    with t.span("feed.put", track="feed.producer", bytes=5) as s:
        s.set(more=1)
    me = threading.get_ident()
    assert fake_annotation == [
        ("init", "dcnn:feed.put", {"bytes": 5}),
        ("enter", "dcnn:feed.put", me),
        ("set", "dcnn:feed.put", {"more": 1}),
        ("exit", "dcnn:feed.put", me)]
    assert len(t) == (1 if ring else 0)
    if ring:
        assert t.events()[0]["args"]["more"] == 1


@pytest.mark.parametrize("ring", [True, False])
def test_cross_thread_entries_stay_ring_only(fake_annotation, ring):
    t = Tracer(enabled=ring)
    h = t.begin("serve.queue", track="serve.queue")
    t.end(h, dispatched=True)
    t.instant("serve.shed")
    t.record_span("feed.worker", 0.0, 1.0)
    assert fake_annotation == []
    assert len(t) == (3 if ring else 0)


def test_annotation_closes_when_the_block_raises(fake_annotation):
    t = Tracer(enabled=True)
    with pytest.raises(KeyError):
        with t.span("boom"):
            raise KeyError("x")
    assert [e[0] for e in fake_annotation] == ["init", "enter", "exit"]
    assert t.events()[0]["args"]["error"] == "KeyError"


def test_obs_imports_and_spans_without_jax():
    """``dcnn_tpu.obs`` stays importable where jax is not (a scraper, a
    merge of trace shards): the mirror resolves only if jax is loaded."""
    code = f"""
import os, sys, types
class Block:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in ("jax", "jaxlib"):
            raise ImportError("blocked: " + name)
sys.meta_path.insert(0, Block())
pkg = types.ModuleType("dcnn_tpu")          # the package without its __init__
pkg.__path__ = [os.path.join({REPO!r}, "dcnn_tpu")]
sys.modules["dcnn_tpu"] = pkg
import dcnn_tpu.obs as obs
import dcnn_tpu.obs.xla, dcnn_tpu.obs.trace
with obs.get_tracer().span("x", a=1) as s:   # ring off: the null span
    s.set(b=2)
t = obs.Tracer(enabled=True)
with t.span("y") as s:
    s.set(k=1)
assert len(t) == 1 and "jax" not in sys.modules
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


# ------------------------------------------------------- compile listener

def _compiles():
    return get_registry().counter("compile_total").value


def test_listener_counts_a_plain_jit_compile_once(global_tracer_enabled):
    obs_xla.install_compile_listener()
    obs_xla.install_compile_listener()          # idempotent
    x = jnp.ones((7, 13))
    f = jax.jit(lambda a: jnp.tanh(a) * 3 + a.sum())
    global_tracer_enabled.clear()               # making x compiled too
    before, secs = _compiles(), get_registry().counter(
        "compile_seconds_total").value
    t0 = time.perf_counter()    # the log is capped: by stamp, not by index
    f(x).block_until_ready()
    assert _compiles() == before + 1
    assert get_registry().counter("compile_seconds_total").value > secs
    f(x).block_until_ready()                    # warm: no event
    assert _compiles() == before + 1
    new = [e for e in obs_xla.compile_log()
           if e[0] >= t0 and e[2] == "backend_compile"]
    assert len(new) == 1 and new[0][1] > 0
    assert [e["name"] for e in global_tracer_enabled.events()
            ].count("xla.compile") == 1


def test_listener_counts_a_compile_site_once_not_twice():
    """The serve engine times each bucket's compile for its own twin
    (``compile_serve_seconds_total``); ``compile_total`` is the listener's
    alone, so a site's compile is not counted a second time."""
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.serve import InferenceEngine

    model = (SequentialBuilder("t25_site").input((1, 6, 6))
             .conv2d(3, 3, 1, 1).activation("relu").flatten().dense(5)
             .build())
    params, state = model.init(jax.random.PRNGKey(0))
    reg = get_registry()
    before = _compiles()
    twin = reg.counter("compile_serve_seconds_total").value
    eng = InferenceEngine.from_model(model, params, state, max_batch=4,
                                     fold=False, warmup=False)
    assert _compiles() == before + len(eng.bucket_sizes)
    assert reg.counter("compile_serve_seconds_total").value > twin


def test_compile_log_is_capped():
    obs_xla.install_compile_listener()
    for _ in range(obs_xla._COMPILE_LOG_CAP + 10):
        obs_xla._on_compile_event(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.0)
    assert len(obs_xla.compile_log()) == obs_xla._COMPILE_LOG_CAP
    obs_xla._on_compile_event("/jax/some/other/event", 1.0)  # ignored
    assert obs_xla.compile_log()[-1][2] == "cache_load"


# ------------------------------------------------------------------ feed

def _feed_counters():
    snap = get_registry().snapshot()
    return {k: snap.get(k, 0) for k in (
        "feed_batches_total", "feed_prep_seconds_total",
        "feed_put_bytes_total", "feed_put_seconds_total",
        "feed_blocked_seconds_total", "feed_wait_seconds_total")}


@pytest.mark.parametrize("stage_batches", [1, 2])
def test_prefetch_epoch_spans_add_up_to_the_counters(
        global_tracer_enabled, stage_batches):
    from dcnn_tpu.data import ArrayDataLoader, PrefetchLoader

    rng = np.random.default_rng(0)
    x = rng.random((40, 3, 4, 4), dtype=np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 40)]
    inner = ArrayDataLoader(x, y, batch_size=8, shuffle=False)
    loader = PrefetchLoader(inner, depth=2, stage_batches=stage_batches,
                            transform=lambda a, b: (a * 2, b))
    before = _feed_counters()
    served = [xb.shape for xb, _ in loader]
    got = {k: v - before[k] for k, v in _feed_counters().items()}
    assert got["feed_batches_total"] == len(inner) == 5
    assert len(served) == -(-5 // stage_batches)
    assert got["feed_put_bytes_total"] == x.nbytes + y.nbytes

    by_name = {}
    for e in global_tracer_enabled.events():
        by_name.setdefault(e["name"], []).append(e)
    total = lambda *names: sum(e["dur_s"] for n in names  # noqa: E731
                               for e in by_name.get(n, []))
    assert len(by_name["feed.next"]) == 5 + 1        # the last finds the end
    assert len(by_name["feed.transform"]) == 5
    assert len(by_name["feed.put"]) == len(by_name["feed.blocked"]) \
        == len(served)
    assert ("feed.stack" in by_name) == (stage_batches > 1)
    assert len(by_name["feed.wait"]) == len(served) + 1   # and the sentinel
    assert {e["track"] for e in by_name["feed.put"]} == {"feed.producer"}
    assert {e["track"] for e in by_name["feed.wait"]} == {"train"}
    assert sum(e["args"]["bytes"] for e in by_name["feed.put"]) \
        == got["feed_put_bytes_total"]
    # a counter is the span's interval plus the two clock reads round it
    slack = 2e-3
    for counter, spans in (
            ("feed_prep_seconds_total",
             ("feed.next", "feed.transform", "feed.stack")),
            ("feed_put_seconds_total", ("feed.put",)),
            ("feed_blocked_seconds_total", ("feed.blocked",)),
            ("feed_wait_seconds_total", ("feed.wait",))):
        assert total(*spans) <= got[counter] <= total(*spans) + slack, counter


# --------------------------------------------------- device-side scopes

def _locations(lowered):
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _tiny_model():
    from dcnn_tpu.nn import SequentialBuilder
    return (SequentialBuilder("t25_scopes").input((3, 8, 8))
            .conv2d(4, 3, 1, 1).batchnorm().activation("relu")
            .flatten().dense(5).build())


def test_resident_epoch_holds_the_phase_scopes_and_every_layer_path():
    from dcnn_tpu.data import DeviceAugmentBuilder
    from dcnn_tpu.data.device_dataset import make_resident_epoch
    from dcnn_tpu.ops.losses import get_loss
    from dcnn_tpu.optim import AdamW
    from dcnn_tpu.train.trainer import create_train_state

    model, opt = _tiny_model(), AdamW(1e-3)
    aug = (DeviceAugmentBuilder("NCHW").random_crop(2)
           .horizontal_flip(0.5).build())
    epoch = make_resident_epoch(model, get_loss("crossentropy"), opt,
                                num_classes=5, batch_size=4, augment=aug)
    ts = jax.eval_shape(lambda k: create_train_state(model, opt, k),
                        jax.random.PRNGKey(0))
    locs = _locations(epoch.lower(
        ts, jax.ShapeDtypeStruct((16, 3, 8, 8), jnp.uint8),
        jax.ShapeDtypeStruct((16,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), 1e-3))
    paths = [l for l in locs if not l.startswith("/")]      # not file names
    for scope in ("data/", "shuffle/", "optim/", "jvp(loss)/"):
        assert any(scope in p for p in paths), scope
    assert any(p.endswith("data/gather") or "/data/gather" in p or
               p.startswith("data/gather") for p in paths), \
        "the batch gather lies under the data scope"
    # every layer keeps the path the benchmark matches convolutions by
    for layer in model.layers:
        assert any(f"jvp({layer.name})" in p for p in paths), layer.name
        assert any(f"transpose(jvp({layer.name}))" in p for p in paths) \
            or layer.name in ("flatten",), layer.name


def test_guarded_step_holds_the_guard_scope():
    from dcnn_tpu.ops.losses import get_loss
    from dcnn_tpu.optim import AdamW
    from dcnn_tpu.train.trainer import create_train_state, make_train_step

    model, opt = _tiny_model(), AdamW(1e-3)
    step = make_train_step(model, get_loss("crossentropy"), opt, guard=True)
    ts = jax.eval_shape(lambda k: create_train_state(model, opt, k),
                        jax.random.PRNGKey(0))
    locs = _locations(step.lower(
        ts, jax.ShapeDtypeStruct((4, 3, 8, 8), jnp.float32),
        jax.ShapeDtypeStruct((4, 5), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), 1e-3))
    for scope in ("guard/", "optim/", "jvp(loss)/"):
        assert any(scope in l for l in locs), scope


def test_resident_dataset_counts_its_staging(global_tracer_enabled):
    from dcnn_tpu.data import DeviceDataset

    reg = get_registry()
    b0 = reg.counter("data_stage_bytes_total").value
    s0 = reg.counter("data_stage_seconds_total").value
    x = np.zeros((12, 3, 4, 4), np.uint8)
    DeviceDataset(x, np.arange(12) % 3, 3, batch_size=4)
    assert reg.counter("data_stage_bytes_total").value == b0 + x.nbytes
    spent = reg.counter("data_stage_seconds_total").value - s0
    ev, = [e for e in global_tracer_enabled.events()
           if e["name"] == "data.stage"]
    assert ev["track"] == "data" and ev["args"]["bytes"] == x.nbytes
    assert ev["args"]["engine"] == "put"
    assert 0 < ev["dur_s"] <= spent


# ------------------------------------------------------------------ gaps

def test_gaps_lays_idle_time_under_the_spans():
    s = 1e9
    ops = {"/device:TPU:0": [(0 * s, 2 * s), (1 * s, 3 * s), (6 * s, 8 * s)]}
    spans = [("dcnn:train.epoch", "MainThread", 0 * s, 10 * s),
             ("dcnn:feed.wait", "MainThread", 3 * s, 5 * s),
             ("dcnn:feed.next", "prefetch-producer", 2 * s, 5.5 * s),
             ("dcnn:train.step", "MainThread", 5.5 * s, 8 * s)]
    g = device_gaps(ops, spans)
    assert g["window_s"] == 10 and g["busy_s"] == 5 and g["idle_s"] == 5
    rows = {r["span"]: r for r in g["rows"]}
    assert rows["dcnn:train.epoch"]["idle_s"] == 5       # all of it
    assert rows["dcnn:feed.wait"]["idle_s"] == 2
    assert rows["dcnn:feed.next"]["idle_s"] == 2.5
    assert rows["dcnn:feed.next"]["threads"] == ["prefetch-producer"]
    assert rows["dcnn:train.step"]["idle_s"] == 0.5
    assert g["none_idle_s"] == 0
    assert "dcnn:feed.next" in format_gaps(g)
    # no span at all: the window is the device's, and nothing is named
    g = device_gaps(ops, [])
    assert g["window_s"] == 8 and g["idle_s"] == 3 == g["none_idle_s"]
    assert device_gaps({}, spans) == {}
