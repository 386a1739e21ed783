"""The readers this benchmark gained with the program's own counters and
scopes (``stage_h2d_gbps``, ``compile_s``, ``data_device_share``,
``optim_device_share``, and the unlisted ``feed_prep_ms``, ``feed_h2d_gbps``):
each on a small made-up reduced trace and registry, and ``None`` where there
is nothing to read (a program without the counters, a rehearsal)."""

import importlib.util
import os
import types

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(reduced=None, peaks=PEAKS, t_open=100.0):
    return {"reduced": reduced or {}, "peaks": peaks, "log": lambda *a: None,
            "window": types.SimpleNamespace(t_open=t_open), "cfg": {}, "traffic": {},
            "chips": 1, "memory_peak_bytes": 0, "counters": {}}


@pytest.fixture
def registry():
    """The program's process-global registry, zeroed before and after."""
    from dcnn_tpu.obs import get_registry
    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


@pytest.mark.parametrize("name,counters,want", [
    ("stage_h2d_gbps", {"data_stage_bytes_total": 1.35e9, "data_stage_seconds_total": 10.0}, 0.135),
    ("feed_prep_ms", {"feed_prep_seconds_total": 4.0, "feed_batches_total": 8}, 500.0),
    ("feed_h2d_gbps", {"feed_put_bytes_total": 2.5e8, "feed_put_seconds_total": 0.5}, 0.5),
])
def test_counter_readers(registry, name, counters, want):
    read = reader(name)
    assert read(ctx()) is None                      # the parent: no such counter
    for k, v in counters.items():
        registry.counter(k).inc(v)
    assert read(ctx()) == pytest.approx(want)
    assert read(ctx(peaks=None)) is None            # a rehearsal


def test_compile_s_sums_backend_compiles_before_the_window(monkeypatch):
    from dcnn_tpu.obs import xla
    read = reader("compile_s")
    log = [(10.0, 2.0, "backend_compile"),
           (20.0, 0.5, "cache_hit"), (20.0, 0.4, "cache_load"),
           (20.1, 0.5, "backend_compile"),           # holds the 0.4 s load
           (150.0, 30.0, "backend_compile")]         # the reference, after the window
    monkeypatch.setattr(xla, "compile_log", lambda: list(log), raising=False)
    assert read(ctx(t_open=100.0)) == pytest.approx(2.5)
    assert read(ctx(t_open=5.0)) is None             # nothing before the window
    assert read(ctx(peaks=None)) is None
    monkeypatch.delattr(xla, "compile_log")          # the parent has no log
    assert read(ctx()) is None


def reduced_with_scopes():
    dev = "/device:TPU:0"
    scope = {"g": "jit(epoch)/while/body/data/gather",
             "d": "jit(epoch)/while/body/data/jit(_one_hot)/eq",
             "s": "jit(epoch)/shuffle/jit(_shuffle)/while/body/sort",
             "o": "jit(epoch)/while/body/optim/mul",
             "l": "jit(epoch)/while/body/jvp(loss)/reduce_sum",
             "c": "jit(epoch)/while/body/transpose(jvp(layer1_block2))/conv_general_dilated",
             "f": "jit(epoch)/while/body/jvp(layer1_block2)/conv_general_dilated"}
    rows, meta, t = [], {}, 0.0
    for key, seconds in (("g", 4.0), ("d", 1.0), ("s", 1.0), ("o", 2.0), ("l", 1.0),
                         ("c", 60.0), ("f", 31.0)):
        text = f"%fusion.{key} = f32[] fusion()"
        rows.append((dev, tr.OPS_LINE, text, t * 1e9, seconds * 1e9))
        meta[text] = {"category": "loop fusion", "scope": scope[key]}
        t += seconds
    return tr.reduce(rows, meta)


def test_scope_names_and_device_shares():
    r = reduced_with_scopes()
    ops = r["devices"]["/device:TPU:0"]["ops"]
    # the scopes name what had no name; a layer's operations keep theirs
    assert set(ops) == {"data/gather", "data/eq", "shuffle/sort", "optim/mul",
                        "loss/reduce_sum", "layer1_block2/conv_bwd", "layer1_block2/conv"}
    assert reader("data_device_share")(ctx(r)) == pytest.approx(6.0)
    assert reader("optim_device_share")(ctx(r)) == pytest.approx(2.0)
    assert reader("data_device_share")(ctx(r, peaks=None)) is None


def test_shares_find_nothing_without_the_scopes():
    dev = "/device:TPU:0"
    text = "%gather.1 = u8[] gather()"
    r = tr.reduce([(dev, tr.OPS_LINE, text, 0.0, 1e9)], {text: {"category": "gather", "scope": ""}})
    assert r["devices"][dev]["ops"] == {"gather": 1.0}
    assert reader("data_device_share")(ctx(r)) is None
    assert reader("optim_device_share")(ctx(r)) is None
    assert reader("data_device_share")(ctx({})) is None
