import gzip
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        doc = json.load(f)
    return [tuple(r) for r in doc["rows"]], doc["meta"]


def test_union_gaps_overlap_by_hand():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.length(merged) == 6
    assert tr.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(merged, 1, 6) == [(3, 5)]
    assert tr.overlap([(3, 5), (8, 10)], [(4, 9)]) == 2
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_reduce_synthetic_two_devices():
    dev = "/device:TPU:%d"
    rows = [(dev % 0, tr.OPS_LINE, "%fusion.1 = f32[] fusion()", 0.0, 4e9),
            (dev % 0, tr.OPS_LINE, "%fusion.2 = f32[] fusion()", 6e9, 2e9),
            (dev % 1, tr.OPS_LINE, "%fusion.1 = f32[] fusion()", 2e9, 2e9),
            (dev % 0, tr.OPS_LINE, "%while.3 = () while()", 0.0, 10e9),
            ("/host:CPU", "python3", "chipbench:next_batch", 4e9, 2e9),
            ("/host:CPU", "python3", "chipbench:step", 0.0, 4e9),
            ("/host:CPU", "python3", "chipbench:step", 6e9, 4e9)]
    meta = {"%fusion.1 = f32[] fusion()": {"category": "convolution fusion",
                                           "scope": "jit(step)/transpose(jvp(layer1_block2))/conv_general_dilated:"},
            "%fusion.2 = f32[] fusion()": {"category": "loop fusion",
                                           "scope": "jit(step)/jvp(bn1)/reduce_sum:"}}
    r = tr.reduce(rows, meta)
    assert r["window_s"] == 10.0
    assert r["devices"][dev % 0]["busy_s"] == 6.0      # the while event is a container
    assert r["devices"][dev % 1]["busy_s"] == 2.0
    assert r["busy_s_mean"] == 4.0
    assert r["devices"][dev % 0]["ops"] == {"layer1_block2/conv_bwd": 4.0, "bn1/reduce_sum": 2.0}
    assert r["spans"] == {"next_batch": 2.0, "step": 8.0}
    # device 0 idles 4-6 (next_batch) and 8-10 (step); device 1 idles 0-2, 4-10
    assert r["idle_by_span"]["next_batch"] == pytest.approx((2.0 + 2.0) / 2)
    assert r["idle_by_span"]["step"] == pytest.approx((2.0 + 6.0) / 2)
    assert len(r["conv_events"]) == 2
    assert tr.top_ops(r, 1) == [["layer1_block2/conv_bwd", 6.0]]


def test_stable_names():
    assert tr.stable_name("jit(step)/transpose(jvp(layer1_block2))/conv_general_dilated:") == "layer1_block2/conv_bwd"
    assert tr.stable_name("jit(step)/jvp(layer1_block2)/conv_general_dilated:") == "layer1_block2/conv"
    assert tr.stable_name("jit(step)/transpose(jvp(bn1))/reduce_sum:") == "bn1/reduce_sum_bwd"
    assert tr.stable_name("", "%copy-done.80 = f32[1] copy-done()") == "copy-done"


def test_recorded_trace(recorded):
    """Three ResNet-18 steps on the v5e, each inside a ``step`` span and
    after a 10 ms ``next_batch`` span in which the device waits."""
    rows, meta = recorded
    r = tr.reduce(rows, meta)
    (plane, d), = r["devices"].items()
    assert plane == "/device:TPU:0"
    assert r["window_s"] == pytest.approx(0.0924, abs=1e-3)
    assert d["busy_s"] == pytest.approx(0.0525, abs=1e-3)
    idle = 1 - r["busy_s_mean"] / r["window_s"]
    assert idle == pytest.approx(0.432, abs=0.01)
    # per-name kernel time: the convolution backward of layer1 leads
    top = tr.top_ops(r, 3)
    assert top[0][0] == "layer1_block2/conv_bwd"
    assert top[0][1] == pytest.approx(0.00524, rel=0.02)
    assert sum(d["ops"].values()) >= d["busy_s"]       # ops may overlap, never undercount
    # gap attribution: the device's idle time lies under next_batch
    assert r["spans"]["next_batch"] == pytest.approx(0.0315, abs=1e-3)
    assert r["idle_by_span"]["next_batch"] == pytest.approx(0.0315, abs=1e-3)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - d["busy_s"], rel=1e-6)
    assert len(r["conv_events"]) == 195                # 65 convolution events a step
