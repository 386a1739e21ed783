"""``conv_phase_roofline`` on made-up reduced traces whose instruction texts
and scopes are cut from a v5e trace of the ResNet-18 epoch program: a layer
computed as four stride-2 products is credited its least time once, and a
program without the ``<layer>.phase`` scope gives nothing to read."""

import json
import os

import pytest

import flops
from test_layer_readers import PEAKS, ctx, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FWD = ("%fusion.1745 = bf16[2048,32,32,32]{0,1,3,2:T(8,128)(2,1)} fusion("
       "bf16[2048,3,64,64]{0,1,3,2:T(4,128)(2,1)S(1)} %copy.380, "
       "bf16[32,3,3,3]{0,1,3,2:T(4,128)(2,1)S(1)} %copy-done.68), kind=kOutput")
FWD_SUMS = ("%fusion.1747 = (f32[32]{0:T(128)S(1)}, f32[32]{0:T(128)S(1)}, "
            "bf16[2048,32,32,32]{0,1,3,2:T(8,128)(2,1)}) fusion(f32[32]{0:T(128)S(1)} %copy-done.141, "
            "bf16[2048,32,32,32]{0,1,3,2:T(8,128)(2,1)} %fusion.1745, "
            "bf16[2048,3,64,64]{0,1,3,2:T(4,128)(2,1)S(1)} %copy.380, "
            "bf16[32,3,3,3]{0,1,3,2:T(4,128)(2,1)S(1)} %copy-done.68), kind=kOutput")
WGRAD = ("%fusion.1834 = bf16[32,3,3,3]{0,1,3,2:T(4,128)(2,1)S(1)} fusion("
         "bf16[2048,3,64,64]{0,1,3,2:T(4,128)(2,1)S(1)} %copy-done.1, "
         "bf16[2048,32,32,32]{0,1,3,2:T(8,128)(2,1)} %fusion.1744), kind=kOutput")
WGRAD_ADAM = ("%fusion.1837 = (f32[32,3,3,3]{0,1,3,2:T(4,128)}, f32[32,3,3,3]{0,1,3,2:T(4,128)S(1)}) "
              "fusion(f32[32,3,3,3]{0,1,3,2:T(4,128)} %get-tuple-element.13782, "
              "bf16[32,3,3,3]{0,1,3,2:T(4,128)(2,1)S(1)} %fusion.1834, "
              "bf16[2048,3,64,64]{0,1,3,2:T(4,128)(2,1)S(1)} %copy-done.1), kind=kOutput")
OTHER = ("%convolution_add_fusion.3 = bf16[2048,64,32,32]{0,1,3,2:T(8,128)(2,1)} fusion("
         "bf16[2048,64,32,32]{0,1,3,2:T(8,128)(2,1)} %a, bf16[64,64,3,3]{0,1,3,2:T(8,128)(2,1)} %w)")


def scope(name, bwd=False):
    inner = f"transpose(jvp({name}))" if bwd else f"jvp({name})"
    return f"jit(epoch)/while/body/closed_call/{inner}/conv_general_dilated:"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "resnet18_tin.json")) as f:
        return json.load(f)


def trace_ctx(cfg, events, peaks=PEAKS):
    c = ctx({"conv_events": events}, peaks=peaks)
    c["cfg"] = cfg
    return c


def test_a_layer_in_four_events_is_credited_once(cfg):
    steps = 3
    step = ([(FWD, scope("conv1.phase"), 0.000189)] * 3
            + [(FWD_SUMS, scope("conv1.phase"), 0.000875)]
            + [(WGRAD, scope("conv1.phase", True), 0.000370)] * 3
            + [(WGRAD_ADAM, scope("conv1.phase", True), 0.000371)]
            + [(OTHER, scope("layer1_block1", True), 0.001120)])   # not a phase: left out
    g = flops.conv_layers(cfg)["conv1"]
    least = sum(flops.conv_min_seconds(kind, cfg["batch_size"], g, PEAKS)[0]
                for kind in ("fwd", "wgrad"))
    spent = 3 * 0.000189 + 0.000875 + 3 * 0.000370 + 0.000371
    got = reader("conv_phase_roofline")(trace_ctx(cfg, step * steps))
    assert got == pytest.approx(100.0 * least / spent)
    assert 0 < got < 100


def test_nothing_to_read(cfg):
    read = reader("conv_phase_roofline")
    parent = [(FWD_SUMS, scope("conv1"), 0.000756), (WGRAD_ADAM, scope("conv1", True), 0.001423),
              (OTHER, scope("layer1_block1", True), 0.001120)]
    assert read(trace_ctx(cfg, parent)) is None                     # no <layer>.phase scope
    assert read(trace_ctx(cfg, [(FWD, scope("nosuch.phase"), 0.001)])) is None
    assert read(trace_ctx(cfg, [])) is None
    assert read(ctx()) is None                                      # an untraced run
    phases = [(FWD, scope("conv1.phase"), 0.000189)]
    assert read(trace_ctx(cfg, phases, peaks=None)) is None         # a rehearsal
    assert read(trace_ctx(cfg, phases)) is not None
