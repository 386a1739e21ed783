import json
import os

import pytest

import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["resnet18_tin"])
def test_train_flops_equal_three_times_forward_complexity(name):
    from dcnn_tpu.models import create_model

    c = cfg(name)
    model = create_model(c["program_model"])
    assert flops.forward_flops_per_image(c) == model.forward_complexity()
    assert flops.train_flops_per_image(c) == 3 * model.forward_complexity()
    assert flops.param_count(c) == model.param_count()


def test_conv_flops_and_bytes_by_hand():
    # 3x3, 64 -> 64 channels on 32x32, batch 2: 2*2*32*32*64*9*64 MACs*2
    assert flops.conv_flops(2, 64, 64, 3, 32, 32) == 150_994_944
    x = 2 * 64 * 32 * 32 * 2          # bf16 input
    y = 2 * 64 * 32 * 32 * 2          # bf16 output
    w = 64 * 64 * 9
    assert flops.conv_bytes("fwd", 2, 64, 64, 3, 32, 32, 32, 32) == x + y + 2 * w
    assert flops.conv_bytes("dgrad", 2, 64, 64, 3, 32, 32, 32, 32) == x + y + 2 * w
    assert flops.conv_bytes("wgrad", 2, 64, 64, 3, 32, 32, 32, 32) == x + y + 4 * w
    # 1x1 stride 2, 64 -> 128, 32x32 -> 16x16, batch 1
    assert flops.conv_flops(1, 64, 128, 1, 16, 16) == 2 * 16 * 16 * 64 * 128
    assert flops.conv_bytes("fwd", 1, 64, 128, 1, 32, 32, 16, 16) == (
        64 * 32 * 32 * 2 + 128 * 16 * 16 * 2 + 128 * 64 * 2)


def test_which_bound_binds():
    peaks = flops.load_peaks("TPU v5 lite")
    convs = flops.conv_layers(cfg("resnet18_tin"))
    assert convs["conv1"] == {"cin": 3, "cout": 32, "k": 3, "h": 64, "w": 64, "oh": 64, "ow": 64}
    # the 3-channel stem moves far more bytes than it multiplies
    assert flops.conv_min_seconds("fwd", 2048, convs["conv1"], peaks)[1] == "bytes"
    t, bound = flops.conv_min_seconds("fwd", 2048, convs["layer4_block2/conv1"], peaks)
    assert bound == "flops"
    assert t == pytest.approx(2 * 2048 * 4 * 4 * 512 * 9 * 512 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v5")
