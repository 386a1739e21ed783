"""Operations and bytes from layer shapes — the benchmark's own count.

A configuration file lists its layers (``chipbench/configs/<name>.json``,
key ``layers``); everything here is arithmetic on that list. The model count
copies the arithmetic of the program's ``forward_complexity()`` (convolution
and dense 2·MACs, batch norm 8 per element, activation 1, pooling one per
window cell, residual add + activation 2) so that ``train_mfu`` can be read
against every earlier record of this repo; a test holds the two equal. A
training step is counted as 3 x forward (backward = two products per forward
product); recomputed operations are not counted.

The per-convolution functions count the least the algorithm needs: each
operand read once and the result written once, in the dtype the
configuration computes in.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterator, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks, by exact ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def _out_hw(h: int, w: int, k: int, stride: int, pad: int) -> Tuple[int, int]:
    return (h + 2 * pad - k) // stride + 1, (w + 2 * pad - k) // stride + 1


def expand_block(layer: dict) -> Tuple[List[dict], List[dict]]:
    """Main and shortcut layer lists of a residual block entry."""
    cin, cout, s = layer["in"], layer["out"], layer["stride"]
    eps = layer.get("eps", 1e-5)
    bn = {"op": "bn", "eps": eps, "momentum": 0.1}
    if layer["op"] == "basic":
        main = [
            {"op": "conv", "out": cout, "k": 3, "stride": s, "pad": 1, "bias": True, "name": "conv0"},
            dict(bn, name="bn0"), {"op": "relu"},
            {"op": "conv", "out": cout, "k": 3, "stride": 1, "pad": 1, "bias": True, "name": "conv1"},
            dict(bn, name="bn1"),
        ]
    elif layer["op"] == "bottleneck":
        mid = layer["mid"]
        main = [
            {"op": "conv", "out": mid, "k": 1, "stride": 1, "pad": 0, "bias": False, "name": "conv0"},
            dict(bn, name="bn0"), {"op": "relu"},
            {"op": "conv", "out": mid, "k": 3, "stride": s, "pad": 1, "bias": False, "name": "conv1"},
            dict(bn, name="bn1"), {"op": "relu"},
            {"op": "conv", "out": cout, "k": 1, "stride": 1, "pad": 0, "bias": False, "name": "conv2"},
            dict(bn, name="bn2"),
        ]
    else:
        raise ValueError(f"not a residual block: {layer['op']}")
    shortcut = []
    if s != 1 or cin != cout:
        shortcut = [
            {"op": "conv", "out": cout, "k": 1, "stride": s, "pad": 0, "bias": False, "name": "proj"},
            dict(bn, name="proj_bn"),
        ]
    return main, shortcut


def _walk(layers: List[dict], shape: Tuple[int, ...], prefix: str = ""
          ) -> Iterator[Tuple[str, dict, Tuple[int, ...], Tuple[int, ...]]]:
    """Yield (name, layer, input shape, output shape) for every plain layer,
    blocks expanded; a block itself is yielded last as op ``add`` so its
    add + activation can be counted."""
    for i, layer in enumerate(layers):
        op = layer["op"]
        name = prefix + layer.get("name", f"{op}{i}")
        if op in ("basic", "bottleneck"):
            main, shortcut = expand_block(layer)
            out = shape
            for item in _walk(main, shape, name + "/"):
                yield item
                out = item[3]
            for item in _walk(shortcut, shape, name + "/"):
                yield item
            yield name, {"op": "add"}, out, out
            shape = out
            continue
        if op == "conv":
            c, h, w = shape
            oh, ow = _out_hw(h, w, layer["k"], layer["stride"], layer["pad"])
            out = (layer["out"], oh, ow)
        elif op in ("maxpool", "avgpool"):
            c, h, w = shape
            oh, ow = _out_hw(h, w, layer["k"], layer["stride"], layer["pad"])
            out = (c, oh, ow)
        elif op == "flatten":
            n = 1
            for d in shape:
                n *= d
            out = (n,)
        elif op == "dense":
            out = (layer["out"],)
        elif op in ("bn", "relu"):
            out = shape
        else:
            raise ValueError(f"unknown layer op {op!r}")
        yield name, layer, shape, out
        shape = out


def walk(cfg: dict):
    return _walk(cfg["layers"], tuple(cfg["input_shape"]))


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def forward_flops_per_image(cfg: dict) -> int:
    total = 0
    for _, layer, sin, sout in walk(cfg):
        op = layer["op"]
        if op == "conv":
            total += 2 * layer["out"] * sin[0] * layer["k"] ** 2 * sout[1] * sout[2]
        elif op == "dense":
            total += 2 * sin[0] * layer["out"]
        elif op == "bn":
            total += 8 * _numel(sin)
        elif op == "relu":
            total += _numel(sin)
        elif op in ("maxpool", "avgpool"):
            total += _numel(sout) * layer["k"] ** 2
        elif op == "add":
            total += 2 * _numel(sout)
    return total


def train_flops_per_image(cfg: dict) -> int:
    return 3 * forward_flops_per_image(cfg)


def param_count(cfg: dict) -> int:
    total = 0
    for _, layer, sin, sout in walk(cfg):
        op = layer["op"]
        if op == "conv":
            total += layer["out"] * sin[0] * layer["k"] ** 2 + (layer["out"] if layer["bias"] else 0)
        elif op == "dense":
            total += sin[0] * layer["out"] + (layer["out"] if layer["bias"] else 0)
        elif op == "bn":
            total += 2 * sin[0]
    return total


def conv_flops(batch: int, cin: int, cout: int, k: int, oh: int, ow: int) -> int:
    """2·MACs of one convolution product. The forward product, the
    input-gradient product and the weight-gradient product of one layer all
    contract the same (batch·oh·ow) x (cin·k·k) x cout volume."""
    return 2 * batch * oh * ow * cin * k * k * cout


def conv_bytes(kind: str, batch: int, cin: int, cout: int, k: int, h: int,
               w: int, oh: int, ow: int, act_bytes: int = 2,
               weight_bytes: int = 2, wgrad_bytes: int = 4) -> int:
    """Least bytes of one convolution product: each operand read once, the
    result written once. ``kind``: ``fwd`` (x, w -> y), ``dgrad``
    (dy, w -> dx), ``wgrad`` (x, dy -> dw; the weight gradient leaves in
    float32, the master dtype)."""
    x = batch * cin * h * w * act_bytes
    y = batch * cout * oh * ow * act_bytes
    wt = cout * cin * k * k * weight_bytes
    if kind == "fwd" or kind == "dgrad":
        return x + y + wt
    if kind == "wgrad":
        return x + y + cout * cin * k * k * wgrad_bytes
    raise ValueError(f"unknown convolution product {kind!r}")


def conv_layers(cfg: dict) -> Dict[str, dict]:
    """Every convolution of the configuration by its scoped name
    (``layer1_block2/conv0``, ``conv1``), with the shapes the functions above
    take (per image: multiply by the batch)."""
    out = {}
    for name, layer, sin, sout in walk(cfg):
        if layer["op"] == "conv":
            out[name] = {"cin": sin[0], "cout": layer["out"], "k": layer["k"],
                         "h": sin[1], "w": sin[2], "oh": sout[1], "ow": sout[2]}
    return out


def conv_min_seconds(kind: str, batch: int, g: dict, peaks: Dict[str, float]
                     ) -> Tuple[float, str]:
    """The least time the chip could take for one convolution product, and
    which bound binds (``flops`` or ``bytes``)."""
    f = conv_flops(batch, g["cin"], g["cout"], g["k"], g["oh"], g["ow"])
    b = conv_bytes(kind, batch, g["cin"], g["cout"], g["k"], g["h"], g["w"],
                   g["oh"], g["ow"])
    tf, tb = f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
