"""Sequential model container.

Reference equivalent: ``Sequential<T>``
(``include/nn/sequential.hpp:39-1152``): ordered layer container with
double-buffered forward/backward, ``split(partitions)`` → stage models
(:967-986), JSON architecture (de)serialization (:1001-1125), binary weight
save/load (:832-915), and per-layer profiling maps (:54-55).

TPU-native differences: forward is a pure function over a params/state pytree
(the reference's ping-pong buffer discipline is XLA's job now); backward is
``jax.grad``; weights save/load lives in ``dcnn_tpu.train.checkpoint``
(checkpoints include optimizer state — an improvement over the reference,
which drops it, SURVEY.md §5.4).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from ..core.precision import cast_to_compute
from ..obs.registry import get_registry
from ..obs.xla import install_compile_listener
from ..ops import activations as act_ops
from ..ops import conv as conv_ops
from ..ops import pool as pool_ops
from .factory import layer_from_config
from .layer import Layer, Shape
from .layers import ActivationLayer, BatchNormLayer, Conv2DLayer, MaxPool2DLayer

Params = Tuple[Dict[str, Any], ...]
State = Tuple[Dict[str, Any], ...]


def _pool_phase_head(layers: Sequence[Layer], x_shape: Tuple[int, ...]
                     ) -> Optional[Tuple[Layer, ...]]:
    """The layers of a head ``Conv2DLayer`` (stride 1) -> [``BatchNormLayer``]
    -> elementwise ``ActivationLayer`` -> ``MaxPool2DLayer`` (2/2/0) over an
    even-sized conv output, which a training-mode ``Sequential.apply``
    computes by window position (``_apply_pool_phase``); ``None`` for every
    other model.

    Only at the head, where the conv reads the model's input: the four
    products read it four times, which is small against what the 2x2 pool's
    interleaving backward costs when the input has a few channels (ResNet-18
    on the v5e: PERF.md, PR 29) and is not measured for a pool deeper in a
    network, whose input is as large as its output."""
    if len(x_shape) != 4 or len(layers) < 3 or type(layers[0]) is not Conv2DLayer:
        return None
    conv = layers[0]
    bn = layers[1] if type(layers[1]) is BatchNormLayer else None
    head = tuple(layers[:4 if bn else 3])
    act, pool = head[-2:]
    out = conv.output_shape(tuple(x_shape[1:]))
    oh, ow = out[1:] if conv.data_format == "NCHW" else out[:2]
    if (type(act) is ActivationLayer and act.activation in act_ops.ELEMENTWISE
            and type(pool) is MaxPool2DLayer
            and conv.stride == (1, 1)
            and pool.kernel_size == pool.stride == (2, 2) and pool.padding == (0, 0)
            and all(l.data_format == conv.data_format for l in (bn, pool) if l)
            and oh % 2 == 0 and ow % 2 == 0):
        return head
    return None


def _apply_pool_phase(head: Tuple[Layer, ...], params: Params, state: State,
                      x: jax.Array) -> Tuple[jax.Array, List[Any]]:
    """The training-mode conv -> [bn] -> activation -> 2x2 max-pool of
    ``_pool_phase_head``, the conv computed once per window position
    (``conv2d_pool_phases``), so that the batch norm, the activation, the
    pool and all their gradients are elementwise over four arrays of the
    pool's output shape and nothing has to be interleaved: the backward of
    ``reduce_window`` max is a select-and-scatter that fuses with nothing and
    needs relu(bn(z)) written out for it. Same mathematics, tie rule and
    layer states as the layer-by-layer path; returns the output and the
    covered layers' states.

    What is gained is in the backward; the forward alone reads x four times
    and is the slower one (PERF.md, PR 29), so evaluation and serving
    (``training=False``) stay on the layer-by-layer path.

    Each product is a quarter of the conv's work and runs under the scope
    ``<conv>.phase``, so that a trace shows it as what it is and not as the
    whole layer's product; bn, activation and pool keep their layers' scopes."""
    conv, act, pool = head[0], head[-2], head[-1]
    get_registry().counter(
        "nn_pool_phase_rewrites_total",
        "traces of Sequential.apply that computed a head conv -> [bn] -> "
        "activation -> 2x2 max-pool by window position").inc()
    with jax.named_scope(f"{conv.name}.phase"):
        p = cast_to_compute(params[0])
        hs = conv_ops.conv2d_pool_phases(
            x, p["w"], p.get("b"), padding=conv.padding, data_format=conv.data_format)
    new_state = list(state[:len(head)])
    if len(head) == 4:
        with jax.named_scope(head[1].name):
            hs, new_state[1] = head[1].apply_parts(
                cast_to_compute(params[1]), state[1], hs, training=True)
    with jax.named_scope(act.name):
        hs = [act.forward(h) for h in hs]
    with jax.named_scope(pool.name):
        h = pool_ops.max_pool2d_phases(*hs)
    return h, new_state


class Sequential:
    def __init__(self, layers: Sequence[Layer] = (), name: str = "sequential",
                 input_shape: Optional[Shape] = None):
        install_compile_listener()
        self.name = name
        self.layers: List[Layer] = []
        self.input_shape: Optional[Tuple[int, ...]] = (
            tuple(input_shape) if input_shape is not None else None)
        for l in layers:
            self.add(l)

    # -- construction --
    def add(self, layer: Layer) -> "Sequential":
        base = layer.name
        names = {l.name for l in self.layers}
        if base in names:
            i = 1
            while f"{base}_{i}" in names:
                i += 1
            layer.name = f"{base}_{i}"
        self.layers.append(layer)
        return self

    def __len__(self) -> int:
        return len(self.layers)

    def __getitem__(self, idx):
        return self.layers[idx]

    # -- functional interface --
    def init(self, key: jax.Array, input_shape: Optional[Shape] = None) -> Tuple[Params, State]:
        """Initialize all layer params/state. ``input_shape`` is per-sample
        (C,H,W)/(features,), like the reference builder's input shape."""
        shape = tuple(input_shape) if input_shape is not None else self.input_shape
        if shape is None:
            raise ValueError("input_shape required (not set at construction)")
        self.input_shape = shape
        keys = jax.random.split(key, max(len(self.layers), 1))
        params, state = [], []
        for i, layer in enumerate(self.layers):
            p, s = layer.init(keys[i], shape)
            params.append(p)
            state.append(s)
            shape = layer.output_shape(shape)
        return tuple(params), tuple(state)

    def apply(self, params: Params, state: State, x: jax.Array, *,
              training: bool = False, rng: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, State]:
        """Chain layers (reference forward loop ``sequential.hpp:459-466``).
        Per-layer rng derived with ``fold_in(rng, i)`` so dropout masks are
        deterministic given one step key.

        Under the ``bf16`` precision mode (core.precision) the input and each
        layer's params are cast to bfloat16 at point of use; layer state (BN
        running statistics) stays fp32, and batch_norm computes its reductions
        in fp32 internally."""
        h = cast_to_compute(x)
        new_state = []
        head = _pool_phase_head(self.layers, h.shape) if training else None
        if head is not None:
            h, new_state = _apply_pool_phase(head, params, state, h)
        for i, layer in enumerate(self.layers[len(new_state):], len(new_state)):
            sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
            # named_scope tags every op with its layer in profiler traces, so
            # xprof framework-op stats aggregate per layer (the fused-step
            # ground truth the replay profiler is compared against in
            # RESULTS.md "profiling skew"); zero runtime cost outside tracing
            with jax.named_scope(getattr(layer, "name", None)
                                 or f"layer{i}"):
                h, s = layer.apply(cast_to_compute(params[i]), state[i], h,
                                   training=training, rng=sub_rng)
            new_state.append(s)
        return h, tuple(new_state)

    def __call__(self, params, state, x, **kw):
        return self.apply(params, state, x, **kw)

    # -- shape / cost metadata --
    def output_shape(self, input_shape: Optional[Shape] = None) -> Shape:
        shape = tuple(input_shape) if input_shape is not None else self.input_shape
        if shape is None:
            raise ValueError("input_shape unknown")
        for layer in self.layers:
            shape = layer.output_shape(shape)
        return shape

    def layer_shapes(self, input_shape: Optional[Shape] = None) -> List[Shape]:
        """Per-layer *input* shapes; index i is what layer i receives."""
        shape = tuple(input_shape) if input_shape is not None else self.input_shape
        if shape is None:
            raise ValueError("input_shape unknown")
        shapes = []
        for layer in self.layers:
            shapes.append(shape)
            shape = layer.output_shape(shape)
        return shapes

    def forward_complexity(self, input_shape: Optional[Shape] = None) -> int:
        total = 0
        for layer, shape in zip(self.layers, self.layer_shapes(input_shape)):
            total += layer.forward_complexity(shape)
        return total

    def param_count(self, input_shape: Optional[Shape] = None) -> int:
        total = 0
        for layer, shape in zip(self.layers, self.layer_shapes(input_shape)):
            total += layer.param_count(shape)
        return total

    # -- pipeline split (reference sequential.hpp:967-986) --
    def split(self, partitions: Sequence[Tuple[int, int]]) -> List["Sequential"]:
        """Split into stage models by [start, end) layer ranges, as produced by
        a Partitioner. Stage input shapes are propagated so each stage can be
        initialized/deployed standalone (the reference ships stage configs as
        JSON to workers, ``coordinator.hpp:524-555``)."""
        stages = []
        shapes = self.layer_shapes() if self.input_shape is not None else None
        for si, (start, end) in enumerate(partitions):
            if not (0 <= start < end <= len(self.layers)):
                raise ValueError(f"bad partition range ({start}, {end})")
            stage = Sequential(name=f"{self.name}_stage{si}")
            stage.layers = self.layers[start:end]
            if shapes is not None:
                stage.input_shape = shapes[start]
            stages.append(stage)
        return stages

    def split_params(self, params: Sequence, partitions: Sequence[Tuple[int, int]]) -> List[Tuple]:
        """Partition an existing params (or state) tuple alongside ``split``."""
        return [tuple(params[start:end]) for (start, end) in partitions]

    # -- config round-trip (reference sequential.hpp:1001-1125) --
    def get_config(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "input_shape": list(self.input_shape) if self.input_shape else None,
            "layers": [l.get_config() for l in self.layers],
        }

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Sequential":
        model = cls(name=cfg.get("name", "sequential"),
                    input_shape=tuple(cfg["input_shape"]) if cfg.get("input_shape") else None)
        for lc in cfg["layers"]:
            model.add(layer_from_config(lc))
        return model

    # -- introspection --
    def summary(self, input_shape: Optional[Shape] = None) -> str:
        """Printable architecture table (reference ``print_profiling_summary``
        prints a similar per-layer table, sequential.hpp:323-418)."""
        shapes = self.layer_shapes(input_shape)
        lines = [f"Sequential '{self.name}'",
                 f"{'#':>3} {'layer':<24} {'output shape':<20} {'params':>12} {'MFLOPs':>10}"]
        total_p = 0
        for i, (layer, shape) in enumerate(zip(self.layers, shapes)):
            out = layer.output_shape(shape)
            p = layer.param_count(shape)
            fl = layer.forward_complexity(shape) / 1e6
            total_p += p
            lines.append(f"{i:>3} {layer.name:<24} {str(out):<20} {p:>12,} {fl:>10.2f}")
        lines.append(f"total params: {total_p:,}")
        return "\n".join(lines)

    def __repr__(self):
        return f"Sequential(name={self.name!r}, layers={len(self.layers)})"
