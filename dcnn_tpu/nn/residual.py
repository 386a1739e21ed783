"""Residual block.

Reference equivalent: ``ResidualBlock``
(``include/nn/blocks_impl/residual_block.hpp:30-170``): main path = arbitrary
layer list, shortcut = identity or projection layer list,
``out = final_activation(F(x) + s(x))``. The reference caches the
pre-activation sum and input shape per microbatch for its hand-written
backward (:36-40, :145-152); here those residuals are owned by autodiff.

JSON serialization recurses into nested layer configs exactly like the
reference's recursive ``residual_block`` handling in the factory
(``layers.hpp:228-287``).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from ..ops import activations as act_ops
from ..ops import conv as conv_ops
from .factory import layer_from_config, register_layer
from .layer import Layer
from .layers import ActivationLayer, BatchNormLayer, Conv2DLayer


@register_layer("residual_block")
class ResidualBlock(Layer):
    has_params = True

    def __init__(self, layers: Sequence[Layer], shortcut: Sequence[Layer] = (),
                 activation: str = "relu", name: Optional[str] = None):
        super().__init__(name)
        self.layers: List[Layer] = list(layers)
        self.shortcut: List[Layer] = list(shortcut)
        self.activation = activation.lower()
        if self.activation not in act_ops.ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}")

    # -- functional interface --
    def init(self, key, input_shape):
        keys = jax.random.split(key, len(self.layers) + max(len(self.shortcut), 1))
        main_params, main_state = [], []
        shape = input_shape
        for i, layer in enumerate(self.layers):
            p, s = layer.init(keys[i], shape)
            main_params.append(p)
            main_state.append(s)
            shape = layer.output_shape(shape)
        short_params, short_state = [], []
        sshape = input_shape
        for i, layer in enumerate(self.shortcut):
            p, s = layer.init(keys[len(self.layers) + i], sshape)
            short_params.append(p)
            short_state.append(s)
            sshape = layer.output_shape(sshape)
        if sshape != shape:
            raise ValueError(
                f"{self.name}: main path output {shape} != shortcut output {sshape}")
        return ({"main": tuple(main_params), "shortcut": tuple(short_params)},
                {"main": tuple(main_state), "shortcut": tuple(short_state)})

    def _channel_last(self, x_shape: Tuple[int, ...]
                      ) -> Optional[Tuple[List[Layer], List[Layer]]]:
        """Channel-last twins of the main path and the shortcut, where the
        block should run channel-last: an NCHW block of convolutions, batch
        norms and elementwise activations only (so that the order of the axes
        is nothing but the layers' ``data_format``), one of whose convolutions
        takes the pair-of-columns form (``ops/conv.py takes_pair_form``),
        which only a channel-last product can. ``None`` for every other
        block.

        The block's input and output stay NCHW; XLA:TPU keeps a narrow
        activation batch-minor in either order, so the two transposes are no
        copies, and between two such blocks they cancel (PERF.md, PR 31:
        72.53 ms a step either way)."""
        every = self.layers + self.shortcut
        if len(x_shape) != 4 or not all(
                (type(l) in (Conv2DLayer, BatchNormLayer) and l.data_format == "NCHW")
                or (type(l) is ActivationLayer and l.activation in act_ops.ELEMENTWISE)
                for l in every):
            return None

        paired = False
        for path in (self.layers, self.shortcut):
            shape = tuple(x_shape[1:])
            for l in path:
                if type(l) is Conv2DLayer:
                    paired |= conv_ops.takes_pair_form(
                        shape[0], l.out_channels, l.kernel_size[1], l.stride,
                        l.padding[1], shape[2])
                shape = l.output_shape(shape)
        if not paired:
            return None

        def twin(layer):
            layer = copy.copy(layer)
            if hasattr(layer, "data_format"):
                layer.data_format = "NHWC"
            return layer

        return [twin(l) for l in self.layers], [twin(l) for l in self.shortcut]

    def apply(self, params, state, x, *, training=False, rng=None):
        # Only a backward gains from the pair form, so evaluation and serving
        # keep the block as it is written.
        last = self._channel_last(x.shape) if training else None
        if last is not None:
            main, shortcut = last
            x = x.transpose(0, 2, 3, 1)
        else:
            main, shortcut = self.layers, self.shortcut
        h = x
        new_main = []
        for i, layer in enumerate(main):
            sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
            h, s = layer.apply(params["main"][i], state["main"][i], h,
                               training=training, rng=sub_rng)
            new_main.append(s)
        s_out = x
        new_short = []
        for i, layer in enumerate(shortcut):
            sub_rng = jax.random.fold_in(rng, 1000 + i) if rng is not None else None
            s_out, s = layer.apply(params["shortcut"][i], state["shortcut"][i], s_out,
                                   training=training, rng=sub_rng)
            new_short.append(s)
        out = act_ops.ACTIVATIONS[self.activation](h + s_out)
        if last is not None:
            out = out.transpose(0, 3, 1, 2)
        return out, {"main": tuple(new_main), "shortcut": tuple(new_short)}

    # -- metadata --
    def output_shape(self, input_shape):
        shape = input_shape
        for layer in self.layers:
            shape = layer.output_shape(shape)
        return shape

    def forward_complexity(self, input_shape):
        total = 0
        shape = input_shape
        for layer in self.layers:
            total += layer.forward_complexity(shape)
            shape = layer.output_shape(shape)
        sshape = input_shape
        for layer in self.shortcut:
            total += layer.forward_complexity(sshape)
            sshape = layer.output_shape(sshape)
        n = 1
        for d in shape:
            n *= d
        return total + 2 * n  # add + activation

    def param_count(self, input_shape):
        total = 0
        shape = input_shape
        for layer in self.layers:
            total += layer.param_count(shape)
            shape = layer.output_shape(shape)
        sshape = input_shape
        for layer in self.shortcut:
            total += layer.param_count(sshape)
            sshape = layer.output_shape(sshape)
        return total

    # -- config --
    def get_config(self) -> Dict[str, Any]:
        return {
            "type": self.type_name, "name": self.name,
            "activation": self.activation,
            "layers": [l.get_config() for l in self.layers],
            "shortcut": [l.get_config() for l in self.shortcut],
        }

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "ResidualBlock":
        return cls(
            layers=[layer_from_config(c) for c in cfg["layers"]],
            shortcut=[layer_from_config(c) for c in cfg.get("shortcut", [])],
            activation=cfg.get("activation", "relu"),
            name=cfg.get("name"),
        )
