"""A mixture-of-experts feed-forward layer that is told which experts it
holds.

The router scores every token over all ``n_routed`` experts and keeps the
``top_k`` largest; the layer computes the part of the result that its own
experts ``first_expert .. first_expert + experts_held`` give, plus the shared
experts, and leaves out what the absent experts would add. That is one
expert-parallel rank's work without its exchange: with the ranks' routed
parts summed (the all-to-all's job, not done here) and the shared part
counted once, the whole layer results (``tests/test_lm.py``, the share test).

**Dropless.** Every (token, held expert) pair is computed whatever the
imbalance. Shapes stay static: the ``T * top_k`` pairs are sorted by expert
with the pairs of absent experts last, and three grouped products
(``ops/grouped.py``) run over the held groups. The buffers are sized for the
worst case (every pair held); the products' work follows the group sizes.

**Balance loss** (sequence-wise, as published): per sequence
``f_e = n_routed / (top_k * S) * #{t: e in top_k(t)}``, ``P_e = mean_t s_te``,
``L_aux = alpha * mean_seq sum_e f_e P_e``. It adds its gradient to the
router and is not part of the model's output or the reported loss.

Scopes, side by side: ``<name>.router``, ``.dispatch``, ``.experts``,
``.combine``, ``.shared``. State: the routing counts since they were last
published (``publish_routing``), as batch-norm statistics ride in state.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import get_registry
from ..ops.grouped import grouped_matmul
from . import initializers as init
from .factory import register_layer
from .layer import ParameterizedLayer
from .transformer import gated_mlp, init_gated_mlp

COUNTS = ("pairs_routed", "pairs_held", "load_max")


@jax.custom_vjp
def add_gradient_of(y, aux):
    """``y``, with the gradient of the scalar ``aux`` added to whatever
    ``aux`` depends on: ``aux``'s cotangent is 1 however ``y`` is used."""
    del aux
    return y


def _add_fwd(y, aux):
    return y, None


def _add_bwd(_, g):
    return g, jnp.ones((), jnp.float32)


add_gradient_of.defvjp(_add_fwd, _add_bwd)


@jax.custom_vjp
def _dispatch(x, order, inverse):
    """Row ``p`` of the result is ``x[order[p] // k]``: each token's row once
    per pair, in sorted-pair order. ``inverse`` is the inverse permutation as
    ``[T, k]``. The backward gathers by it and sums a token's pairs, so
    neither direction scatters."""
    return x[order // inverse.shape[1]]


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), inverse


def _dispatch_bwd(inverse, g):
    return g[inverse].sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, order, inverse):
    """The sorted pairs' rows back in (token, pair) order, summed per token:
    ``_dispatch`` transposed."""
    return ys[inverse].sum(axis=1)


def _combine_fwd(ys, order, inverse):
    return _combine(ys, order, inverse), (order, inverse.shape[1])


def _combine_bwd(res, g):
    order, k = res
    return g[order // k], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def init_routing_state():
    return {k: jnp.zeros((), jnp.int32) for k in COUNTS}


def publish_routing(state):
    """Add the routing counts that ride in ``state`` (any pytree; the dicts
    with this module's keys are found) to the registry's counters and return
    the state with those counts back at zero. Called where the host has
    just fenced on the dispatch's loss, so it waits for nothing."""
    found = []

    def visit(node):
        if isinstance(node, dict) and set(COUNTS) <= set(node):
            found.append(node)
            return {**node, **init_routing_state()}
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(visit(v) for v in node)
        return node

    cleared = visit(state)
    if not found:
        return state
    got = jax.device_get([[n[k] for k in COUNTS] for n in found])
    reg = get_registry()
    reg.counter("moe_pairs_routed_total",
                "(token, expert) pairs the routers of expert layers chose, "
                "absent experts' included").inc(int(sum(g[0] for g in got)))
    reg.counter("moe_pairs_held_total",
                "(token, expert) pairs computed here: those of the experts "
                "the layers hold").inc(int(sum(g[1] for g in got)))
    reg.gauge("moe_expert_load_max",
              "the largest number of pairs one held expert got in one step "
              "of the last dispatch").set(int(max(g[2] for g in got)))
    return cleared


@register_layer("moe")
class MoELayer(ParameterizedLayer):
    def __init__(self, width: int, *, n_routed: int, top_k: int,
                 first_expert: int = 0, experts_held: Optional[int] = None,
                 n_shared: int = 0, aux_alpha: float = 0.0,
                 routed_scale: float = 1.0, norm_topk: bool = False,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name)
        self.width, self.n_routed, self.top_k = int(width), int(n_routed), int(top_k)
        self.first_expert = int(first_expert)
        self.experts_held = int(n_routed if experts_held is None else experts_held)
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_routed:
            raise ValueError(f"{self.name}: experts {self.first_expert}.."
                             f"{self.first_expert + self.experts_held} of {self.n_routed}")
        self.n_shared = int(n_shared)
        self.aux_alpha = float(aux_alpha)
        self.routed_scale = float(routed_scale)
        self.norm_topk = bool(norm_topk)
        self.init_std = float(init_std)

    def init(self, key, input_shape):
        e, g, std = input_shape[-1], self.experts_held, self.init_std
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        params = {"router": init.normal(kr, (e, self.n_routed), std),
                  "experts": {"gate": init.normal(kg, (g, e, self.width), std),
                              "up": init.normal(ku, (g, e, self.width), std),
                              "down": init.normal(kd, (g, self.width, e), std)}}
        if self.n_shared:
            params["shared"] = init_gated_mlp(ks, e, self.n_shared * self.width, std)
        return params, init_routing_state()

    def route(self, router_w, x):
        """Scores over all experts in float32, the ``top_k`` largest with
        their weights, and the balance loss. ``x``: (B, S, E)."""
        logits = jnp.matmul(x, router_w, preferred_element_type=jnp.float32)
        s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_w, top_e = jax.lax.top_k(s, self.top_k)
        if self.norm_topk:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        top_w = top_w * self.routed_scale
        seq = x.shape[1]
        chosen = jnp.sum(jax.nn.one_hot(top_e, self.n_routed, dtype=jnp.float32),
                         axis=(1, 2))                               # (B, n_routed)
        f = chosen * (self.n_routed / (self.top_k * seq))
        aux = self.aux_alpha * jnp.mean(jnp.sum(f * jnp.mean(s, axis=1), axis=-1))
        return top_w, top_e, aux

    def apply(self, params, state, x, *, training=False, rng=None):
        b, s, e = x.shape
        t, k, g = b * s, self.top_k, self.experts_held
        name = self.name
        with jax.named_scope(name + ".router"):
            top_w, top_e, aux = self.route(params["router"], x)
        with jax.named_scope(name + ".dispatch"):
            local = top_e.reshape(t * k) - self.first_expert
            held = (local >= 0) & (local < g)
            group = jnp.where(held, local, g)           # absent experts last
            order = jnp.argsort(group, stable=True)
            inverse = jnp.argsort(order).reshape(t, k)
            sizes = jnp.sum(jax.nn.one_hot(group, g + 1, dtype=jnp.int32), axis=0)[:g]
            xs = _dispatch(x.reshape(t, e), order, inverse)
        with jax.named_scope(name + ".experts"):
            w = params["experts"]
            hidden = (jax.nn.silu(grouped_matmul(xs, w["gate"], sizes))
                      * grouped_matmul(xs, w["up"], sizes))
            ys = grouped_matmul(hidden, w["down"], sizes)
        with jax.named_scope(name + ".combine"):
            # the absent experts' pairs: rows of zeros (grouped_matmul), weight 0
            weight = jnp.where(held, top_w.reshape(t * k), 0.0)[order]
            y = _combine(ys * weight[:, None].astype(ys.dtype), order,
                         inverse).reshape(b, s, e)
        if self.n_shared:
            with jax.named_scope(name + ".shared"):
                y = y + gated_mlp(params["shared"], x)
        if training:
            if self.aux_alpha:
                y = add_gradient_of(y, aux)
            state = {"pairs_routed": state["pairs_routed"] + t * k,
                     "pairs_held": state["pairs_held"] + jnp.sum(sizes),
                     "load_max": jnp.maximum(state["load_max"], jnp.max(sizes))}
        return y, state

    def param_count(self, input_shape):
        e = input_shape[-1]
        return (e * self.n_routed
                + 3 * e * self.width * (self.experts_held + self.n_shared))

    def get_config(self):
        return {**super().get_config(), "width": self.width,
                "n_routed": self.n_routed, "top_k": self.top_k,
                "first_expert": self.first_expert,
                "experts_held": self.experts_held, "n_shared": self.n_shared,
                "aux_alpha": self.aux_alpha, "routed_scale": self.routed_scale,
                "norm_topk": self.norm_topk, "init_std": self.init_std}
