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
(``ops/grouped.py``) run over the held groups.

**Row buffers sized by what the step routes here.** Only scalars are kept
for all ``T * top_k`` pairs (expert ids, weights, the sorted order and its
inverse). The held pairs are computed in rounds of ``R`` sorted rows
(``round_rows``: twice the even share ``T * top_k * experts_held /
n_routed``), as many rounds as the step's own count of held pairs needs, a
loop whose trip count is read on the device: none when nothing is held, one
or two on text, ``T * top_k / R`` when every pair is held, so the layer is
exact at any imbalance and every row buffer has ``R`` rows. A round gathers
its ``R`` rows (dispatch), runs the products, the activation and the pairs'
weights over them, takes its rows in token order (one sort of ``R`` keys),
adds each token's at most ``top_k`` adjacent rows and places the sums by one
gather of ``T`` rows (combine); dispatch and combine are each other's
transposes, so neither direction scatters or touches ``T * top_k`` rows. The
backward pass runs the same rounds, computes each anew and pulls the
cotangent through it (``_rounds``), so all the routed part keeps is its
operands.

**Scores.** ``scoring="softmax"`` (the default): the weights are the
softmax's. ``scoring="sigmoid"``: each expert's score is its own sigmoid, and
the ``top_k`` are the largest of score plus ``select_bias``, a buffer in the
layer's state that no gradient reaches and that does not enter the weights
(the family balances load by moving it; nothing here moves it yet).

**Balance loss** (sequence-wise, as published): per sequence
``f_e = n_routed / (top_k * S) * #{t: e in top_k(t)}``, ``P_e = mean_t s_te``,
``L_aux = alpha * mean_seq sum_e f_e P_e``. It adds its gradient to the
router and is not part of the model's output or the reported loss.

Scopes, side by side (inside the rungs, innermost): ``<name>.router``,
``.dispatch``, ``.experts``, ``.combine``, ``.shared``. State: the routing
counts since they were last published (``publish_routing``), as batch-norm
statistics ride in state.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import get_registry
from ..ops.grouped import grouped_matmul, padded_rows
from . import initializers as init
from .factory import register_layer
from .layer import ParameterizedLayer
from .transformer import gated_mlp, init_gated_mlp

COUNTS = ("pairs_routed", "pairs_held", "load_max", "pair_rows", "fallbacks")


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


def round_rows(tokens: int, top_k: int, experts_held: int, n_routed: int) -> int:
    """The rows of a round of the routed part: twice the even share of the
    pairs, rounded up to a row tile of the grouped product, and no more than
    all the pairs."""
    pairs = tokens * top_k
    return min(padded_rows(2 * -(-pairs * experts_held // n_routed)), pairs)


@jax.custom_vjp
def _sort_pairs(group, weight):
    """``(order, weight[order])`` for ``order`` the stable sort of the pairs
    by ``group``. The weights ride in the sort and their cotangents ride back
    in a sort by ``order``: on the chip a gather or a scatter of ``T * top_k``
    scalars costs ten such sorts (0.95 ms against 0.08)."""
    pairs = jnp.arange(group.shape[0], dtype=jnp.int32)
    _, order, weight = jax.lax.sort((group, pairs, weight), num_keys=1, is_stable=True)
    return order, weight


def _sort_pairs_fwd(group, weight):
    order, weight = _sort_pairs(group, weight)
    return (order, weight), order


def _sort_pairs_bwd(order, g):
    return None, jax.lax.sort((order, g[1]), num_keys=1)[1]


_sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


def _token_order(order, total, count, k):
    """For ``C = len(order)`` sorted rows of which the first ``total`` are
    held: ``(token, source, owner, first, count)``. ``token[r]`` is row
    ``r``'s token; ``source[q]`` is the row of the ``q``-th held pair in
    token order and ``owner[q]`` its token (``T`` past the held pairs, which
    no token is); token ``t``'s ``count[t]`` rows start at ``first[t]``."""
    c = order.shape[0]
    rows = jnp.arange(c, dtype=jnp.int32)
    key = jnp.where(rows < total, order, count.shape[0] * k)
    key, source = jax.lax.sort((key, rows), num_keys=1)
    return (jax.lax.div(order, k), source, jax.lax.div(key, k),
            jnp.cumsum(count) - count, count)


def _sum_by_token(rows, index, k):
    """``[T, E]``: each token's held rows of ``rows [C, E]`` summed (in
    float32). In token order a token's rows are adjacent and at most ``k``:
    ``k - 1`` shifted, masked adds leave the sum on its first row."""
    _, source, owner, first, count = index
    c = rows.shape[0]
    ordered = jnp.pad(rows[source], ((0, k - 1), (0, 0)))
    owners = jnp.pad(owner, (0, k - 1), constant_values=-1)
    acc = ordered[:c].astype(jnp.float32)
    for j in range(1, k):
        acc = acc + jnp.where((owners[j:j + c] == owner)[:, None], ordered[j:j + c], 0)
    placed = jnp.take(acc.astype(rows.dtype), first, axis=0, mode="clip")
    return jnp.where((count > 0)[:, None], placed, 0)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _spread(x, index, k):
    """``[C, E]``: row ``r`` is its token's row of ``x [T, E]``;
    ``_gather_sum`` transposed."""
    return x[index[0]]


def _spread_fwd(x, index, k):
    return x[index[0]], index


def _spread_bwd(k, index, g):
    return _sum_by_token(g, index, k), None


_spread.defvjp(_spread_fwd, _spread_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_sum(rows, index, k):
    """``_sum_by_token``, whose transpose is ``_spread``: a gather of ``C``
    rows, so neither direction scatters."""
    return _sum_by_token(rows, index, k)


def _gather_sum_fwd(rows, index, k):
    return _sum_by_token(rows, index, k), index


def _gather_sum_bwd(k, index, g):
    return g[index[0]], None


_gather_sum.defvjp(_gather_sum_fwd, _gather_sum_bwd)


def _rounds(one_round, scope, turns, x, *operands):
    """``sum(one_round(j, x, *operands) for j in range(turns))`` in float32,
    as ``x``; ``turns`` is read on the device. ``operands``: float trees and,
    last, a tree of integers. The backward pass runs the same rounds again:
    each is computed anew and the cotangent pulled through it, so a round
    keeps nothing for it, and the cotangents are summed in float32. The sums
    lie under ``scope``."""
    def wide(tree):
        with jax.named_scope(scope):
            return jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)

    def add(acc, new):
        with jax.named_scope(scope):
            return jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype), acc, new)

    def like(acc, tree):
        with jax.named_scope(scope):
            return jax.tree_util.tree_map(lambda a, b: a.astype(b.dtype), acc, tree)

    @jax.custom_vjp
    def rounds(turns, x, *operands):
        total = jax.lax.fori_loop(0, turns, lambda j, acc: add(acc, one_round(j, x, *operands)),
                                  wide(x))
        return like(total, x)

    def backward(res, g):
        turns, x, *floats, index = res

        def pull(j, acc):
            through = jax.vjp(lambda *floats: one_round(j, *floats, index), x, *floats)[1]
            return add(acc, through(g))
        cotangents = jax.lax.fori_loop(0, turns, pull, wide((x, *floats)))
        return (None, *like(cotangents, (x, *floats)), None)

    rounds.defvjp(lambda turns, x, *operands: (rounds(turns, x, *operands),
                                               (turns, x, *operands)), backward)
    return rounds(turns, x, *operands)


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
    reg.counter("moe_pair_rows_total",
                "rows of the buffers the expert layers computed over (the "
                "rounds each layer-step took times a round's rows); "
                "moe_pairs_held_total over this is the buffers' occupancy"
                ).inc(int(sum(g[3] for g in got)))
    reg.counter("moe_capacity_fallbacks_total",
                "layer-steps whose held pairs took every round, T * top_k "
                "rows in all: the worst case").inc(int(sum(g[4] for g in got)))
    return cleared


@register_layer("moe")
class MoELayer(ParameterizedLayer):
    def __init__(self, width: int, *, n_routed: int, top_k: int,
                 first_expert: int = 0, experts_held: Optional[int] = None,
                 n_shared: int = 0, aux_alpha: float = 0.0,
                 routed_scale: float = 1.0, norm_topk: bool = False,
                 scoring: str = "softmax", init_std: float = 0.02,
                 name: Optional[str] = None):
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
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: unknown scoring {scoring!r}")
        self.scoring = scoring
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
        state = init_routing_state()
        if self.scoring == "sigmoid":
            state["select_bias"] = jnp.zeros((self.n_routed,), jnp.float32)
        return params, state

    def route(self, router_w, x, select_bias=None):
        """Scores over all experts in float32, the ``top_k`` largest with
        their weights, and the balance loss. ``x``: (B, S, E)."""
        logits = jnp.matmul(x, router_w, preferred_element_type=jnp.float32)
        if self.scoring == "sigmoid":
            s = jax.nn.sigmoid(logits.astype(jnp.float32))
            _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(select_bias), self.top_k)
            top_w = jnp.take_along_axis(s, top_e, axis=-1)
        else:
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

    def _experts(self, w, xs, sizes, weight):
        """The held experts over sorted rows, each row times its pair's
        weight (before the last product: the scaled rows are not kept)."""
        with jax.named_scope(self.name + ".experts"):
            hidden = (jax.nn.silu(grouped_matmul(xs, w["gate"], sizes))
                      * grouped_matmul(xs, w["up"], sizes))
            hidden = hidden * weight[:, None].astype(hidden.dtype)
            return grouped_matmul(hidden, w["down"], sizes)

    def _round(self, rows: int):
        """The routed part's ``j``-th round: sorted rows ``j * rows ..`` of
        the step, ``(j, x [T, E], expert weights, sorted weights, integers)
        -> [T, E]``. Every operation lies under one of the layer's scopes,
        innermost (a trace names an event by that)."""
        k, name = self.top_k, self.name

        def one_round(j, x, w, weight, index):
            order, place, ends, total = index
            with jax.named_scope(name + ".dispatch"):
                start = j * rows
                mine = jax.lax.dynamic_slice(order, (start,), (rows,))
                weight = jax.lax.dynamic_slice(weight, (start,), (rows,))
                sizes = jnp.diff(jnp.clip(ends - start, 0, rows), prepend=0)
                # a token's pairs of this round: its held pairs sorted into it
                count = jnp.sum(place == j, axis=1, dtype=jnp.int32)
                index = _token_order(mine, jnp.clip(total - start, 0, rows), count, k)
                xs = _spread(x, index, k)
            ys = self._experts(w, xs, sizes, weight)
            with jax.named_scope(name + ".combine"):
                return _gather_sum(ys, index, k)
        return one_round

    def routed(self, w, x, top_w, top_e):
        """The held experts' part of the result: ``x [T, E]``, each token's
        experts and weights ``[T, k]`` -> ``[T, E]``; and the step's counts
        (pairs held, the largest group, buffer rows, whether every round was
        needed)."""
        t, k, g = x.shape[0], self.top_k, self.experts_held
        rows = round_rows(t, k, g, self.n_routed)
        most = -(-t * k // rows)
        with jax.named_scope(self.name + ".dispatch"):
            local = top_e.reshape(t * k) - self.first_expert
            held = (local >= 0) & (local < g)
            group = jnp.where(held, local, g)           # absent experts last
            sizes = jnp.sum(jax.nn.one_hot(group, g + 1, dtype=jnp.int32), axis=0)[:g]
            # the absent experts' pairs: weight 0 (their rows are zeros too)
            order, weight = _sort_pairs(group, jnp.where(held, top_w.reshape(t * k), 0.0))
            # the round each held pair is sorted into; -1 for the others
            at = jax.lax.sort((order, jnp.arange(t * k, dtype=jnp.int32)), num_keys=1)[1]
            place = jnp.where(held, at // rows, -1).reshape(t, k)
            total = jnp.sum(sizes)
            turns = (total + rows - 1) // rows
            spare = most * rows - t * k                 # the last round's rows past the pairs
            order, weight = jnp.pad(order, (0, spare)), jnp.pad(weight, (0, spare))
        # under no scope of its own: a trace would name the rounds' events by it
        y = _rounds(self._round(rows), self.name + ".combine", turns, x, w, weight,
                    (order, place, jnp.cumsum(sizes), total))
        return y, (total, jnp.max(sizes), turns * rows,
                   ((turns == most) & (most > 1)).astype(jnp.int32))

    def apply(self, params, state, x, *, training=False, rng=None):
        b, s, e = x.shape
        t, k = b * s, self.top_k
        with jax.named_scope(self.name + ".router"):
            bias = {"select_bias": state["select_bias"]} if self.scoring == "sigmoid" else {}
            top_w, top_e, aux = self.route(params["router"], x, **bias)
        y, (held, load, rows, fell_back) = self.routed(
            params["experts"], x.reshape(t, e), top_w.reshape(t, k), top_e.reshape(t, k))
        y = y.reshape(b, s, e)
        if self.n_shared:
            with jax.named_scope(self.name + ".shared"):
                y = y + gated_mlp(params["shared"], x)
        if training:
            if self.aux_alpha:
                y = add_gradient_of(y, aux)
            state = {**state, "pairs_routed": state["pairs_routed"] + t * k,
                     "pairs_held": state["pairs_held"] + held,
                     "load_max": jnp.maximum(state["load_max"], load),
                     "pair_rows": state["pair_rows"] + rows,
                     "fallbacks": state["fallbacks"] + fell_back}
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
                "norm_topk": self.norm_topk, "scoring": self.scoring,
                "init_std": self.init_std}
