"""The kernel choices of the language-model path, measured alone on the
chip at the cell's shapes (``chiprun -- python3 tools/bench_lm_kernels.py``,
about four minutes; the result goes to ``chiprun_out/bench_lm_kernels.json``;
``... bench_lm_kernels.py routed`` runs the named sections only):

- the grouped product of an expert layer (98,304 pair rows, 8 held experts,
  2048 -> 1408 -> 2048; gate, up and down, forward alone and with the
  backward): ``jax.lax.ragged_dot`` against the Pallas grouped product that
  ``ops/grouped.py`` calls (``jax.experimental.pallas.ops.tpu.megablox``),
  at two tilings, over group sizes that are uneven as Zipf ids route them,
  even, and with every pair held;
- one expert layer's whole routed part, dispatch through combine
  (``routed``; 16,384 tokens, top 6 of 64 with 8 held; forward and backward
  under a checkpoint, as the model's block runs it), at 12,288, 25,800 and
  98,304 held pairs of 98,304: PR 32's form over ``T * k``-row buffers
  against ``MoELayer.routed``, which takes as many rounds of 24,576 rows
  as the step's own count needs;
- the flash kernels at latent attention's shapes (4 x 16 and 4 x 32 heads
  x 4096, scores at 192, values at 128): the forward and the forward with
  the backward at the layer's tiles, and the backward alone at five tile
  shapes in either form, the fused kernel and the two kernels (the form
  chosen by patching ``ops/attention._fused_backward_fits``), with the two
  forms' largest difference at the layer's tiles;
- the chunked gated delta rule of a KDA layer (``kda``; 4 x 32 heads x 4096
  positions, keys and values 128 wide, decays as the layer starts them):
  ``ops/delta_rule.py`` forward and with the backward, a sequence at a time
  under a checkpoint as ``models/latent_moe.py`` runs a KDA mixer, at chunks
  of 64, 32 and 128 positions, with a chunk's inside by the Pallas kernels
  (what the TPU takes) and by XLA's products (what every other backend
  takes); the inside of one sequence's chunks alone, either way, forward and
  with the backward; XLA's parts (the decayed products, the triangular
  inverse); and the recurrence position by position on one sequence,
  forward only;
- the scan over one sequence's chunks alone (``scan``; 32 heads x 64 chunks
  of 64, 128 wide, bfloat16, the six arrays as the inside's kernels write
  them): ``lax.scan`` over XLA's products against the two scan kernels at
  groups of 8, 16 and 32 heads, forward and with the backward, and the
  cell's layer and step estimated from them (4 sequences, each forward
  once and again under the checkpoint with its backward).

The numbers in ``ops/grouped.py``, ``nn/latent_attention.py``,
``ops/delta_rule.py`` and ``CHANGES.md`` (PRs 32, 33, 35, 36) are this script's.
"""

import faulthandler
import functools
import importlib
import json
import os
import sys
import time

faulthandler.dump_traceback_later(600, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox  # noqa: E402

from dcnn_tpu.nn import moe  # noqa: E402
from dcnn_tpu.ops import delta_rule  # noqa: E402
from dcnn_tpu.ops.attention import flash_attention  # noqa: E402
from dcnn_tpu.ops.grouped import grouped_matmul  # noqa: E402

# the module, which the package's function of the same name shadows
attention = importlib.import_module("dcnn_tpu.ops.attention")

M, K, N, G = 98304, 2048, 1408, 8
GROUPS = {"zipf_like": [4200, 2600, 1900, 1300, 900, 700, 400, 288],
          "even": [1536] * 8, "all_held": [12288] * 8}
TILINGS = ((512, 1024, 1024), (512, 512, 1024))
LAYER_TILES = (512, 1024)          # nn/latent_attention.py's
FLASH_TILES = (LAYER_TILES, (1024, 512), (512, 512), (256, 1024), (1024, 1024))
FLASH_HEADS = (16, 32)             # DeepSeek-V2-Lite's, Kimi-Linear's
TOKENS, TOP_K, ROUTED = 16384, 6, 64
HELD_PAIRS = (12288, 25800, 98304)


def median_seconds(f, *args, n=5):
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t)
    return sorted(times)[len(times) // 2]


def ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)


def pallas(tiling):
    def product(x, w, sizes):
        return megablox.gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tiling)
    return product


def expert_forward(product):
    return lambda x, gate, up, down, s: product(
        jax.nn.silu(product(x, gate, s)) * product(x, up, s), down, s)


def expert_loss(product):
    forward = expert_forward(product)

    def loss(x, gate, up, down, s):
        live = (jnp.arange(M) < jnp.sum(s))[:, None]
        return jnp.sum(jnp.where(live, forward(x, gate, up, down, s), 0).astype(jnp.float32) ** 2)
    return loss


def grouped_products(out):
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    x = jax.random.normal(keys[0], (M, K), jnp.bfloat16)
    gate = jax.random.normal(keys[1], (G, K, N), jnp.bfloat16) * 0.02
    up = jax.random.normal(keys[2], (G, K, N), jnp.bfloat16) * 0.02
    down = jax.random.normal(keys[3], (G, N, K), jnp.bfloat16) * 0.02
    candidates = {"ragged_dot": ragged,
                  **{"megablox_%d_%d_%d" % t: pallas(t) for t in TILINGS}}
    for label, sizes in GROUPS.items():
        s = jnp.asarray(sizes, jnp.int32)
        flops = 3 * 2.0 * sum(sizes) * K * N
        row = {"pairs": sum(sizes)}
        for name, product in candidates.items():
            tf = median_seconds(jax.jit(expert_forward(product)), x, gate, up, down, s)
            tb = median_seconds(jax.jit(jax.grad(expert_loss(product), argnums=(0, 1, 2, 3))),
                                x, gate, up, down, s)
            row[name] = {"fwd_ms": tf * 1e3, "fwd_tflops": flops / tf / 1e12,
                         "fwd_bwd_ms": tb * 1e3, "fwd_bwd_tflops": 3 * flops / tb / 1e12}
            print(label, name, row[name], flush=True)
        out[label] = row
    # the two products agree on the groups' rows; neither writes the rows past them
    s = jnp.asarray(GROUPS["zipf_like"], jnp.int32)
    a, b = ragged(x, gate, s), pallas(TILINGS[0])(x, gate, s)
    live = sum(GROUPS["zipf_like"])
    out["max_abs_diff_live_rows"] = float(jnp.max(jnp.abs(
        a[:live].astype(jnp.float32) - b[:live].astype(jnp.float32))))
    out["ragged_rows_past_groups_max"] = float(jnp.max(jnp.abs(a[live:].astype(jnp.float32))))


def routing(pairs_held, key):
    """``[T, k]`` experts and weights with exactly ``pairs_held`` pairs on the
    8 held experts, spread over them as unevenly as ``GROUPS['zipf_like']``."""
    kp, kh, ka, kw = jax.random.split(key, 4)
    share = jnp.asarray(GROUPS["zipf_like"], jnp.float32)
    held = jax.random.choice(kh, G, (TOKENS * TOP_K,), p=share / share.sum())
    absent = jax.random.randint(ka, (TOKENS * TOP_K,), G, ROUTED)
    first = jax.random.permutation(kp, TOKENS * TOP_K) < pairs_held
    top_e = jnp.where(first, held, absent).astype(jnp.int32).reshape(TOKENS, TOP_K)
    return jax.random.uniform(kw, (TOKENS, TOP_K), jnp.float32, 0.05, 0.3), top_e


@jax.custom_vjp
def dispatch_pr32(x, order, inverse):
    """PR 32's dispatch: ``x[order // k]``, ``T * k`` rows; its backward
    gathers ``[T, k, E]`` by the inverse permutation and sums over ``k``."""
    return x[order // inverse.shape[1]]


dispatch_pr32.defvjp(lambda x, order, inverse: (dispatch_pr32(x, order, inverse), inverse),
                     lambda inverse, g: (g[inverse].sum(axis=1), None, None))


@jax.custom_vjp
def combine_pr32(ys, order, inverse):
    """PR 32's combine: ``dispatch_pr32`` transposed."""
    return ys[inverse].sum(axis=1)


combine_pr32.defvjp(lambda ys, order, inverse: (combine_pr32(ys, order, inverse),
                                                (order, inverse.shape[1])),
                    lambda res, g: (g[res[0] // res[1]], None, None))


def routed_as_in_pr32(w, x, top_w, top_e):
    """PR 32's routed part: every buffer ``T * k`` rows, the weights after
    the last product, the backward pass what JAX derives."""
    t, k = top_e.shape
    local = top_e.reshape(t * k)
    held = local < G
    group = jnp.where(held, local, G)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.argsort(order).reshape(t, k)
    sizes = jnp.sum(jax.nn.one_hot(group, G + 1, dtype=jnp.int32), axis=0)[:G]
    xs = dispatch_pr32(x, order, inverse)
    hidden = (jax.nn.silu(grouped_matmul(xs, w["gate"], sizes))
              * grouped_matmul(xs, w["up"], sizes))
    ys = grouped_matmul(hidden, w["down"], sizes)
    weight = jnp.where(held, top_w.reshape(t * k), 0.0)[order]
    return combine_pr32(ys * weight[:, None].astype(ys.dtype), order, inverse)


def routed_parts(out):
    keys = [jax.random.PRNGKey(i) for i in range(5)]
    x = jax.random.normal(keys[0], (TOKENS, K), jnp.bfloat16)
    w = {"gate": jax.random.normal(keys[1], (G, K, N), jnp.bfloat16) * 0.02,
         "up": jax.random.normal(keys[2], (G, K, N), jnp.bfloat16) * 0.02,
         "down": jax.random.normal(keys[3], (G, N, K), jnp.bfloat16) * 0.02}
    layer = moe.MoELayer(N, n_routed=ROUTED, top_k=TOP_K, experts_held=G, name="l")
    forms = {"pr32": routed_as_in_pr32, "held_pairs": lambda *a: layer.routed(*a)[0]}

    def train(form):
        def loss(w, x, top_w, top_e):
            y = jax.checkpoint(form)(w, x, top_w, top_e)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    steps = {name: train(form) for name, form in forms.items()}
    for pairs in HELD_PAIRS:
        top_w, top_e = routing(pairs, keys[4])
        row = {name: median_seconds(step, w, x, top_w, top_e) * 1e3
               for name, step in steps.items()}
        a, b = (forms[name](w, x, top_w, top_e).astype(jnp.float32) for name in forms)
        row["max_abs_diff"] = float(jnp.max(jnp.abs(a - b)))
        row["max_abs"] = float(jnp.max(jnp.abs(a)))
        out[f"routed_{pairs}_ms"] = row
        print("routed", pairs, row, flush=True)


def flash_kernels(out):
    b, s, d, dv, scale = 4, 4096, 192, 128, 0.1147
    bq, bkv = LAYER_TILES
    fits = attention._fused_backward_fits
    forms = {"fused": lambda *a: True, "two_kernels": lambda *a: False}
    for h in FLASH_HEADS:
        keys = jax.random.split(jax.random.PRNGKey(h), 4)
        q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, h, s, dv), jnp.bfloat16)
        g = jax.random.normal(keys[3], (b, h, s, dv), jnp.bfloat16)
        half = b * h * s * (s + 1) / 2
        o, lse = jax.jit(functools.partial(
            attention._flash_forward, causal=True, block_q=bq, block_kv=bkv, scale=scale,
            interpret=False))(q, k, v)
        tf = median_seconds(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=scale, block_q=bq, block_kv=bkv)), q, k, v)
        row = {"fwd_ms": tf * 1e3, "fwd_tflops": 2 * half * (d + dv) / tf / 1e12}
        grads = {}
        for form, chooses in forms.items():
            attention._fused_backward_fits = chooses
            for tq, tkv in FLASH_TILES:
                backward = jax.jit(functools.partial(
                    attention._flash_backward, causal=True, block_q=tq, block_kv=tkv,
                    scale=scale, interpret=False))
                try:
                    tb = median_seconds(backward, q, k, v, o, lse, g)
                    row[f"bwd_{form}_{tq}_{tkv}"] = {
                        "ms": tb * 1e3, "tflops": 2 * half * (3 * d + 2 * dv) / tb / 1e12}
                    if (tq, tkv) == LAYER_TILES:
                        grads[form] = backward(q, k, v, o, lse, g)
                except Exception as e:       # a tile shape the chip's compiler refuses
                    row[f"bwd_{form}_{tq}_{tkv}"] = {"error": repr(e)[:300]}
                print("flash", h, form, tq, tkv, row[f"bwd_{form}_{tq}_{tkv}"], flush=True)
        attention._fused_backward_fits = fits
        # the two forms on the same inputs: products in the same order, so
        # they differ by the rounding of their bfloat16 casts at most
        row["max_abs_diff_dq_dk_dv"] = [
            float(jnp.max(jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32))))
            for a, c in zip(grads["fused"], grads["two_kernels"])]
        tb = median_seconds(jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, scale=scale, block_q=bq, block_kv=bkv
            ).astype(jnp.float32) ** 2), argnums=(0, 1, 2))), q, k, v)
        row["fwd_bwd_ms"] = tb * 1e3
        out[f"flash_{b}x{h}"] = row
        print("flash", h, {n: r for n, r in row.items() if not n.startswith("bwd_")}, flush=True)


KDA_SIZE = (4, 32, 4096, 128)
KDA_CHUNKS = (64, 32, 128)


def kda_rule(out):
    b, h, s, d = KDA_SIZE
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda a: (a / jnp.linalg.norm(a, axis=-1, keepdims=True)).astype(jnp.bfloat16)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, h, s, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, h, s, d)))
    v = jax.random.normal(keys[2], (b, h, s, d), jnp.bfloat16)
    # a step in [0.001, 0.1] times a rate in [1, 16], as the layer starts
    g = -(jnp.exp(jax.random.uniform(keys[3], (b, h, s, d), minval=jnp.log(1e-3),
                                     maxval=jnp.log(1e-1)))
          * jax.random.uniform(keys[4], (1, h, 1, 1), minval=1.0, maxval=16.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, h, s)))
    takes = delta_rule.takes_kernel
    forms = {"kernels": takes, "xla": lambda *a: False}     # the second: XLA's products on the TPU too
    for form, chooses in forms.items():
        delta_rule.takes_kernel = chooses
        for chunk in KDA_CHUNKS:
            def rule(q, k, v, g, beta, chunk=chunk):
                one = jax.checkpoint(
                    lambda a: delta_rule.chunked_gated_delta_rule(*a, chunk=chunk))
                return jax.lax.map(one, (q, k, v, g, beta))
            try:
                tf = median_seconds(jax.jit(rule), q, k, v, g, beta)
                tb = median_seconds(jax.jit(jax.grad(
                    lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2, 3, 4))), q, k, v, g, beta)
                row = {"fwd_ms": tf * 1e3, "fwd_bwd_ms": tb * 1e3}
            except Exception as e:       # a shape the chip's compiler or memory refuses
                row = {"error": repr(e)[:300]}
            out[f"kda_{form}_chunk{chunk}"] = row
            print("kda", form, chunk, row, flush=True)
        out[f"kda_{form}_one_sequence_fwd_ms"] = 1e3 * median_seconds(
            jax.jit(lambda *a: delta_rule.chunked_gated_delta_rule(*a)),
            q[0], k[0], v[0], g[0], beta[0])
    delta_rule.takes_kernel = takes
    # the inside of one sequence's 2,048 chunks of 64, alone: the two kernels, and XLA's products
    chunked = lambda a, w: a[0].reshape(h, s // 64, 64, w)  # noqa: E731
    k1, q1, v1, g1, b1 = (chunked(k, d), chunked(q, d), chunked(v, d), chunked(g, d),
                          beta[0].reshape(h, s // 64, 64))

    def with_backward(inside):
        def both(*a):
            results, pull = jax.vjp(inside, *a)
            return pull(results)
        return jax.jit(both)
    for form, inside, args in (
            ("kernels", lambda *a: delta_rule._inside_kernels(*a, 64, False),
             (q[0], k[0], v[0], g[0], beta[0][:, None, :])),
            ("xla", lambda *a: delta_rule._inside(*a, jnp.bfloat16), (q1, k1, v1, g1, b1))):
        out[f"kda_inside_{form}_one_sequence"] = {
            "fwd_ms": 1e3 * median_seconds(jax.jit(inside), *args),
            "fwd_bwd_ms": 1e3 * median_seconds(with_backward(inside), *args)}
        print("kda inside", form, out[f"kda_inside_{form}_one_sequence"], flush=True)

    def products(k1, q1, g1):
        return delta_rule.decayed_products(jnp.stack([k1, q1], axis=-3), k1,
                                           jnp.cumsum(g1, axis=-2), jnp.bfloat16)
    both = jax.jit(products)(k1, q1, g1)
    lower = both[..., 0, :, :] * jnp.tril(jnp.ones((64, 64)), -1)
    parts = {"decayed_products_ms": median_seconds(jax.jit(products), k1, q1, g1),
             "unit_lower_inverse_ms": median_seconds(
                 jax.jit(delta_rule.unit_lower_inverse), lower),
             "by_token_one_sequence_fwd_ms": median_seconds(
                 jax.jit(delta_rule.gated_delta_rule_by_token),
                 q[0], k[0], v[0], g[0], beta[0], n=3)}
    out["kda_xla_parts_one_sequence"] = {n: t * 1e3 for n, t in parts.items()}
    print("kda parts", out["kda_xla_parts_one_sequence"], flush=True)


SCAN_GROUPS = (8, 16, 32)


def kda_scan(out):
    _, h, s, d = KDA_SIZE
    keys = jax.random.split(jax.random.PRNGKey(1), 6)

    def unit(a):
        return (a / jnp.linalg.norm(a, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    q = unit(jax.random.normal(keys[0], (h, s, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (h, s, d)))
    v = jax.random.normal(keys[2], (h, s, d), jnp.bfloat16)
    g = -(jnp.exp(jax.random.uniform(keys[3], (h, s, d), minval=jnp.log(1e-3),
                                     maxval=jnp.log(1e-1)))
          * jax.random.uniform(keys[4], (h, 1, 1), minval=1.0, maxval=16.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (h, s)))[:, None, :]
    six = jax.jit(lambda *a: delta_rule._inside_kernels(*a, 64, False))(q, k, v, g, beta)
    n = s // 64

    def by_xla(*six):
        along = (*six[:5], jnp.moveaxis(six[5], 1, 0))
        o = jnp.moveaxis(delta_rule._scan(*along, jnp.bfloat16), 0, 1)
        return o.reshape(h, s, d).astype(jnp.bfloat16)

    def by_kernels(*six):
        return delta_rule._scan_kernels(*six, jnp.bfloat16, False)

    def with_backward(f):
        def both(*a):
            o, pull = jax.vjp(f, *a)
            return pull(o)
        return jax.jit(both)
    group = delta_rule.HEAD_GROUP
    forms = [("xla", by_xla, group)] + [(f"kernels_{g}", by_kernels, g) for g in SCAN_GROUPS]
    reference = jax.jit(by_xla)(*six).astype(jnp.float32)
    for form, f, g in forms:
        delta_rule.HEAD_GROUP = g
        fwd = median_seconds(jax.jit(f), *six)
        both = median_seconds(with_backward(f), *six)
        got = jax.jit(f)(*six).astype(jnp.float32)
        row = {"fwd_ms": fwd * 1e3, "fwd_bwd_ms": both * 1e3,
               "layer_step_ms": 4e3 * (fwd + both), "chunks": n,
               "o_relative_to_xla": float(jnp.linalg.norm(got - reference)
                                          / jnp.linalg.norm(reference))}
        out[f"kda_scan_{form}_one_sequence"] = row
        print("kda scan", form, row, flush=True)
    delta_rule.HEAD_GROUP = group


SECTIONS = {"grouped": grouped_products, "routed": routed_parts, "flash": flash_kernels,
            "kda": kda_rule, "scan": kda_scan}


def main():
    out = {"device": jax.devices()[0].device_kind}
    os.makedirs("chiprun_out", exist_ok=True)
    for name in sys.argv[1:] or SECTIONS:
        SECTIONS[name](out)
        with open("chiprun_out/bench_lm_kernels.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
