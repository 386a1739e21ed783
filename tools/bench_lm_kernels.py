"""The two kernel choices of the language-model path, measured alone on the
chip at the cell's shapes (``chiprun -- python3 tools/bench_lm_kernels.py``,
about two minutes; the result goes to ``chiprun_out/bench_lm_kernels.json``):

- the grouped product of an expert layer (98,304 pair rows, 8 held experts,
  2048 -> 1408 -> 2048; gate, up and down, forward alone and with the
  backward): ``jax.lax.ragged_dot`` against the Pallas grouped product that
  ``ops/grouped.py`` calls (``jax.experimental.pallas.ops.tpu.megablox``),
  at two tilings, over group sizes that are uneven as Zipf ids route them,
  even, and with every pair held;
- the flash kernels at latent attention's shapes (4 x 16 heads x 4096,
  scores at 192, values at 128), forward and with the backward, at five
  tile shapes.

The numbers in ``ops/grouped.py``, ``nn/latent_attention.py`` and
``CHANGES.md`` (PR 32) are this script's.
"""

import faulthandler
import json
import os
import sys
import time

faulthandler.dump_traceback_later(600, exit=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox  # noqa: E402

from dcnn_tpu.ops.attention import flash_attention  # noqa: E402

M, K, N, G = 98304, 2048, 1408, 8
GROUPS = {"zipf_like": [4200, 2600, 1900, 1300, 900, 700, 400, 288],
          "even": [1536] * 8, "all_held": [12288] * 8}
TILINGS = ((512, 1024, 1024), (512, 512, 1024))
FLASH_TILES = ((1024, 512), (512, 512), (1024, 1024), (512, 1024), (256, 512))


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


def flash_kernels(out):
    b, h, s, d, dv = 4, 16, 4096, 192, 128
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(3), (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(4), (b, h, s, dv), jnp.bfloat16)
    half = b * h * s * (s + 1) / 2
    for bq, bkv in FLASH_TILES:
        def attend(q, k, v, bq=bq, bkv=bkv):
            return flash_attention(q, k, v, causal=True, scale=0.1147, block_q=bq, block_kv=bkv)
        try:
            tf = median_seconds(jax.jit(attend), q, k, v)
            tb = median_seconds(jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))), q, k, v)
            row = {"fwd_ms": tf * 1e3, "fwd_tflops": 2 * half * (d + dv) / tf / 1e12,
                   "fwd_bwd_ms": tb * 1e3,
                   "fwd_bwd_tflops": 2 * half * ((d + dv) + (3 * d + 2 * dv)) / tb / 1e12}
        except Exception as e:       # a tile shape the chip's compiler refuses
            row = {"error": repr(e)[:300]}
        out[f"flash_{bq}_{bkv}"] = row
        print("flash", bq, bkv, row, flush=True)


def main():
    out = {"device": jax.devices()[0].device_kind}
    grouped_products(out)
    flash_kernels(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_lm_kernels.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
