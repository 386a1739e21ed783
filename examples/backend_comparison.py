"""Accelerator-vs-host op comparison (reference
``examples/cuda_vs_avx2_comparison.cpp:332`` — CUDA kernels vs AVX2 kernels
on the same workloads). Here: the default backend (TPU) vs the host CPU
devices, same jitted ops, correctness-gated against each other.

Usage: JAX_PLATFORMS=cpu python examples/backend_comparison.py    # host-only
       python examples/backend_comparison.py                     # TPU vs CPU
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np

from common import time_callable   # benchmarks/common.py timing harness
from dcnn_tpu.ops import conv as conv_ops


def main():
    rng = np.random.default_rng(0)
    default_dev = jax.devices()[0]
    cpu_dev = jax.devices("cpu")[0]
    devices = {str(default_dev.platform): default_dev}
    if cpu_dev.platform != default_dev.platform:
        devices["cpu"] = cpu_dev

    m = int(os.environ.get("SIZE", "1024"))
    a = rng.standard_normal((m, m), np.float32)
    b = rng.standard_normal((m, m), np.float32)
    x = rng.standard_normal((8, 64, 32, 32), np.float32)
    w = (rng.standard_normal((64, 64, 3, 3), np.float32) / 24.0)

    cases = {
        f"matmul_{m}x{m}": (lambda aa, bb: jnp.matmul(aa, bb), (a, b),
                            2.0 * m ** 3),
        "conv_64x32x32": (lambda xx, ww: conv_ops.conv2d(
            xx, ww, stride=1, padding=1), (x, w),
            2.0 * 8 * 64 * 64 * 9 * 32 * 32),
    }

    print(f"{'case':<18} " + "".join(f"{n:>14}" for n in devices)
          + "   agreement")
    for cname, (fn, args, flops) in cases.items():
        outs, cols = {}, []
        for dname, dev in devices.items():
            dargs = tuple(jax.device_put(v, dev) for v in args)
            jfn = jax.jit(fn, device=dev)
            outs[dname] = np.asarray(jfn(*dargs))
            dt = time_callable(lambda: jfn(*dargs), steps=5)
            cols.append(f"{flops / dt / 1e9:>11.1f} GF")
        vals = list(outs.values())
        err = (np.max(np.abs(vals[0] - vals[-1]))
               / max(1.0, np.max(np.abs(vals[-1]))))
        print(f"{cname:<18} " + "".join(f"{c:>14}" for c in cols)
              + f"   max rel err {err:.2e}")


if __name__ == "__main__":
    main()
