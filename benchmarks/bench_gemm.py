"""GEMM microbenchmark with correctness gate.

Reference equivalent: ``/root/reference/benchmarks/gemm_benchmark.cpp:16-50``
(AVX2-blocked SGEMM vs MKL cblas_sgemm, gated by ``check_match``). Here the
"kernel under test" is the MXU via ``jnp.matmul`` at each precision policy
(parity = fp32-equivalent multi-pass, fast/bf16 = native bf16 passes), gated
against fp64 numpy.
"""

from __future__ import annotations

import functools
import sys

import numpy as np

from common import (Result, check_match, print_table, replace_feed, report,
                    time_chained, tiny_mode)

SIZES = [(512, 512, 512), (1024, 1024, 1024), (2048, 2048, 2048),
         (4096, 4096, 4096)]
TOLS = {"parity": 2e-5, "fast": 2e-2}


def run() -> dict:
    import jax
    import jax.numpy as jnp

    from dcnn_tpu.core.precision import get_precision, set_precision

    sizes = SIZES[:2] if tiny_mode() else SIZES
    results = []
    rng = np.random.default_rng(0)
    for mode in ("parity", "fast"):
        set_precision(mode)

        @functools.partial(jax.jit, static_argnums=())
        def mm(a, b):
            return jnp.matmul(a, b, precision=get_precision())

        for m, n, k in sizes:
            a = rng.standard_normal((m, k), np.float32)
            b = rng.standard_normal((k, n), np.float32)
            da, db = jax.device_put(a), jax.device_put(b)
            got = mm(da, db)
            ok, err = check_match(got, a.astype(np.float64) @ b, TOLS[mode])
            # iteration count scaled inversely with FLOPs so the timed delta
            # stays well above dispatch jitter even for sub-ms matmuls
            length = (8 if tiny_mode()
                      else max(32, min(2048, int(32 * (4096 / m) ** 2))))
            # square matmul: the output IS the next iteration's lhs — full
            # consumption, zero dependency overhead
            dt, _ = time_chained(mm, (da, db), replace_feed(0),
                                 length=length)
            gflops = 2.0 * m * n * k / dt / 1e9
            results.append(Result(
                name=f"gemm_{m}x{n}x{k}_{mode}", seconds=dt, rate=gflops,
                unit="GFLOP/s", correct=ok, max_err=err))
    set_precision("parity")
    return report("gemm", results)


if __name__ == "__main__":
    doc = run()
    print_table(doc)
    sys.exit(0 if doc["all_correct"] else 1)
