"""The device fence for wall-clock measurement.

JAX dispatch is asynchronous: a timing that does not wait for the result
measures the enqueue. ``hard_fence`` is the one name the timing code in this
repo waits through (``bench.py``, ``benchmarks/``, ``train/profiling.py``, the
transfer engine, the pipeline load tracker).

It is ``jax.block_until_ready``. An earlier version read one element of every
leaf back to the host through a jitted probe, on the premise that
``block_until_ready`` could return early. On the installation there is (TPU
v5e, jax 0.9.0, libtpu 0.0.34) the two were timed against each other (PR 21's
chip run, CHANGES.md): the same five ResNet-18 train steps, 12 alternating
repetitions, medians 380.65 ms vs 380.34 ms — a difference of 0.08%, inside
the quartile spread of either — and a chained 20 x 8192^3 bf16 matmul at 190
TFLOP/s under both (below the chip's 197 peak, so neither returns early). The
probe bought nothing and cost a dispatch, so it went.

Reference equivalent: the reference times kernels around explicit
``cudaDeviceSynchronize`` (e.g. ``benchmarks/gemm_benchmark.cpp``).
"""

from __future__ import annotations

import jax


def hard_fence(tree) -> None:
    """Block until every array leaf in ``tree`` has finished computing."""
    jax.block_until_ready(tree)
