"""Continuous-batching decode driver: the generative-serving smoke.

The one-shot servers (``serve_snapshot.py``, ``serve_router.py``) answer
each request with a single dispatch; this driver serves the *iterative*
workload — greedy autoregressive decode over a tiny ``MHADecoder`` —
through the ISSUE-20 stack: a ``DecodeEngine`` whose paged decode step is
pre-compiled per (batch-bucket, page-bucket) so admission never compiles,
a ``KVPagePool`` recycling fixed KV pages through a free list, and a
``ContinuousBatcher`` admitting sequences into free slots at step
boundaries instead of draining the batch.

The headline it prints — and asserts — is the determinism contract:
every sequence's continuously-batched output is **bit-identical** to the
same sequence decoded alone (``decode_reference``, batch of one, same
compiled sessions), no matter what neighbours shared its steps. Then the
occupancy/throughput story: mean slot occupancy and generated tokens/s
for continuous vs sequential batch-of-one on the same length mix, plus
the ``DecodeMetrics`` Prometheus exposition tail.

Untrained weights are fine here: greedy argmax over a deterministic
model is exactly as bit-stable as a trained one, and the vocabulary is
tiny on purpose — this is a serving-plane demo, not a language model.

Usage:
    python examples/serve_decode.py

Env knobs: ``DECODE_SLOTS`` (default 4), ``DECODE_SEQS`` (default 12),
``DECODE_MAX_NEW`` (default 10).
"""

from __future__ import annotations

import os
import time

from common import setup

import numpy as np

import dcnn_tpu  # noqa: F401  (platform override side effects)


def main():
    setup("serve_decode")
    import jax

    from dcnn_tpu.models import MHADecoder
    from dcnn_tpu.serve import (ContinuousBatcher, DecodeEngine,
                                decode_reference)

    max_slots = int(os.environ.get("DECODE_SLOTS", "4"))
    n_seqs = int(os.environ.get("DECODE_SEQS", "12"))
    max_new = int(os.environ.get("DECODE_MAX_NEW", "10"))

    model = MHADecoder(vocab_size=32, embed_dim=32, num_heads=2,
                       num_layers=2, max_seq_len=64)
    params = model.init(jax.random.PRNGKey(0))
    print(f"model: {model}")

    t0 = time.perf_counter()
    engine = DecodeEngine(model, params, max_slots=max_slots, page_size=8,
                          max_pages_per_seq=4, name="example")
    print(f"engine: {engine}")
    print(f"  {len(engine.compile_stats)} (batch, pages) sessions "
          f"compiled in {time.perf_counter() - t0:.2f}s — admission "
          f"never compiles again")

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, model.vocab_size,
                            size=int(rng.integers(2, 10))).tolist()
               for _ in range(n_seqs)]

    # sequential batch-of-one baseline through the SAME sessions
    t0 = time.perf_counter()
    reference = [decode_reference(engine, p, max_new_tokens=max_new)
                 for p in prompts]
    naive_wall = time.perf_counter() - t0

    # continuous batching: all sequences submitted up front, the
    # scheduler interleaves them through the slots
    with ContinuousBatcher(engine, queue_capacity=n_seqs) as batcher:
        t0 = time.perf_counter()
        futs = [batcher.submit(p, max_new_tokens=max_new) for p in prompts]
        results = [f.result(timeout=30) for f in futs]
        cont_wall = time.perf_counter() - t0
        snap = batcher.metrics.snapshot()
        prom = batcher.metrics.prometheus()

    for i, (got, want) in enumerate(zip(results, reference)):
        assert np.array_equal(got, want), (
            f"sequence {i}: continuous {got} != batch-of-one {want}")
    print(f"\nbit-identity: {n_seqs}/{n_seqs} sequences identical to "
          f"batch-of-one decode  [OK]")

    tokens = sum(len(r) for r in results)
    print(f"\n{'':>24}  {'continuous':>12}  {'batch-of-one':>12}")
    print(f"{'wall (s)':>24}  {cont_wall:>12.3f}  {naive_wall:>12.3f}")
    print(f"{'tokens/s':>24}  {tokens / cont_wall:>12.1f}  "
          f"{tokens / naive_wall:>12.1f}")
    print(f"{'slot occupancy':>24}  {snap['slot_occupancy']:>12.3f}  "
          f"{1 / max_slots:>12.3f}")
    print(f"\nsteps={snap['steps']} admissions={snap['admissions']} "
          f"evictions={snap['evictions']} "
          f"pages_in_use={snap['pages_in_use']}")
    print("\n/metrics tail (decode_* series):")
    for line in prom.splitlines():
        if line.startswith("decode_") and "_bucket" not in line:
            print(f"  {line}")


if __name__ == "__main__":
    main()
