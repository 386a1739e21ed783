"""Bucketed inference engine: the compiled half of the serving stack.

The reference's deployment story ends at binary weight files readable only
by its own C++ runtime (``sequential.hpp:832-915``); our export chain
(fold → int8 → StableHLO) already ships a portable *program*. This module
turns either source — a checkpoint dir or an exported artifact — into an
**online-servable** unit: one ahead-of-time compiled session per batch
bucket (powers of two up to ``max_batch``), pre-warmed so the first real
request never pays a compile, with zero-pad-to-bucket dispatch.

Why buckets instead of one batch-polymorphic callable: XLA compiles per
concrete shape anyway, so an unconstrained batcher would accumulate one
executable per distinct arrival count (and pay a fresh compile — seconds —
mid-traffic for each new one). Power-of-two buckets cap the executable
count at ``log2(max_batch)+1`` and bound padding waste at <2x, the same
trade TensorFlow-Serving's batching scheduler makes with
``allowed_batch_sizes``.

Numerics contract (asserted in ``tests/test_serve.py``):

- padding is row-exact *within* a session — zero rows ride along and are
  sliced off; the real rows' logits are bit-identical to the same batch
  unpadded at the same bucket;
- **int8 engines are bit-identical across buckets too**
  (``batch_invariant=True``): every cross-row-shape reduction in the
  quantized graph is an exact int8×int8→int32 integer accumulation, which
  is reduction-order-free, so a request's logits don't depend on which
  bucket served it. Float graphs are only allclose across buckets — XLA
  retiles fp32 conv/GEMM reductions per shape — which is exactly why the
  int8 graph is the serving graph of record. **Except on the TPU with bf16
  glue** (``DCNN_PRECISION=bf16``): there the engine does not make the
  promise. Measured on the v5e (PR 21's chip run, CHANGES.md): the int8
  ResNet-18's batch-1 bucket differs from buckets 8 and 32 (which agree)
  from the first residual block on — max logit difference 4.9e-4 at logits
  of 0.07 — with or without ``--xla_allow_excess_precision=false``, while
  with fp32 glue (parity mode) all buckets agree bit for bit.

Sessions are compiled with buffer donation on accelerator backends: the
padded input batch is a fresh per-dispatch buffer the caller never reuses,
so donating it lets XLA overwrite it in place instead of allocating output
alongside input (CPU ignores donation, so it is skipped there to keep logs
clean).
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.precision import get_compute_dtype
from ..obs import get_registry, get_tracer
from ..obs.xla import (executable_cost, install_compile_listener,
                       record_compile, sample_hbm)


def serve_buckets(max_batch: int) -> List[int]:
    """Batch buckets: powers of two up to ``max_batch``, with ``max_batch``
    itself always the last bucket (so a non-power-of-two cap costs one
    extra session instead of silently over-padding): 32 → [1,2,4,8,16,32],
    6 → [1,2,4,6]."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


class InferenceEngine:
    """Pre-compiled, bucketed, warm inference sessions over one model.

    ``apply_fn(x) -> logits`` is the already-transformed eval-mode forward
    (weights closed over); use the classmethods to build one from a
    checkpoint dir, a live model, or a StableHLO artifact — they apply the
    deployment transforms (fold / int8) and set ``batch_invariant``
    accordingly.
    """

    def __init__(self, apply_fn: Callable, input_shape: Sequence[int], *,
                 max_batch: int = 32, input_dtype: Any = jnp.float32,
                 donate: Optional[bool] = None, warmup: bool = True,
                 batch_invariant: bool = False, name: str = "engine",
                 version: Optional[Any] = None, registry=None):
        self.name = name
        # model-version identity (the CheckpointManager step for engines
        # built by serve/swap.py's EngineFactory; None for ad-hoc engines).
        # The router tier and ModelVersionManager route/report on it.
        self.version = version
        # cost/HBM gauges land here (default: the process-global registry);
        # a batcher's start_telemetry additionally mirrors them onto its
        # own scrape registry so a private-registry replica still exposes
        # them on /metrics
        self.registry = registry if registry is not None else get_registry()
        install_compile_listener()
        self.input_shape = tuple(int(d) for d in input_shape)
        self.input_dtype = jnp.dtype(input_dtype)
        self.bucket_sizes = serve_buckets(max_batch)
        self.max_batch = self.bucket_sizes[-1]
        self.batch_invariant = bool(batch_invariant)
        if donate is None:
            # donation is a no-op (plus a warning per compile) on CPU
            donate = jax.default_backend() in ("tpu", "gpu")
        jitted = jax.jit(apply_fn, donate_argnums=(0,) if donate else ())
        self._sessions: Dict[int, Any] = {}
        self.compile_stats: Dict[int, Dict[str, float]] = {}
        tracer = get_tracer()
        for b in self.bucket_sizes:
            spec = jax.ShapeDtypeStruct((b, *self.input_shape),
                                        self.input_dtype)
            t0 = time.perf_counter()
            with tracer.span("serve.compile", track="serve",
                             engine=name, bucket=b):
                session = jitted.lower(spec).compile()
            compile_s = time.perf_counter() - t0
            record_compile(compile_s, what="serve", registry=self.registry)
            t0 = time.perf_counter()
            if warmup:
                with tracer.span("serve.warmup", track="serve",
                                 engine=name, bucket=b):
                    jax.block_until_ready(session(jnp.zeros(
                        (b, *self.input_shape), self.input_dtype)))
            self._sessions[b] = session
            self.compile_stats[b] = {
                "compile_s": round(compile_s, 4),
                "warmup_s": round(time.perf_counter() - t0, 4)}
            # XLA's own accounting for this bucket's executable (obs/xla):
            # FLOPs + bytes-accessed feed the serve roofline and the
            # analytic per-sample cost the bench/router read
            cost = executable_cost(session)
            if cost is not None:
                self.compile_stats[b].update(
                    {k: cost[k] for k in ("flops", "bytes_accessed",
                                          "bytes_per_flop") if k in cost})
        self._export_cost_gauges(self.registry)
        # post-compile HBM watermark: engine startup is the serve-side
        # allocation spike (every bucket's weights + workspace); no-op on
        # backends without memory stats
        sample_hbm(self.registry)

    def _export_cost_gauges(self, registry) -> None:
        """Set the per-sample XLA cost gauges on ``registry`` (engine
        startup does it for :attr:`registry`; ``start_telemetry`` repeats
        it for the batcher's scrape registry)."""
        top = self.compile_stats.get(self.max_batch, {})
        if top.get("flops"):
            registry.gauge(
                "serve_flops_per_sample",
                "XLA cost-analysis FLOPs per sample at the largest "
                "serve bucket").set(top["flops"] / self.max_batch)
            if top.get("bytes_per_flop") is not None:
                registry.gauge(
                    "serve_bytes_per_flop",
                    "roofline byte/FLOP ratio of the largest serve "
                    "bucket executable").set(top["bytes_per_flop"])

    # -- constructors --
    @classmethod
    def from_model(cls, model, params, state, *, fold: bool = True,
                   int8_calib: Optional[Any] = None,
                   act_quantile: Optional[float] = None, **kw
                   ) -> "InferenceEngine":
        """Engine over a live :class:`~dcnn_tpu.nn.Sequential`.

        ``fold=True`` runs :func:`~dcnn_tpu.nn.fold.fold_batchnorm`;
        passing a calibration batch as ``int8_calib`` additionally runs
        :func:`~dcnn_tpu.nn.quantize.quantize_model` (which folds first) —
        the int8 engine gets the cross-bucket ``batch_invariant``
        guarantee where it holds (module docstring)."""
        from ..nn import fold_batchnorm, quantize_model

        if model.input_shape is None:
            raise ValueError("model has no input_shape; build it through "
                             "SequentialBuilder.input or set input_shape")
        invariant = False
        if int8_calib is not None:
            model, params, state = quantize_model(
                model, params, state, int8_calib, fold_bn=fold,
                act_quantile=act_quantile)
            # module docstring: the float glue between the integer layers
            # breaks bit-identity at bucket 1 on the TPU when it is bf16
            invariant = not (jax.default_backend() == "tpu"
                             and get_compute_dtype() == jnp.bfloat16)
        elif fold:
            model, params, state = fold_batchnorm(model, params, state)

        def apply_fn(x):
            return model.apply(params, state, x, training=False)[0]

        kw.setdefault("name", model.name)
        return cls(apply_fn, model.input_shape,
                   batch_invariant=invariant, **kw)

    @classmethod
    def from_checkpoint(cls, path: str, *, seed: int = 0, **kw
                        ) -> "InferenceEngine":
        """Engine from a ``save_checkpoint`` dir (the committed
        ``model_snapshots/mnist_cnn_model`` layout). Transform kwargs as in
        :meth:`from_model`."""
        from ..train.checkpoint import load_checkpoint

        model, params, state, _, _, _ = load_checkpoint(path, seed=seed)
        return cls.from_model(model, params, state, **kw)

    @classmethod
    def from_artifact(cls, blob_or_path, **kw) -> "InferenceEngine":
        """Engine from a serialized StableHLO artifact
        (:func:`~dcnn_tpu.nn.export.export_inference` bytes or a file
        path). Needs a batch-polymorphic artifact — a pinned-batch export
        can only ever run its one shape, which defeats bucketing."""
        from jax import export as jax_export

        if isinstance(blob_or_path, (str, os.PathLike)):
            with open(blob_or_path, "rb") as f:
                blob = f.read()
        else:
            blob = bytes(blob_or_path)
        exported = jax_export.deserialize(blob)
        aval = exported.in_avals[0]
        lead = aval.shape[0]
        if isinstance(lead, int):
            raise ValueError(
                f"artifact has a pinned batch dimension ({lead}); serve "
                "needs a batch-polymorphic export (export_inference with "
                "batch_size=None, the default)")
        kw.setdefault("name", "artifact")
        return cls(exported.call, tuple(int(d) for d in aval.shape[1:]),
                   input_dtype=aval.dtype, **kw)

    # -- bucket math --
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n."""
        if not 1 <= n <= self.max_batch:
            raise ValueError(f"batch of {n} outside [1, {self.max_batch}]")
        for b in self.bucket_sizes:
            if b >= n:
                return b
        raise AssertionError("unreachable: last bucket is max_batch")

    def pad_to_bucket(self, x: np.ndarray) -> Tuple[jnp.ndarray, int]:
        """Zero-pad ``(n, *input_shape)`` rows up to the nearest bucket.
        Returns ``(padded, n)``. The result is always a FRESH device
        buffer (host round-trip if ``x`` was a device array), so handing
        it to :meth:`run_padded` can never donate a buffer the caller
        still holds."""
        x = np.asarray(x, dtype=self.input_dtype)
        n = x.shape[0]
        b = self.bucket_for(n)
        if b > n:
            pad = np.zeros((b - n, *self.input_shape),
                           dtype=self.input_dtype)
            x = np.concatenate([x, pad])
        return jnp.asarray(x), n

    def run_padded(self, x) -> jnp.ndarray:
        """Run one pre-compiled session; ``x.shape[0]`` must be a bucket.

        On accelerator backends the session donates its input: a device
        array passed here is CONSUMED (standard ``jax.jit`` donation
        semantics) — prepare per-dispatch buffers with
        :meth:`pad_to_bucket`, which never aliases caller memory."""
        b = x.shape[0]
        session = self._sessions.get(b)
        if session is None:
            raise ValueError(f"no session for batch {b}; buckets are "
                             f"{self.bucket_sizes}")
        return session(jnp.asarray(x, dtype=self.input_dtype))

    # -- synchronous convenience path (the batcher uses the pieces above) --
    def infer(self, x) -> jnp.ndarray:
        """Run ``x`` — one sample ``input_shape`` or a batch
        ``(n, *input_shape)`` of any size — through the bucketed sessions;
        batches beyond ``max_batch`` are chunked. Returns logits with the
        same leading-dim convention as the input."""
        x = np.asarray(x)
        single = x.shape == self.input_shape
        if single:
            x = x[None]
        if x.shape[1:] != self.input_shape:
            raise ValueError(f"expected trailing dims {self.input_shape}, "
                             f"got array of shape {x.shape}")
        outs = []
        for lo in range(0, x.shape[0], self.max_batch):
            chunk = x[lo:lo + self.max_batch]
            padded, n = self.pad_to_bucket(chunk)
            outs.append(self.run_padded(padded)[:n])
        y = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return y[0] if single else y

    def __repr__(self) -> str:
        ver = f", version={self.version!r}" if self.version is not None else ""
        return (f"InferenceEngine({self.name!r}, input={self.input_shape}, "
                f"buckets={self.bucket_sizes}, "
                f"batch_invariant={self.batch_invariant}{ver})")
