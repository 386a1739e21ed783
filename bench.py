"""North-star benchmark: ResNet-18 Tiny-ImageNet training throughput.

Prints ONE JSON line:
  {"metric": "resnet18_tiny_imagenet_train_images_per_sec", "value": N,
   "unit": "images/sec/chip", "vs_baseline": R, ...}

``vs_baseline`` divides by a **measured** PyTorch figure from
``BASELINE_MEASURED.json`` (produced by ``torch_baselines/measure_baseline.py``
— same model/optimizer/loss on synthetic tensors). A ``torch_cuda`` entry is
preferred; otherwise ``torch_cpu`` (measured on this host) is used and
``baseline`` in the output says which. The reference itself publishes no
numbers (BASELINE.md).

Extra reported fields: achieved model TFLOP/s and MFU (from the model's own
analytic FLOP count — forward_complexity x3 for fwd+bwd, the standard
training-FLOPs convention), per-step latency, and with BENCH_MATRIX=1 a
layout x dtype sweep (NCHW/NHWC x fp32/bf16). Since r6 the capture carries
both MFU figures, and as of this release the headline `mfu` IS
`mfu_analytic` (XLA cost_analysis FLOPs of the actual compiled step
executable — what the program really costs post-fusion) with
`mfu_formula` (forward_complexity x3) kept as the secondary key the
r01-r05 trajectory gated on (obs/regress.py gates both, with an `mfu`
fallback for pre-switch captures), plus
`roofline_bytes_per_flop` + `phases.xla_cost` (the executable's
bytes-accessed/FLOP roofline coordinate), a `telemetry_essentials` block
(compile_total/compile_seconds_total counters, HBM watermark, h2d gauges —
always on, no trace artifact needed), and a `regressions` block: the
newest-vs-trailing-window verdict from dcnn_tpu/obs/regress.py
(standalone CLI: benchmarks/compare.py).

Runs the full jitted train step (forward+backward+Adam update) on synthetic
data resident in HBM, so the number isolates compute+HBM (the reference's
benchmarks do the same — synthetic tensors, no input pipeline; feed-rate is
benchmarked separately in benchmarks/).

Timing: BENCH_REPS repetitions of BENCH_STEPS steps each, best repetition
reported (standard throughput practice — the steady-state capability of the
chip).

Feed-path measurements reported alongside: ``pipeline_img_per_sec`` /
``feed_efficiency`` time the HBM-resident epoch path (dataset staged to
device once, shuffle/decode/one-hot fused into the dispatch — the intended
way to train HBM-fitting datasets); ``host_feed_*`` time the prefetch+chunked
host loader for datasets that exceed HBM (h2d_gbps reported alongside).

Env knobs: BENCH_MODEL (resnet18 default | resnet50), BENCH_BATCH (default
2048), BENCH_STEPS
(default 40), BENCH_REPS (default 5), DCNN_PRECISION (default bf16 =
mixed-precision activations; "fast" = bf16 MXU with fp32 storage; "parity"
for fp32), BENCH_CHUNK (train steps per device dispatch via the in-jit
train loop train.make_multi_step; default 40: one launch per chunk), BENCH_FORMAT (NHWC default — TPU-preferred tiling),
BENCH_MATRIX=1 for the layout/dtype sweep, BENCH_RESIDENT_SAMPLES
(resident-path dataset size, default 51200), BENCH_PROFILE=/path to dump a
jax.profiler trace, BENCH_SERVE=1 for the online-serving
latency-vs-offered-load curve (dcnn_tpu/serve/; knobs
BENCH_SERVE_LOADS/_SECONDS/_MAX_BATCH/_WAIT_MS/_QUEUE/_INT8 — emitted
under a "serving" key) plus the router-tier block (serving.router:
N-replica vs 1-replica capacity probe, latency-vs-load through the
Router, and a kill-a-replica availability sub-soak; knobs
BENCH_SERVE_ROUTER=0 to skip, BENCH_SERVE_ROUTER_REPLICAS default 4,
BENCH_SERVE_ROUTER_SECONDS per-phase traffic window, regression-gated
via serving.router.* keys in dcnn_tpu/obs/regress.py), BENCH_OBS=1 to enable the unified tracer
(dcnn_tpu/obs/) for the whole run — exports the JSONL trace shard and
merges it (python -m dcnn_tpu.obs.trace) into the Chrome trace_event
artifact (BENCH_OBS_TRACE, default /tmp/dcnn_bench_trace.json; open in
Perfetto: training step spans on the "train" track, per-chunk H2D
gather/put spans on the transfer-thread tracks, serve spans under
BENCH_SERVE=1, trace_id/span_id identity on every span) and appends a
"telemetry" block (merged trace path + shard list, span counts,
ring-saturation drop counts, metrics-registry snapshot) to the JSON line
(see docs/observability.md), BENCH_FEED_WORKERS
(default 0) to run the host side of the streaming + host-feed sections on
a shared-memory input-worker pool (dcnn_tpu/data/workers.py — gather +
augment + pack off the producer thread; per-worker prep spans and
prep_img_per_sec land under streaming_timeline.worker_prep),
BENCH_FEED_AUGMENT=1 to add host augmentation (flip+crop) to the streaming
feed so the prep measurement exercises the full gather+augment+pack path
(tuning guide: docs/performance.md), BENCH_WIRE=0 to skip the
uint8-first feed-wire block (default on — emitted under a "feed_wire"
key: wire_bytes_per_image, effective vs logical-f32 H2D rate, and
per-codec compression ratios for the selectable wire codecs
zlib/zstd/lz4/shuffle-lz4/shuffle-zstd over image-u8 and grad-f32
payloads; wire_bytes_per_image and streaming_img_per_sec are
regression-gated via dcnn_tpu/obs/regress.py), BENCH_FAULTS=1 for
the checkpoint save/restore overhead probe (dcnn_tpu/resilience/; knob
BENCH_FAULTS_REPS — emitted under a "resilience" key: sync save wall,
async save's step-loop cost, verified-restore wall, plus an "elastic"
sub-block measuring a real kill-a-host recovery on a 2-peer loopback DP
fleet: detection latency, checkpoint-restore wall, reconfiguration wall,
optimizer steps lost; docs/reliability.md §"Elastic training"),
BENCH_AUTOSCALE=1 for the telemetry-driven autoscaler's diurnal soak
(dcnn_tpu/serve/soak.py, the same sleep-free driver tier-1 gates —
emitted under an "autoscale" key: availability / slo_violation_minutes /
scale_up_reaction_s regression-gated via autoscale.* in
dcnn_tpu/obs/regress.py; knobs BENCH_AUTOSCALE_SECONDS default 240,
BENCH_AUTOSCALE_PEAK_RPS/_TROUGH_RPS default 200/20;
docs/deployment.md §6), BENCH_DECODE=1 for the continuous-batching
decode probe (dcnn_tpu/serve/decode.py — emitted under a "decode" key:
generated tokens/s, TTFT p99, and slot occupancy for the iteration-level
scheduler vs the sequential batch-of-one baseline on the same synthetic
length mix, decode.* regression-gated via dcnn_tpu/obs/regress.py; knobs
BENCH_DECODE_SLOTS default 8, BENCH_DECODE_SEQS default 24;
docs/deployment.md §"Generative serving").
"""

from __future__ import annotations

import json
import os
import sys
import time

# guarded inserts (only if absent): the benchmarks/ dir holds the
# generically-named `common` module — double-insertion or late insertion
# ahead of site-packages could shadow unrelated imports (ADVICE r5)
_ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (_ROOT, os.path.join(_ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# Peak dense-matmul TFLOP/s per chip, by jax device_kind prefix. bf16 figures;
# fp32 on the MXU runs at ~1/2 (v5e) via fp32 accumulate of bf16x3 passes —
# MFU is only reported for the bf16 ("fast") precision mode where the peak is
# well-defined.
PEAK_BF16_TFLOPS = {
    "TPU v6": 918.0,
    "TPU v5p": 459.0,
    "TPU v5": 197.0,   # v5 lite (v5e)
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}


def _peak_tflops(device_kind: str) -> float:
    for prefix, peak in PEAK_BF16_TFLOPS.items():
        if device_kind.startswith(prefix):
            return peak
    raise KeyError(
        f"no peak FLOP/s entry for device_kind {device_kind!r}: add it to "
        f"PEAK_BF16_TFLOPS with its source rather than report a null MFU")


def _failed_sections(out: dict) -> list:
    """Top-level sections (and their direct sub-blocks) that caught their
    own failure into ``{"error": ...}`` / ``{"skipped": ...}``: the JSON
    line still carries them, but the process must not exit 0."""
    failed = []
    for name, block in out.items():
        if not isinstance(block, dict):
            continue
        for sub_name, sub in [(name, block)] + [
                (f"{name}.{k}", v) for k, v in block.items()
                if isinstance(v, dict)]:
            if "error" in sub or "skipped" in sub:
                failed.append(sub_name)
    return failed


def _load_measured_baseline(root: str):
    path = os.path.join(root, "BASELINE_MEASURED.json")
    if not os.path.exists(path):
        return None, None
    with open(path) as f:
        data = json.load(f)
    for key in ("torch_cuda", "torch_cpu"):
        if key in data:
            return key, data[key]
    return None, None


def _measure(step, ts, x, y, key, steps, reps):
    """Best-of-reps steady-state throughput. Returns (best_seconds, ts):
    the train step donates its TrainState argument, so the rolling state must
    be threaded through every call (a stale reference is a deleted buffer on
    TPU) and handed back to the caller.

    Fenced with ``core.fence.hard_fence`` after the last step of a rep."""
    import jax

    from dcnn_tpu.core.fence import hard_fence
    from dcnn_tpu.obs import get_tracer

    tracer = get_tracer()  # no-op spans unless BENCH_OBS=1 enabled it
    from dcnn_tpu.obs import get_registry

    rep_times = []
    for r in range(reps):
        t0 = time.perf_counter()
        for i in range(steps):
            # dispatch-side span (~0.4 µs disabled, sub-µs enabled, vs
            # multi-ms dispatches — timing impact is noise)
            with tracer.span("train.step", track="train", rep=r, step=i):
                ts, loss, _ = step(ts, x, y, jax.random.fold_in(key, i), 1e-3)
        hard_fence(loss)
        rep_times.append(time.perf_counter() - t0)
        # tsdb history feed: created at first SET (not before the rep) so
        # the capture-long sampler never records a pre-measurement zero
        get_registry().gauge(
            "bench_step_seconds_last",
            "per-step wall of the newest bench rep (tsdb history feed)"
        ).set(rep_times[-1] / steps)
    return min(rep_times), ts, rep_times


def run_config(batch, steps, reps, data_format, profile_dir=None, chunk=1,
               pipeline=False):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dcnn_tpu.models import (
        create_resnet18_tiny_imagenet, create_resnet50_tiny_imagenet)
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.train import make_multi_step, make_train_step
    from dcnn_tpu.train.trainer import create_train_state

    bench_model = os.environ.get("BENCH_MODEL", "resnet18")
    make = {"resnet18": create_resnet18_tiny_imagenet,
            "resnet50": create_resnet50_tiny_imagenet}[bench_model]
    model = make(data_format)
    opt = Adam(1e-3)
    key = jax.random.PRNGKey(0)
    ts = create_train_state(model, opt, key)

    shape = (batch, 3, 64, 64) if data_format == "NCHW" else (batch, 64, 64, 3)
    rng = np.random.default_rng(0)

    if chunk > 1:
        # K distinct batches per dispatch: the in-jit train loop
        # (train.make_multi_step) — one executable launch per K steps.
        steps = max(chunk, (steps // chunk) * chunk)
        kshape = (chunk,) + shape
        xs = jnp.asarray(rng.normal(size=kshape).astype(np.float32))
        ys = jnp.asarray(np.eye(200, dtype=np.float32)[
            rng.integers(0, 200, size=(chunk, batch))])
        multi = make_multi_step(model, softmax_cross_entropy, opt)
        step = lambda ts_, x_, y_, rng_, lr_: multi(ts_, x_, y_, rng_, lr_) + (None,)
        x, y = xs, ys
        dispatches = steps // chunk
    else:
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        y = jnp.asarray(np.eye(200, dtype=np.float32)[rng.integers(0, 200, size=batch)])
        step = make_train_step(model, softmax_cross_entropy, opt)
        dispatches = steps

    # warmup / compile (a few steps: first-call autotuning).
    # Phase walls are recorded separately so the variance study (RESULTS.md)
    # can attribute run-to-run spread: compile (first dispatch, cache-served
    # or not), remaining warmup, then the timed reps.
    from dcnn_tpu.core.fence import hard_fence

    def _cache_entries():
        # persistent compile-cache population (utils.enable_compile_cache
        # pointed jax at a dir); None when the cache isn't file-backed
        d = getattr(jax.config, "jax_compilation_cache_dir", None)
        if not d or not os.path.isdir(d):
            return None
        return sum(1 for n in os.listdir(d)
                   if os.path.isfile(os.path.join(d, n)))

    n_cache0 = _cache_entries()
    t0 = time.perf_counter()
    ts, loss, _ = step(ts, x, y, jax.random.fold_in(key, 997), 1e-3)
    hard_fence(loss)
    compile_s = time.perf_counter() - t0
    n_cache1 = _cache_entries()
    # cache warmth (satellite r6): a cold compile WRITES a new persistent
    # cache entry, a warm one is served from disk — so "no new entries"
    # separates cache effects from real compile-time regressions in the
    # trajectory. (149.9 s cold vs seconds warm on the r5 capture.)
    cache_hit = (n_cache0 == n_cache1) if n_cache0 is not None else None
    t0 = time.perf_counter()
    for i in range(1, 2 if chunk > 1 else 4):
        ts, loss, _ = step(ts, x, y, jax.random.fold_in(key, 997 + i), 1e-3)
    hard_fence(loss)
    warmup_s = time.perf_counter() - t0
    # warm-run compile probe: a FRESH jit of the same computation pays
    # trace + persistent-cache load, never a full XLA compile — the
    # compile_s a rerun of this config would report
    t0 = time.perf_counter()
    if chunk > 1:
        multi2 = make_multi_step(model, softmax_cross_entropy, opt)
        step2 = lambda ts_, x_, y_, rng_, lr_: (
            multi2(ts_, x_, y_, rng_, lr_) + (None,))
    else:
        step2 = make_train_step(model, softmax_cross_entropy, opt)
    ts, loss, _ = step2(ts, x, y, jax.random.fold_in(key, 996), 1e-3)
    hard_fence(loss)
    compile_warm_s = time.perf_counter() - t0
    step2 = multi2 = None

    # XLA's own accounting of the headline executable (dcnn_tpu/obs/xla):
    # post-fusion FLOPs + bytes-accessed from cost_analysis() feed the
    # analytic MFU (mfu_analytic, reported next to the forward_complexity
    # formula value) and the roofline byte/FLOP ratio; the compile walls
    # land on the compile_total/compile_seconds_total counters
    from dcnn_tpu.obs.xla import jit_cost, record_compile
    record_compile(compile_s, what="train")
    record_compile(compile_warm_s, what="train_warm")
    jitted = multi if chunk > 1 else step
    xla_cost = jit_cost(jitted, ts, x, y, jax.random.fold_in(key, 0), 1e-3)
    if xla_cost is not None:
        imgs_per_dispatch = batch * (chunk if chunk > 1 else 1)
        if xla_cost.get("flops"):
            xla_cost["flops_per_img"] = xla_cost["flops"] / imgs_per_dispatch
        from dcnn_tpu.obs import get_registry
        _reg = get_registry()
        for k, gname in (("flops", "train_step_flops"),
                         ("bytes_accessed", "train_step_bytes_accessed"),
                         ("bytes_per_flop", "train_step_bytes_per_flop")):
            if xla_cost.get(k) is not None:
                _reg.gauge(gname, f"XLA cost analysis: {k} of the headline "
                                  f"train executable").set(xla_cost[k])

    if profile_dir:
        with jax.profiler.trace(profile_dir):
            _, ts, _ = _measure(step, ts, x, y, key, min(dispatches, 5), 1)

    dt, ts, rep_times = _measure(step, ts, x, y, key, dispatches, reps)
    img_per_sec = batch * steps / dt
    phases = {"compile_s": round(compile_s, 3),
              "compile_cache_hit": cache_hit,
              "compile_warm_s": round(compile_warm_s, 3),
              "warmup_s": round(warmup_s, 3),
              "rep_s": [round(r, 4) for r in rep_times],
              "steps_per_rep": steps,
              "xla_cost": ({k: (round(v, 6) if k == "bytes_per_flop"
                                else round(v, 1))
                            for k, v in xla_cost.items()}
                           if xla_cost is not None else None)}
    # release the headline working set (the staged K-batch chunk is ~4 GB
    # fp32 at batch 4096×20) before the feed sections allocate their own —
    # holding both exceeds HBM at the larger default batch
    x = y = xs = ys = step = None
    del ts

    resident_img_per_sec = None
    if pipeline and os.environ.get("BENCH_RESIDENT", "1") != "0":
        # HBM-resident feed (data/device_dataset.py): the dataset is staged
        # to device once as uint8; shuffle/gather/decode/one-hot + the train
        # step run inside ONE dispatch per epoch — zero steady-state H2D.
        # This is the intended way to train an HBM-fitting dataset (the
        # TPU-native analog of the reference's decode-once host-RAM strategy,
        # tiny_imagenet_data_loader.hpp:26-132) and the headline feed path.
        import numpy as np

        from dcnn_tpu.core.fence import hard_fence as _hf
        from dcnn_tpu.data.device_dataset import make_resident_epoch

        # fixed default (not batch-scaled): same resident working set and
        # compile size across headline-batch changes
        n_res = int(os.environ.get("BENCH_RESIDENT_SAMPLES", "51200"))
        n_res = max((n_res // batch) * batch, batch)
        rng_np = np.random.default_rng(1)
        x_res = jnp.asarray(rng_np.integers(
            0, 256, size=(n_res, *shape[1:]), dtype=np.uint8))
        y_res = jnp.asarray(rng_np.integers(0, 200, size=n_res).astype(np.int32))
        epoch_fn = make_resident_epoch(
            model, softmax_cross_entropy, opt,
            num_classes=200, batch_size=batch)
        ts3 = create_train_state(model, opt, key)
        ts3, l = epoch_fn(ts3, x_res, y_res, jax.random.fold_in(key, 7000), 1e-3)
        _hf(l)  # warmup: compile + first epoch
        # best-of-reps, same discipline as _measure: a single epoch timing
        # is exposed to one dispatch-jitter spike and skews feed_efficiency
        # (ADVICE r3 #2)
        best = float("inf")
        for r in range(reps):
            t0 = time.perf_counter()
            ts3, l = epoch_fn(ts3, x_res, y_res,
                              jax.random.fold_in(key, 7001 + r), 1e-3)
            _hf(l)
            best = min(best, time.perf_counter() - t0)
        resident_img_per_sec = n_res / best

    pipeline_img_per_sec = h2d_gbps = None
    if pipeline and os.environ.get("BENCH_PIPELINE", "1") != "0":
        # Input-pipeline-included throughput: host loader (uint8 images +
        # int labels — the idiomatic TPU feed payload, 4x fewer H2D bytes
        # than fp32) -> PrefetchLoader with chunked staging (K batches
        # stacked per transfer) + on-device decode (cast/scale/one-hot via
        # device_transform) -> in-jit K-step train loop (train.make_multi_step,
        # one dispatch per chunk). Compares feed rate vs step rate;
        # h2d_gbps is reported alongside so feed_efficiency can be read in
        # context.
        import numpy as np

        from dcnn_tpu.core.fence import hard_fence as _hf
        from dcnn_tpu.core.precision import get_compute_dtype
        from dcnn_tpu.data import ArrayDataLoader, PrefetchLoader
        from dcnn_tpu.train import make_multi_step

        stage = int(os.environ.get("BENCH_STAGE", "10"))
        n_chunks = int(os.environ.get("BENCH_PIPELINE_CHUNKS", "5"))
        n_samples = batch * stage * n_chunks
        rng_np = np.random.default_rng(0)
        x_u8 = rng_np.integers(0, 256, size=(n_samples, *shape[1:]),
                               dtype=np.uint8)
        labels = rng_np.integers(0, 200, size=n_samples).astype(np.int32)
        loader = ArrayDataLoader(x_u8, labels, batch_size=batch, shuffle=False)
        loader.load_data()

        # raw H2D bandwidth for context (one 64 MiB buffer, hard-fenced)
        probe = rng_np.integers(0, 256, size=(64 << 20,), dtype=np.uint8)
        _hf(jax.device_put(probe[: 1 << 20]))  # warm the transfer path
        t0 = time.perf_counter()
        _hf(jax.device_put(probe))
        h2d_gbps = probe.nbytes / (time.perf_counter() - t0) / 1e9

        cdt = get_compute_dtype() or jnp.float32
        # multiply-by-reciprocal form: the wire contract's canonical decode
        # (data/wire.py) — division differs by 1 ulp via double rounding
        decode = jax.jit(lambda xu, yi: (
            xu.astype(cdt) * np.asarray(np.float32(1.0 / 255.0), cdt),
            jax.nn.one_hot(yi, 200, dtype=jnp.float32)))
        # BENCH_FEED_WORKERS>0: the producer's gather+collate runs on the
        # shared-memory worker pool (data/workers.py) instead of the
        # producer thread — bit-identical batches, parallel host prep
        feed_workers = int(os.environ.get("BENCH_FEED_WORKERS", "0"))
        pf = PrefetchLoader(loader, depth=2, stage_batches=stage,
                            device_transform=decode,
                            feed_workers=feed_workers)
        multi = make_multi_step(model, softmax_cross_entropy, opt)
        ts2 = create_train_state(model, opt, key)
        # untimed epoch: compiles the multi-step executable + warms the
        # producer thread and H2D path
        n = 0
        for xs_c, ys_c in pf:
            ts2, loss = multi(ts2, xs_c, ys_c, jax.random.fold_in(key, 5000 + n), 1e-3)
            n += 1
        _hf(loss)
        # timed epoch, steady state: the first chunk (producer cold at t0 —
        # its host stack + H2D has nothing to overlap with) is dispatched but
        # excluded; timing starts once the pipeline is filled
        t0, n = None, 0
        for xs_c, ys_c in pf:
            ts2, loss = multi(ts2, xs_c, ys_c, jax.random.fold_in(key, 6000 + n), 1e-3)
            if t0 is None:
                _hf(loss)
                t0 = time.perf_counter()
                continue
            n += xs_c.shape[0]
        _hf(loss)
        if n:
            pipeline_img_per_sec = batch * n / (time.perf_counter() - t0)
        pf.close()  # releases the worker pool, if one was configured

    streaming_img_per_sec = overlap_eff = None
    streaming_timeline = None
    # default-on since r5 (VERDICT r4 #4: the driver capture must carry a
    # real number); BENCH_STREAMING=0 opts out.
    if pipeline and os.environ.get("BENCH_STREAMING", "1") == "1":
        # Streaming feed (data/streaming.py): datasets > HBM stream through
        # in double-buffered uint8 shards — shard i+1's async device_put
        # rides under shard i's fused dispatch. Law: epoch wall ≈
        # max(T_feed, T_compute) + 1 shard latency; overlap_efficiency
        # reports max(T_feed_est, T_compute_est) / wall (1.0 = perfect
        # overlap).
        import numpy as np

        from dcnn_tpu.core.fence import hard_fence as _hf
        from dcnn_tpu.data import (
            AugmentationBuilder, FeedWorkerPool, StreamingDeviceDataset,
            TransferEngine, make_shard_step, train_streaming_epoch)

        # small default shard count (≈12 MB/batch): 2x2 batches still
        # exercises the double-buffer overlap
        sb = int(os.environ.get("BENCH_STREAM_SHARD_BATCHES", "2"))
        n_shards = int(os.environ.get("BENCH_STREAM_SHARDS", "2"))
        # chunked multi-stream transfer engine (data/transfer.py): C chunks
        # per shard shipped by a pool of transfer threads — several H2D
        # copies in flight at once — handed to the shard step as a chunk
        # tuple (in-dispatch reassembly)
        n_chunks = int(os.environ.get("BENCH_STREAM_CHUNKS", "4"))
        n_threads = int(os.environ.get("BENCH_STREAM_THREADS", "2"))
        # parallel host input pipeline (data/workers.py): gather (+ host
        # augmentation under BENCH_FEED_AUGMENT=1) + pack run on
        # BENCH_FEED_WORKERS worker processes writing shared-memory ring
        # slots; 0 keeps the serial producer (bit-identical either way)
        feed_workers = int(os.environ.get("BENCH_FEED_WORKERS", "0"))
        feed_augment = os.environ.get("BENCH_FEED_AUGMENT", "0") == "1"
        n_s = batch * sb * n_shards
        rng_np = np.random.default_rng(2)
        xs_host = rng_np.integers(0, 256, size=(n_s, *shape[1:]),
                                  dtype=np.uint8)
        ys_host = rng_np.integers(0, 200, size=n_s).astype(np.int32)
        sds = StreamingDeviceDataset(xs_host, ys_host, 200, batch_size=batch,
                                     shard_batches=sb)
        sstep = make_shard_step(model, softmax_cross_entropy, opt,
                                num_classes=200, batch_size=batch,
                                shard_batches=sb)
        engine = TransferEngine(num_chunks=n_chunks, num_threads=n_threads,
                                reassemble="chunks")
        host_aug = None
        if feed_augment:
            host_aug = (AugmentationBuilder(data_format)
                        .horizontal_flip(p=0.5).random_crop(2, p=1.0)
                        .build())
        pool = None
        if feed_workers > 0 or host_aug is not None:
            pool = FeedWorkerPool(sds.x, sds.y, sds.shard_samples,
                                  num_workers=feed_workers,
                                  augment=host_aug, seed=0)
        ts4 = create_train_state(model, opt, key)
        ts4, _ = train_streaming_epoch(sstep, ts4, sds,
                                       jax.random.fold_in(key, 8000), 1e-3,
                                       engine=engine, worker_pool=pool,
                                       epoch=0)
        _hf(ts4.params)  # warmup epoch: compile + H2D path
        tl = []
        t0 = time.perf_counter()
        ts4, _ = train_streaming_epoch(sstep, ts4, sds,
                                       jax.random.fold_in(key, 8001), 1e-3,
                                       timeline=tl, engine=engine,
                                       worker_pool=pool, epoch=1)
        _hf(ts4.params)
        wall = time.perf_counter() - t0
        engine.close()
        if pool is not None:
            pool.close()
        streaming_img_per_sec = n_s / wall
        t_compute = n_s / img_per_sec
        # measured feed time from the per-shard timeline (the engine's
        # actual per-shard feed walls: chunk-parallel gather + the union of
        # the in-flight put spans), not the bulk h2d_gbps estimate — the r4
        # overlap number was computed against the estimate and
        # under-credited the implementation
        t_feed = (sum(e["feed_wall_s"] for e in tl)
                  or (xs_host.nbytes / (h2d_gbps * 1e9) if h2d_gbps else 0.0))
        overlap_eff = max(t_feed, t_compute) / wall
        fed_bytes = sum(e["bytes"] for e in tl)
        put_union = sum(e["put_s"] for e in tl)
        streaming_timeline = {
            "gather_s": round(sum(e["gather_s"] for e in tl), 3),
            "put_s": round(put_union, 3),
            "feed_wall_s": round(sum(e["feed_wall_s"] for e in tl), 3),
            "dispatch_s": round(sum(e["dispatch_s"] for e in tl), 3),
            "queue_wait_s": round(sum(e["queue_wait_s"] for e in tl), 3),
            "wall_s": round(wall, 3),
            "t_compute_est_s": round(t_compute, 3),
            # chunked multi-stream evidence: peak concurrently in-flight
            # chunk transfers, per-chunk span count, and the effective H2D
            # rate over the union of the put spans
            "transfer_chunks": n_chunks,
            "transfer_threads": n_threads,
            "chunk_put_spans": [
                [round(c["put_start_t"], 3), round(c["put_end_t"], 3)]
                for e in tl for c in e["chunks"]],
            "inflight_max": max((e["inflight_max"] for e in tl), default=0),
            "h2d_gbps_effective": (round(fed_bytes / put_union / 1e9, 3)
                                   if put_union > 0 else None),
            # uint8-first wire accounting (docs/performance.md §5):
            # wire_bytes_per_image counts what actually crossed H2D per
            # sample (images + labels as shipped); logical_gbps rates the
            # float32-equivalent payload (images at 4 bytes/px, labels
            # as-is) over the same put union — the "how fast does this
            # LOOK to the f32 consumer" number, ~4x the effective rate
            # on a uint8 wire
            "wire_bytes_per_image": round(fed_bytes / n_s, 2),
            "logical_gbps": (round(
                (fed_bytes - xs_host.nbytes + 4 * xs_host.nbytes)
                / put_union / 1e9, 3) if put_union > 0 else None)}
        preps = [e["prep"] for e in tl if "prep" in e]
        if preps:
            # host-side shard-prep accounting from the pool's per-worker
            # spans: per-worker phase sums, the per-shard [prep_t0,
            # prep_t1) spans, and throughput over their UNION (overlapped
            # workers must not double-count) — the measurement surface for
            # the ≥2x parallel-prep acceptance gate
            from dcnn_tpu.data.transfer import union_seconds

            per_worker = {}
            for p in preps:
                d = per_worker.setdefault(
                    str(p["worker"]),
                    {"shards": 0, "gather_s": 0.0, "augment_s": 0.0,
                     "pack_s": 0.0})
                d["shards"] += 1
                for k in ("gather_s", "augment_s", "pack_s"):
                    d[k] += p[k]
            prep_union = union_seconds([(p["prep_t0"], p["prep_t1"])
                                        for p in preps])
            streaming_timeline["feed_workers"] = feed_workers
            streaming_timeline["feed_augment"] = feed_augment
            streaming_timeline["worker_prep"] = {
                "per_worker": {w: {k: (round(v, 4) if isinstance(v, float)
                                       else v) for k, v in d.items()}
                               for w, d in sorted(per_worker.items())},
                "prep_spans": [[round(p["prep_t0"], 3),
                                round(p["prep_t1"], 3),
                                p["worker"]] for p in preps],
                "prep_s_union": round(prep_union, 3),
                "prep_img_per_sec": (round(n_s / prep_union, 1)
                                     if prep_union > 0 else None)}

    # analytic training FLOPs: fwd + bwd ~= 3x forward (standard convention;
    # the reference's partitioner uses the same estimator family)
    fwd_flops_per_img = model.forward_complexity()
    train_flops = 3.0 * fwd_flops_per_img * img_per_sec
    return (img_per_sec, dt / steps, train_flops / 1e12, pipeline_img_per_sec,
            h2d_gbps, resident_img_per_sec, streaming_img_per_sec, overlap_eff,
            phases, streaming_timeline)


def feed_wire_section(streaming_timeline):
    """uint8-first feed-wire evidence (docs/performance.md §5): the wire
    accounting the streaming epoch measured (bytes actually shipped per
    image, effective vs logical-f32 H2D rate) plus per-codec compression
    ratios over two representative payloads — a spatially correlated
    uint8 image shard (the feed wire) and a small-magnitude float32
    gradient block (the elastic grad exchange) — each round-tripped
    through the MetaCompressor tensor framing and verified bit-equal
    before the ratio is trusted. Codecs whose native backend is absent
    report ``{"available": False}`` instead of a fabricated number."""
    import numpy as np

    from dcnn_tpu.utils.compression import MetaCompressor, resolve_codec

    rng = np.random.default_rng(11)
    # smooth ramp + bounded noise: correlated like a real image — pure rng
    # noise is incompressible and would read every codec as ratio 1.0
    ramp = np.linspace(0.0, 255.0, 64 * 64,
                       dtype=np.float32).reshape(64, 64)
    img = (ramp[None, :, :, None]
           + rng.integers(-8, 9, size=(32, 64, 64, 3)).astype(np.float32))
    img_u8 = np.clip(img, 0.0, 255.0).astype(np.uint8)
    grad_f32 = rng.standard_normal((256, 1024)).astype(np.float32) * 1e-3
    mc = MetaCompressor()
    codecs = {}
    for name in ("zlib", "zstd", "lz4", "shuffle-lz4", "shuffle-zstd"):
        try:
            codec = resolve_codec(name)
        except RuntimeError:
            codecs[name] = {"available": False}
            continue
        entry = {"available": True}
        for key, arr in (("image_u8", img_u8), ("grad_f32", grad_f32)):
            t0 = time.perf_counter()
            wire = mc.compress_array(arr, codec=codec)
            dt = time.perf_counter() - t0
            back = mc.decompress_array(wire)
            if back.dtype != arr.dtype or not np.array_equal(back, arr):
                raise AssertionError(
                    f"wire codec {name} round-trip mismatch on {key}")
            entry[f"{key}_ratio"] = round(arr.nbytes / len(wire), 3)
            entry[f"{key}_compress_mbps"] = (round(arr.nbytes / dt / 1e6, 1)
                                             if dt > 0 else None)
        codecs[name] = entry
    tl = streaming_timeline or {}
    return {
        # the wire contract: every feed path ships uint8, decode (cast +
        # scale by 1/255) runs on device after the put
        "wire_dtype": "uint8",
        "wire_bytes_per_image": tl.get("wire_bytes_per_image"),
        "h2d_gbps_effective": tl.get("h2d_gbps_effective"),
        "logical_gbps": tl.get("logical_gbps"),
        "codecs": codecs,
    }


def int8_inference_section(data_format: str):
    """Deployment-graph throughput: BN-folded bf16 vs int8 PTQ ResNet-18
    inference (nn.quantize_model; RESULTS.md 'int8 PTQ inference'). Returns
    (bf16_img_per_sec, int8_img_per_sec). Timing is the shared
    benchmarks/common.time_chained harness (two-length difference method on
    TPU, per-dispatch fallback on CPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from common import dep_feed, e2e_chain_length, time_chained

    from dcnn_tpu.models import create_resnet18_tiny_imagenet
    from dcnn_tpu.nn import fold_batchnorm, quantize_model
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.train.trainer import create_train_state

    # CPU path (the verify recipe's tiny run) shrinks the problem: a
    # batch-256 resnet18 chain takes minutes on a 1-core host
    on_tpu = jax.default_backend() == "tpu"
    batch = int(os.environ.get("BENCH_INT8_BATCH",
                               "256" if on_tpu else "8"))
    length = e2e_chain_length(8)  # jitter rationale: benchmarks/common.py
    model = create_resnet18_tiny_imagenet(data_format)
    ts = create_train_state(model, Adam(1e-3), jax.random.PRNGKey(3))
    shape = ((batch, 3, 64, 64) if data_format == "NCHW"
             else (batch, 64, 64, 3))
    x = jnp.asarray(np.random.default_rng(5).normal(size=shape),
                    jnp.float32)
    fmodel, fp, fs = fold_batchnorm(model, ts.params, ts.state)
    qmodel, qp, qs = quantize_model(model, ts.params, ts.state, x)

    # roofline sanity gate lives in the shared harness (time_chained
    # roofline=): retry on physically impossible implied FLOP rates, and
    # return None rather than let an impossible number into the driver
    # capture if it persists
    fwd_flops = float(model.forward_complexity()) * batch
    bf16_peak = 197e12 if on_tpu else None
    dt_f, f_sane = time_chained(
        lambda c: fmodel.apply(fp, fs, c, training=False)[0], (x,),
        dep_feed(0), length=length, roofline=(fwd_flops, bf16_peak))
    dt_q, q_sane = time_chained(
        lambda c: qmodel.apply(qp, qs, c, training=False)[0], (x,),
        dep_feed(0), length=length,
        roofline=(fwd_flops, bf16_peak * 2 if bf16_peak else None))
    if not (f_sane and q_sane):
        return None
    return batch / dt_f, batch / dt_q


def serve_section(data_format, engine=None, loads=None, seconds=None):
    """BENCH_SERVE=1: online-serving latency vs offered load
    (dcnn_tpu/serve/ — bucketed compiled sessions + dynamic batcher;
    RESULTS.md 'Online serving'). Open-loop single-sample arrivals at each
    offered rate; the returned block carries, per point, achieved
    throughput, p50/p95/p99 latency, mean batch occupancy, and the shed
    fraction — the four numbers that together say whether the batcher is
    turning offline img/s into a servable p99 or just queueing.

    ``engine``/``loads``/``seconds`` are injectable for the tier-1
    structure test; the bench path builds a ResNet-18 engine (int8 by
    default — the serving graph of record; BENCH_SERVE_INT8=0 for folded
    float) and derives default loads from a measured closed-loop capacity
    probe so the curve always brackets saturation (~0.25x/0.5x/1x)."""
    import numpy as np
    import jax

    from dcnn_tpu.serve import DynamicBatcher, InferenceEngine, \
        ServeMetrics, open_loop

    on_tpu = jax.default_backend() == "tpu"
    if engine is None:
        from dcnn_tpu.models import create_resnet18_tiny_imagenet
        from dcnn_tpu.optim import Adam
        from dcnn_tpu.train.trainer import create_train_state

        mb = int(os.environ.get("BENCH_SERVE_MAX_BATCH",
                                "64" if on_tpu else "8"))
        model = create_resnet18_tiny_imagenet(data_format)
        ts = create_train_state(model, Adam(1e-3), jax.random.PRNGKey(9))
        rng = np.random.default_rng(11)
        calib = None
        if os.environ.get("BENCH_SERVE_INT8", "1") == "1":
            calib = rng.normal(size=(32, *model.input_shape)
                               ).astype(np.float32)
        engine = InferenceEngine.from_model(model, ts.params, ts.state,
                                            int8_calib=calib, max_batch=mb)

    rng = np.random.default_rng(12)
    pool = rng.normal(size=(max(2 * engine.max_batch, 32),
                            *engine.input_shape)).astype(np.float32)

    # closed-loop capacity probe: full-bucket dispatches back to back —
    # the ceiling the open-loop curve is read against
    full = pool[:engine.max_batch]
    np.asarray(engine.run_padded(full))  # sessions are warm; settle caches
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        y = engine.run_padded(full)
    np.asarray(y)  # host materialization fences the chain
    capacity = reps * engine.max_batch / (time.perf_counter() - t0)

    if loads is None:
        env_loads = os.environ.get("BENCH_SERVE_LOADS")
        if env_loads:
            loads = [float(v) for v in env_loads.split(",")]
        else:
            loads = [round(capacity * f, 1) for f in (0.25, 0.5, 1.0)]
    if seconds is None:
        seconds = float(os.environ.get("BENCH_SERVE_SECONDS",
                                       "5" if on_tpu else "2"))
    wait_ms = float(os.environ.get("BENCH_SERVE_WAIT_MS", "5"))
    qcap = int(os.environ.get("BENCH_SERVE_QUEUE",
                              str(4 * engine.max_batch)))

    # under BENCH_OBS=1 the serve counters must land in the process-global
    # registry or the telemetry block would silently omit the serve_*
    # series it promises; points stay separable via their own snapshots
    # (per-instance state), the registry carries the cumulative run
    obs_reg = None
    if os.environ.get("BENCH_OBS", "0") == "1":
        from dcnn_tpu.obs import get_registry
        obs_reg = get_registry()

    points = []
    for rps in loads:
        metrics = (ServeMetrics(registry=obs_reg) if obs_reg is not None
                   else ServeMetrics())
        batcher = DynamicBatcher(engine, max_wait_ms=wait_ms,
                                 queue_capacity=qcap, metrics=metrics)
        open_loop(batcher, pool, rps, seconds)
        batcher.drain(timeout=600)
        s = metrics.snapshot()
        rnd = lambda v, k=2: None if v is None else round(v, k)
        points.append({
            "offered_rps": rnd(rps, 1),
            "achieved_rps": rnd(s["throughput_rps"], 1),
            "p50_ms": rnd(s["p50_ms"]),
            "p95_ms": rnd(s["p95_ms"]),
            "p99_ms": rnd(s["p99_ms"]),
            "batch_occupancy": rnd(s["batch_occupancy"], 3),
            "shed_fraction": rnd(s["shed_fraction"], 4),
            "completed": s["requests_completed"],
        })
    doc = {
        "graph": engine.name,
        "device_kind": jax.devices()[0].device_kind,
        "max_batch": engine.max_batch,
        "buckets": engine.bucket_sizes,
        "max_wait_ms": wait_ms,
        "queue_capacity": qcap,
        "seconds_per_point": seconds,
        "capacity_img_per_sec": round(capacity, 1),
        "loads": points,
    }
    # router tier (only on the env-driven bench path: the tier-1 structure
    # test injects its own engine and exercises router_section directly)
    if os.environ.get("BENCH_SERVE_ROUTER", "1") == "1" \
            and "BENCH_SERVE_LOADS" not in os.environ:
        doc["router"] = router_section(data_format)
    return doc


def router_section(data_format, engines=None, seconds=None,
                   load_fracs=(0.25, 0.5, 0.8)):
    """BENCH_SERVE=1 ``serving.router`` block: the router-tier headlines
    (dcnn_tpu/serve/router.py; regression-gated via ``serving.router.*``
    keys in obs/regress.py):

    - **capacity probe** — closed-loop img/s of 1 replica vs N replicas
      driven concurrently (``capacity_scaling_x`` is the router tier's
      reason to exist; the acceptance bar is >= 3x at the default 4
      in-process replicas on the build host);
    - **latency-vs-load curve THROUGH the router** — open-loop batch-8
      requests at fractions of the N-replica capacity, per-point
      p50/p99/shed from RouterMetrics;
    - **kill-a-replica availability sub-soak** — one replica is killed
      mid-soak; availability = completed/accepted (accepted work is
      re-admitted to survivors, so this should stay ~1.0), plus typed
      failures, shed fraction, and whether the restarted replica
      rejoined.

    The probe graph is a dispatch-heavy serving CNN (28x28 two-conv
    stack) at max_batch 64 rather than the ResNet-18 headline model:
    per-replica scaling is a property of the router tier, and N copies
    of the big graph would spend the whole budget compiling. Engines are
    injectable for the tier-1 structure test."""
    import threading as _threading

    import numpy as np
    import jax

    from dcnn_tpu.serve import InferenceEngine, LocalReplica, Router, \
        RouterMetrics, open_loop

    n_replicas = int(os.environ.get("BENCH_SERVE_ROUTER_REPLICAS", "4"))
    if seconds is None:
        seconds = float(os.environ.get("BENCH_SERVE_ROUTER_SECONDS", "1.5"))
    if engines is None:
        from dcnn_tpu.nn import SequentialBuilder
        from dcnn_tpu.optim import Adam
        from dcnn_tpu.train.trainer import create_train_state

        mb = int(os.environ.get("BENCH_SERVE_ROUTER_MAX_BATCH", "64"))
        model = (SequentialBuilder(name="router_probe",
                                   data_format=data_format or "NHWC")
                 .input((28, 28, 1))
                 .conv2d(32, 3, padding=1).batchnorm().activation("relu")
                 .conv2d(32, 3, padding=1).batchnorm().activation("relu")
                 .maxpool2d(2).flatten().dense(10)
                 .build())
        ts = create_train_state(model, Adam(1e-3), jax.random.PRNGKey(21))
        engines = [InferenceEngine.from_model(
            model, ts.params, ts.state, max_batch=mb,
            name=f"router-probe-{i}") for i in range(n_replicas)]
    n_replicas = len(engines)
    mb = engines[0].max_batch
    rng = np.random.default_rng(23)
    pool = rng.normal(size=(mb, *engines[0].input_shape)
                      ).astype(np.float32)

    # -- capacity probe: 1 replica vs N driven concurrently ---------------
    def closed_loop(eng, secs):
        n = 0
        np.asarray(eng.run_padded(pool))  # warm/settle
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs:
            np.asarray(eng.run_padded(pool))
            n += mb
        return n / (time.perf_counter() - t0)

    cap1 = closed_loop(engines[0], seconds)
    rates = [0.0] * n_replicas

    def probe(i):
        rates[i] = closed_loop(engines[i], seconds)

    threads = [_threading.Thread(target=probe, args=(i,), daemon=True)
               for i in range(n_replicas)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cap_n = sum(rates)

    # -- latency-vs-load through the router -------------------------------
    replicas = [LocalReplica(eng, name=f"bench-r{i}", queue_capacity=8 * mb,
                             max_wait_ms=1.0)
                for i, eng in enumerate(engines)]
    points = []
    kill_doc = {}
    router = Router(replicas, metrics=RouterMetrics())
    try:
        # rows per request: offered img/s = rps * batch (8 at the default
        # max_batch 64; smaller when an injected engine's buckets are)
        batch = min(8, max(1, mb // 4))
        samples = [pool[j:j + batch] for j in range(0, mb - batch, batch)]
        for frac in load_fracs:
            rps = max(frac * cap_n / batch, 1.0)
            m = RouterMetrics()
            router.metrics = m
            open_loop(router, samples, rps, seconds)
            deadline = time.monotonic() + 60
            while router.outstanding() and time.monotonic() < deadline:
                time.sleep(0.005)
            s = m.snapshot()["total"]
            lat = m.snapshot()["normal"]
            rnd = lambda v, k=2: None if v is None else round(v, k)
            points.append({
                "offered_img_per_sec": round(rps * batch, 1),
                "achieved_rps": rnd(s["throughput_rps"], 1),
                "p50_ms": rnd(lat["p50_ms"]),
                "p99_ms": rnd(lat["p99_ms"]),
                "shed_fraction": rnd(s["shed_fraction"], 4),
                "completed": s["completed"],
                "failed": s["failed"],
            })

        # -- kill-a-replica availability sub-soak -------------------------
        m = RouterMetrics()
        router.metrics = m
        victim = replicas[0]
        # the kill must fire mid-soak even when the generator never
        # sleeps (an overloaded open loop is behind schedule constantly),
        # so it rides a timer, not the pacing hook
        killer = _threading.Timer(seconds / 2, victim.kill)
        killer.daemon = True
        killer.start()

        def soak_sleep(dt):
            time.sleep(dt)
            router.check_replicas()

        rps = max(0.4 * cap_n / batch, 1.0)
        futs = open_loop(router, samples, rps, seconds, sleep=soak_sleep)
        killer.join()  # the kill has fired by end-of-soak + join
        if not victim.is_dead():
            victim.kill()
        router.check_replicas()
        deadline = time.monotonic() + 60
        while router.outstanding() and time.monotonic() < deadline:
            time.sleep(0.005)
        accepted = len(futs)
        completed = sum(1 for _, f in futs
                        if f.done() and f.exception() is None)
        typed_failures = sum(1 for _, f in futs
                             if f.done() and f.exception() is not None)
        undone = accepted - completed - typed_failures
        victim.restart()
        rejoined = router.check_replicas().get("bench-r0") == "rejoined"
        s = m.snapshot()["total"]
        kill_doc = {
            "offered_img_per_sec": round(rps * batch, 1),
            "accepted": accepted,
            "completed": completed,
            "typed_failures": typed_failures,
            "silently_dropped": undone,  # MUST be 0 — the ledger contract
            "availability": round(completed / accepted, 4) if accepted
            else None,
            "shed_fraction": round(s["shed_fraction"], 4),
            "replica_deaths": int(m.registry.snapshot()[
                "serve_router_replica_deaths_total"]),
            "rejoined_after_restart": rejoined,
        }
    finally:
        router.shutdown(drain=False)
        for r in replicas:
            try:
                r.close()
            except Exception:
                pass

    return {
        "replicas": n_replicas,
        "max_batch": mb,
        "graph": engines[0].name,
        "seconds_per_phase": seconds,
        "capacity_1_img_per_sec": round(cap1, 1),
        "capacity_img_per_sec": round(cap_n, 1),
        "capacity_scaling_x": round(cap_n / cap1, 2) if cap1 else None,
        "loads": points,
        "kill_soak": kill_doc,
    }


def autoscale_section():
    """BENCH_AUTOSCALE=1 ``autoscale`` block: the telemetry-driven
    autoscaler's diurnal soak (dcnn_tpu/serve/soak.py — the same driver
    tier-1 gates, so the capture's numbers and the test's assertions can
    never drift apart). A 10x peak-to-trough diurnal curve through the
    router with a replica preemption and a canary swap injected
    mid-load, the autoscaler breathing the fleet between 1 and 6
    replicas; entirely virtual-time (fake clock, zero sleeps), so a
    four-minute soak costs well under a second of wall.

    Regression-gated keys (obs/regress.py ``autoscale.*``):
    ``availability`` (completed/accepted through kill + canary + every
    resize), ``slo_violation_minutes`` (integrated breach time), and
    ``scale_up_reaction_s`` (worst breach-start → capacity-added wall,
    gated only against captures with the same ``up_cooldown_s`` budget).
    Knobs: BENCH_AUTOSCALE_SECONDS (virtual soak length = diurnal
    period, default 240), BENCH_AUTOSCALE_PEAK_RPS / _TROUGH_RPS
    (default 200 / 20)."""
    from dcnn_tpu.serve.soak import run_diurnal_soak

    seconds = float(os.environ.get("BENCH_AUTOSCALE_SECONDS", "240"))
    peak = float(os.environ.get("BENCH_AUTOSCALE_PEAK_RPS", "200"))
    trough = float(os.environ.get("BENCH_AUTOSCALE_TROUGH_RPS", "20"))
    t0 = time.perf_counter()
    report, scaler, router = run_diurnal_soak(
        seconds=seconds, period=seconds, peak=peak, trough=trough)
    wall = time.perf_counter() - t0
    try:
        cfg = scaler.cfg
        reaction = report["reaction_max_s"]
        return {
            "soak_virtual_seconds": seconds,
            "wall_seconds": round(wall, 3),
            "peak_rps": peak,
            "trough_rps": trough,
            "peak_to_trough_x": round(peak / trough, 2),
            "availability": (round(report["availability"], 6)
                             if report["availability"] is not None
                             else None),
            "slo_violation_minutes": round(
                report["slo_violation_minutes"], 4),
            "scale_up_reaction_s": (round(reaction, 3)
                                    if reaction is not None else None),
            "accepted": report["accepted"],
            "completed": report["completed"],
            "typed_failures": report["typed_failures"],
            "silently_dropped": report["silently_dropped"],
            "scale_ups": report["scale_ups"],
            "scale_downs": report["scale_downs"],
            "peak_fleet": report["peak_fleet"],
            "final_fleet": report["final_fleet"],
            "up_cooldown_s": cfg.up_cooldown_s,
            "down_cooldown_s": cfg.down_cooldown_s,
            "slo_p99_ms": cfg.slo_p99_ms,
        }
    finally:
        router.shutdown(drain=False)
        for r in router.replicas().values():
            try:
                r.close()
            except Exception:
                pass


def decode_section():
    """BENCH_DECODE=1 ``decode`` block: continuous-batching autoregressive
    decode (dcnn_tpu/serve/decode.py) vs the naive batch-of-one baseline
    — SAME engine, SAME compiled sessions, SAME synthetic length mix, so
    the delta is pure scheduling. The naive path is ``decode_reference``
    run sequentially (each sequence decodes alone at batch bucket 1 —
    occupancy 1/max_slots by construction); the continuous path is the
    iteration-level scheduler admitting into free slots at step
    boundaries. Engine construction (compiles) is excluded from both
    timings.

    Regression-gated keys (obs/regress.py ``decode.*``):
    ``tokens_per_sec`` (generated tokens only), ``ttft_p99_ms``
    (submit → first generated token across the whole run), and
    ``slot_occupancy`` (mean active/max over steps) — guarded on
    ``max_slots``. Knobs: BENCH_DECODE_SLOTS (default 8),
    BENCH_DECODE_SEQS (default 24)."""
    import jax
    import numpy as np

    from dcnn_tpu.models import MHADecoder
    from dcnn_tpu.serve import (ContinuousBatcher, DecodeEngine,
                                decode_reference)
    from dcnn_tpu.serve.metrics import DecodeMetrics

    max_slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
    n_seqs = int(os.environ.get("BENCH_DECODE_SEQS", "24"))
    model = MHADecoder(vocab_size=32, embed_dim=32, num_heads=2,
                       num_layers=2, max_seq_len=64)
    params = model.init(jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    engine = DecodeEngine(model, params, max_slots=max_slots, page_size=8,
                          max_pages_per_seq=4, name="bench-decode")
    build_s = time.perf_counter() - t0

    # synthetic length mix: short chats to long generations, seeded so
    # every capture decodes the identical workload
    rng = np.random.default_rng(0)
    seqs = []
    for _ in range(n_seqs):
        plen = int(rng.integers(2, 12))
        max_new = int(rng.integers(4, engine.max_context - plen))
        prompt = rng.integers(0, model.vocab_size, size=plen).tolist()
        seqs.append((prompt, max_new))

    # naive baseline: strictly sequential batch-of-one (slot occupancy is
    # 1/max_slots per step by construction — one resident sequence)
    naive_tokens = 0
    naive_ttft = []
    t0 = time.perf_counter()
    for prompt, max_new in seqs:
        t_seq = time.perf_counter()
        got = decode_reference(engine, prompt, max_new_tokens=max_new)
        # first token lands after this sequence's prefill, which starts
        # only when every earlier sequence finished — that serialization
        # IS the baseline's TTFT story
        naive_ttft.append((t_seq - t0)
                          + (time.perf_counter() - t_seq) / max(len(got), 1))
        naive_tokens += len(got)
    naive_wall = time.perf_counter() - t0

    # continuous: same sequences, iteration-level scheduler, sync-driven
    metrics = DecodeMetrics()
    batcher = ContinuousBatcher(engine, metrics=metrics,
                                queue_capacity=n_seqs, start=False)
    futs = [batcher.submit(p, max_new_tokens=mn) for p, mn in seqs]
    t0 = time.perf_counter()
    while batcher.step():
        pass
    cont_wall = time.perf_counter() - t0
    results = [f.result(timeout=5) for f in futs]
    cont_tokens = sum(len(r) for r in results)
    s = metrics.snapshot()

    naive_ttft.sort()
    p99_i = min(int(0.99 * (len(naive_ttft) - 1) + 0.5),
                len(naive_ttft) - 1)
    naive_tps = naive_tokens / naive_wall if naive_wall > 0 else None
    cont_tps = cont_tokens / cont_wall if cont_wall > 0 else None
    return {
        "max_slots": max_slots,
        "sequences": n_seqs,
        "page_size": engine.page_size,
        "pool_pages": engine.pool.num_pages,
        "engine_build_s": round(build_s, 3),
        "generated_tokens": cont_tokens,
        "steps": s["steps"],
        "evictions": s["evictions"],
        "tokens_per_sec": round(cont_tps, 1) if cont_tps else None,
        "tokens_per_sec_naive": round(naive_tps, 1) if naive_tps else None,
        "speedup_x": (round(cont_tps / naive_tps, 2)
                      if cont_tps and naive_tps else None),
        "ttft_p99_ms": (round(s["ttft_p99_ms"], 3)
                        if s["ttft_p99_ms"] is not None else None),
        "ttft_p99_ms_naive": round(naive_ttft[p99_i] * 1e3, 3),
        "slot_occupancy": (round(s["slot_occupancy"], 4)
                           if s["slot_occupancy"] is not None else None),
        "slot_occupancy_naive": round(1 / max_slots, 4),
        "wall_seconds": round(cont_wall, 3),
        "wall_seconds_naive": round(naive_wall, 3),
    }


def faults_section():
    """BENCH_FAULTS=1: the measured cost of robustness — checkpoint
    save/restore wall for a real model's train state, sync vs async (the
    async number is what the step loop actually pays: the device_get
    snapshot + enqueue), plus verified-restore time. Small fixed model
    (the serving-scale digits CNN shape) so the number is comparable
    across runs; knob BENCH_FAULTS_REPS (default 5)."""
    import tempfile
    import time as _t

    import jax
    import numpy as np

    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.resilience import CheckpointManager
    from dcnn_tpu.train.trainer import create_train_state

    reps = int(os.environ.get("BENCH_FAULTS_REPS", "5"))
    model = (SequentialBuilder("bench_ckpt")
             .input((1, 28, 28))
             .conv2d(32, 3, 1, 1).batchnorm().activation("relu")
             .conv2d(32, 3, 1, 1).batchnorm().activation("relu")
             .maxpool2d(2).flatten().dense(128).dense(10)
             .build())
    opt = Adam(1e-3)
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    n_bytes = sum(np.asarray(l).nbytes for l in jax.tree_util.tree_leaves(
        {"p": ts.params, "s": ts.state, "o": ts.opt_state}))

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        sync_s, enqueue_s, restore_s = [], [], []
        for i in range(reps):
            t0 = _t.perf_counter()
            cm.save(2 * i + 1, model, ts.params, ts.state, ts.opt_state,
                    opt, {"rep": i})
            sync_s.append(_t.perf_counter() - t0)
            t0 = _t.perf_counter()
            cm.save_async(2 * i + 2, model, ts.params, ts.state,
                          ts.opt_state, opt, {"rep": i})
            enqueue_s.append(_t.perf_counter() - t0)  # the step loop's cost
            cm.wait()
            t0 = _t.perf_counter()
            r = cm.restore_latest()
            restore_s.append(_t.perf_counter() - t0)
            assert r is not None
        cm.close()
    return {
        "state_bytes": int(n_bytes),
        "reps": reps,
        "save_sync_s": round(min(sync_s), 4),
        "save_async_step_loop_s": round(min(enqueue_s), 4),
        "async_blocking_fraction": round(min(enqueue_s) / max(min(sync_s),
                                                              1e-9), 4),
        "restore_verified_s": round(min(restore_s), 4),
        "elastic": elastic_subsection(),
        "pipeline": pipeline_subsection(),
        "gray": gray_subsection(),
    }


def elastic_subsection():
    """The measured cost of surviving a host loss: a 2-peer in-process
    elastic DP fleet over loopback (parallel/elastic.py), one peer killed
    mid-epoch by a deterministic FaultPlan — reporting how long the
    survivor took to notice (detection), how long the checkpoint restore
    took, the whole reconfiguration wall, and how many optimizer steps
    were lost (re-run) to the rewind."""
    import tempfile
    import threading

    import numpy as np

    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data.loader import ArrayDataLoader, one_hot
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel import comm
    from dcnn_tpu.parallel.elastic import ElasticController, PeerSpec
    from dcnn_tpu.resilience import FaultPlan
    from dcnn_tpu.resilience.faults import InjectedCrash

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 32)).astype(np.float32)
    y = one_hot(rng.integers(0, 8, 64), 8)

    socks = [comm.listen(0, host="127.0.0.1") for _ in range(2)]
    peers = [PeerSpec(i, "127.0.0.1", s.getsockname()[1])
             for i, s in enumerate(socks)]
    ctls, results = {}, {}
    victim_plan = FaultPlan().arm("elastic.heartbeat", at=5,
                                  exc=InjectedCrash)

    with tempfile.TemporaryDirectory() as d:
        def runner(i):
            model = (SequentialBuilder("bench_elastic").input((32,))
                     .dense(64).activation("relu").dense(8).build())
            cfg = TrainingConfig(
                epochs=2, learning_rate=0.05, seed=3, snapshot_dir=None,
                elastic=True, elastic_microbatches=2,
                elastic_timeout_s=20.0, elastic_heartbeat_s=0.0,
                elastic_ckpt_steps=2, checkpoint_dir=d)
            ctl = ElasticController(
                model, SGD(0.05), "softmax_crossentropy",
                ArrayDataLoader(x, y, batch_size=16, seed=7),
                config=cfg, rank=i, peers=peers, listen_sock=socks[i],
                fault_plan=victim_plan if i == 1 else None)
            ctls[i] = ctl
            try:
                results[i] = ctl.fit(epochs=2)
            except InjectedCrash:
                results[i] = "crashed"

        # daemon: if a controller wedges, the hung-fleet error must still
        # let the bench process exit instead of blocking interpreter
        # shutdown on a non-daemon join
        threads = [threading.Thread(target=runner, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        if any(t.is_alive() for t in threads):
            return {"error": "elastic bench fleet hung"}

    stats = ctls[0].stats
    return {
        "peers": 2,
        "reconfigures": stats["reconfigures"],
        "detection_s": round(max(stats["detection_s"] or [0.0]), 4),
        "restore_wall_s": round(max(stats["restore_s"] or [0.0]), 4),
        "reconfigure_wall_s": round(max(stats["reconfigure_s"] or [0.0]), 4),
        "steps_lost": int(sum(stats["steps_lost"])),
        "world_after": ctls[0].world,
        "generation": ctls[0].gen,
    }


def pipeline_subsection():
    """The measured cost of surviving a stage loss: a real 3-stage TCP
    pipeline over loopback (parallel/distributed_pipeline.py +
    worker.py), stage 1 killed mid-batch by a deterministic FaultPlan —
    reporting how long the coordinator took to notice (detection), the
    whole repartition-and-resume wall, how many journaled batches the
    recovery replayed, and how many batches were lost (0 while the
    journal covers the checkpoint cadence)."""
    import tempfile
    import threading
    import time as _t

    import jax
    import numpy as np

    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel import (
        DistributedPipelineCoordinator, PipelineTimeouts, StageWorker, comm,
    )
    from dcnn_tpu.resilience import FaultPlan
    from dcnn_tpu.resilience.faults import InjectedCrash

    rng = np.random.default_rng(0)
    x_all = rng.normal(size=(8, 8, 16)).astype(np.float32)
    y_all = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (8, 8))]

    socks = [comm.listen(0, host="127.0.0.1") for _ in range(3)]
    addrs = [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    plans = [FaultPlan() for _ in range(3)]
    # dispatch sequence on stage 1: CONFIG@0, then per batch F,F,B,B,U
    # (+1 GATHER per commit) — at=18 lands mid-batch 4, one batch past
    # the batch-2 commit, so the recovery exercises the journal replay
    plans[1].arm("pipeline.stage_death", at=18, exc=InjectedCrash)
    workers = [StageWorker(0, listen_sock=s, fault_plan=p)
               for s, p in zip(socks, plans)]

    def _serve(w):
        try:
            w.serve()
        except InjectedCrash:
            pass  # the simulated kill — sockets already closed
    threads = [threading.Thread(target=_serve, args=(w,), daemon=True)
               for w in workers]
    for t in threads:
        t.start()

    model = (SequentialBuilder("bench_pipe").input((16,))
             .dense(32).activation("relu")
             .dense(24).activation("relu")
             .dense(4).build())
    with tempfile.TemporaryDirectory() as d:
        co = DistributedPipelineCoordinator(
            model, SGD(0.05, momentum=0.9), "softmax_crossentropy",
            workers=addrs, num_microbatches=2,
            timeouts=PipelineTimeouts(batch_s=60.0, heartbeat_s=0.05,
                                      respawn_s=0.5),
            checkpoint_dir=d, checkpoint_every=2)
        co.deploy_stages(jax.random.PRNGKey(0))
        t_batches = []
        recovery_idx = None
        try:
            for b in range(x_all.shape[0]):
                before = co.stats["recoveries"]
                t0 = _t.perf_counter()
                co.train_batch_sync(x_all[b], y_all[b], 0.05,
                                    jax.random.PRNGKey(b))
                t_batches.append(_t.perf_counter() - t0)
                if co.stats["recoveries"] > before:
                    recovery_idx = b
        except Exception as e:  # a hung fleet must not eat the capture
            return {"error": f"{type(e).__name__}: {e}"}
        finally:
            co.shutdown()
            for w in workers:
                w.stop()
    stats = co.stats
    # replay overhead: the recovery re-runs journaled batches inside the
    # batch call the death interrupted — compare THAT call's wall to a
    # clean steady-state batch (batch 0 pays the cold compile and the
    # recovery batch is excluded from the clean baseline)
    steady = [t for i, t in enumerate(t_batches)
              if i not in (0, recovery_idx)]
    clean = sorted(steady)[len(steady) // 2] if steady else 0.0
    recovery_batch = (t_batches[recovery_idx]
                      if recovery_idx is not None else 0.0)
    return {
        "stages": 3,
        "batches": x_all.shape[0],
        "recoveries": stats["recoveries"],
        "detection_s": round(max(stats["detection_s"] or [0.0]), 4),
        "repartition_wall_s": round(max(stats["recovery_s"] or [0.0]), 4),
        "replayed_batches": int(stats["replayed_batches"]),
        "batches_lost": int(stats["batches_lost"]),
        "respawns": stats["respawns"],
        "clean_batch_s": round(clean, 4),
        "recovery_batch_s": round(recovery_batch, 4),
        "replay_overhead_x": round(recovery_batch / max(clean, 1e-9), 2),
        "stages_after": co.num_stages,
        "generation": co.generation,
    }


def gray_subsection():
    """The measured cost of surviving a fail-SLOW host (gray failure,
    docs/reliability.md §11): a 3-peer loopback elastic fleet with
    ``slow_detect`` on and one peer running 10x slow via
    ``FaultPlan.slow`` — reporting how long the leader's detector took to
    convict (detection_s) and the eviction/reconfiguration wall — plus
    the hedged-serving probe: a 2-replica router with one stalled
    replica, client-measured p99 with hedging off vs on (the
    ``hedge_p99_ratio`` the regression gate reads) and the probation →
    rejoin round-trip."""
    out = {}
    out.update(_gray_elastic_probe())
    out.update(_gray_hedge_probe())
    return out


def _gray_elastic_probe():
    import tempfile
    import threading
    import time as _t

    import numpy as np

    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data.loader import ArrayDataLoader, one_hot
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel import comm
    from dcnn_tpu.parallel.elastic import ElasticController, PeerSpec
    from dcnn_tpu.resilience import FaultPlan

    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 32)).astype(np.float32)
    y = one_hot(rng.integers(0, 8, 96), 8)

    socks = [comm.listen(0, host="127.0.0.1") for _ in range(3)]
    peers = [PeerSpec(i, "127.0.0.1", s.getsockname()[1])
             for i, s in enumerate(socks)]
    ctls, results = {}, {}
    # rank 2 (never the leader) stalls 50 ms per step INSIDE the measured
    # local-compute wall — alive, beating, and dragging the fleet. An
    # absolute stall (not factor=) so the outlier ratio stays ~10x even
    # while everyone's EWMA is still decaying off the first-step compile
    # spike; small batches give the detector enough steps to convict.
    victim_plan = FaultPlan().slow("elastic.slow_peer", delay_s=0.05)

    with tempfile.TemporaryDirectory() as d:
        def runner(i):
            model = (SequentialBuilder("bench_gray").input((32,))
                     .dense(64).activation("relu").dense(8).build())
            cfg = TrainingConfig(
                epochs=8, learning_rate=0.05, seed=3, snapshot_dir=None,
                elastic=True, elastic_microbatches=6,
                elastic_timeout_s=20.0, elastic_heartbeat_s=0.0,
                elastic_ckpt_steps=2, checkpoint_dir=d,
                slow_detect=True, slow_dwell_s=0.2, slow_min_samples=2)
            ctl = ElasticController(
                model, SGD(0.05), "softmax_crossentropy",
                ArrayDataLoader(x, y, batch_size=12, seed=7),
                config=cfg, rank=i, peers=peers, listen_sock=socks[i],
                fault_plan=victim_plan if i == 2 else None)
            ctls[i] = ctl
            try:
                results[i] = ctl.fit(epochs=8)
            except BaseException as e:  # the victim's eviction surfaces here
                results[i] = repr(e)

        threads = [threading.Thread(target=runner, args=(i,), daemon=True)
                   for i in range(3)]
        t0 = _t.perf_counter()
        for t in threads:
            t.start()
        # detection = fleet start -> the leader's first conviction
        # (includes warmup/compile; the regress spec's atol absorbs that)
        t_detect = None
        deadline = _t.perf_counter() + 120
        while _t.perf_counter() < deadline:
            ctl = ctls.get(0)
            if ctl is not None and ctl.stats["stragglers_evicted"] > 0:
                t_detect = _t.perf_counter() - t0
                break
            if not any(t.is_alive() for t in threads):
                break
            _t.sleep(0.01)
        for t in threads[:2]:  # the evicted victim's thread may linger
            t.join(timeout=120)
        if any(t.is_alive() for t in threads[:2]):
            return {"error": "gray elastic fleet hung", "peers": 3}

    stats = ctls[0].stats
    return {
        "peers": 3,
        "stragglers_evicted": stats["stragglers_evicted"],
        "detection_s": round(t_detect, 4) if t_detect is not None else None,
        "evict_wall_s": round(max(stats["reconfigure_s"] or [0.0]), 4),
        "world_after": ctls[0].world,
    }


def _gray_hedge_probe():
    import threading as _threading
    import time as _t

    import jax
    import numpy as np

    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.resilience import FaultPlan
    from dcnn_tpu.resilience.slowness import SlownessConfig
    from dcnn_tpu.serve import (
        InferenceEngine, LocalReplica, Router, RouterMetrics)
    from dcnn_tpu.train.trainer import create_train_state

    model = (SequentialBuilder("bench_hedge").input((16,))
             .dense(32).activation("relu").dense(4).build())
    ts = create_train_state(model, Adam(1e-3), jax.random.PRNGKey(5))
    engines = [InferenceEngine.from_model(model, ts.params, ts.state,
                                          max_batch=8,
                                          name=f"hedge-probe-{i}")
               for i in range(3)]
    x = np.random.default_rng(9).normal(size=(1, 16)).astype(np.float32)
    slow_plan = FaultPlan().slow("serve.slow_replica", delay_s=0.1)

    def burst_p99(router, bursts=25, width=8, warmup=0):
        """Client-measured p99 over bursts of concurrent requests (the
        router's own p99 window spans phases, so it can't be the
        per-phase measurement — it IS the hedge-delay feed, though).
        ``warmup`` bursts run first with their walls discarded: the
        hedge delay needs ~20 completions of in-router p99 before it
        arms, so cold-start walls would measure the warm-up window,
        not the steady-state hedging benefit."""
        walls = []
        recording = False

        def one():
            t0 = _t.perf_counter()
            fut = router.submit(x)
            fut.result(timeout=60)
            if recording:
                walls.append(_t.perf_counter() - t0)

        for burst in range(warmup + bursts):
            recording = burst >= warmup
            ths = [_threading.Thread(target=one, daemon=True)
                   for _ in range(width)]
            for th in ths:
                th.start()
            while any(th.is_alive() for th in ths):
                router.check_replicas()  # pumps hedges + probation
                _t.sleep(0.002)
        walls.sort()
        return walls[min(int(0.99 * (len(walls) - 1) + 0.5),
                         len(walls) - 1)] * 1e3

    def mk_replicas(with_plan):
        return [LocalReplica(engines[0], name="hedge-r0", queue_capacity=64,
                             max_wait_ms=0.5,
                             fault_plan=slow_plan if with_plan else None),
                LocalReplica(engines[1], name="hedge-r1", queue_capacity=64,
                             max_wait_ms=0.5)]

    def run_phase(with_plan, **router_kw):
        reps = mk_replicas(with_plan)
        m = RouterMetrics()
        router = Router(reps, metrics=m, **router_kw)
        try:
            return burst_p99(router, warmup=3), m
        finally:
            router.shutdown(drain=False)
            for r in reps:
                try:
                    r.close()
                except Exception:
                    pass

    p99_healthy, _ = run_phase(False, hedge=False, slow_detect=False)
    p99_no_hedge, _ = run_phase(True, hedge=False, slow_detect=False)
    # mult 0.1 over the polluted in-router p99 (~the stall itself) keeps
    # the hedge delay well under the stall, so a stuck request re-issues
    # long before the slow replica would have answered
    p99_hedge, m = run_phase(True, hedge=True, hedge_multiplier=0.1,
                             hedge_min_s=0.02, slow_detect=False)
    snap = m.registry.snapshot()
    hedges = int(snap.get("serve_router_hedges_total", 0))

    # probation round-trip: detector on, no hedging — the slow replica
    # must be demoted, then rejoin once the fault clears. Three replicas,
    # not two: with exactly two scored components the fleet median is the
    # mean of both walls, so the MAD/ratio outlier test can never fire
    reps = mk_replicas(True) + [
        LocalReplica(engines[2], name="hedge-r2", queue_capacity=64,
                     max_wait_ms=0.5)]
    m2 = RouterMetrics()
    router = Router(reps, metrics=m2, hedge=False, slow_detect=True,
                    slow_config=SlownessConfig(min_peers=2, dwell_s=0.1,
                                               min_samples=2),
                    probation_cooldown_s=0.2)
    probation = rejoined = False
    try:
        deadline = _t.perf_counter() + 30
        while _t.perf_counter() < deadline and not probation:
            burst_p99(router, bursts=2)
            probation = any(st["probation"]
                            for st in router.replica_stats().values())
        if probation:
            slow_plan.unslow("serve.slow_replica")
            deadline = _t.perf_counter() + 30
            while _t.perf_counter() < deadline and not rejoined:
                burst_p99(router, bursts=1)
                rejoined = not any(st["probation"]
                                   for st in router.replica_stats().values())
    finally:
        router.shutdown(drain=False)
        for r in reps:
            try:
                r.close()
            except Exception:
                pass

    total = sum(int(v) for k, v in snap.items()
                if k.startswith("serve_router_completed_")) or None
    return {
        "hedge_replicas": 2,
        "p99_healthy_ms": round(p99_healthy, 2),
        "p99_no_hedge_ms": round(p99_no_hedge, 2),
        "p99_with_hedge_ms": round(p99_hedge, 2),
        "hedge_p99_ratio": round(p99_hedge / max(p99_no_hedge, 1e-9), 4),
        "hedges": hedges,
        "hedge_wins": int(snap.get("serve_router_hedge_wins_total", 0)),
        "hedge_rate": (round(hedges / total, 4)
                       if total else None),
        "probation_entered": probation,
        "probation_rejoined": rejoined,
    }


def main() -> None:
    import jax

    from dcnn_tpu.core.device import require_tpu
    from dcnn_tpu.utils import enable_compile_cache
    require_tpu("bench.py")
    enable_compile_cache()

    obs_on = os.environ.get("BENCH_OBS", "0") == "1"
    if obs_on:
        # enable BEFORE any instrumented section so engine compile spans,
        # feed spans, and train steps all land on one timeline
        from dcnn_tpu.obs import configure
        configure(enabled=True,
                  capacity=int(os.environ.get("BENCH_OBS_CAPACITY",
                                              "262144")))

    # monitoring-plane history (dcnn_tpu/obs/tsdb.py): a sampler thread
    # snapshots the registry for the WHOLE capture, so r06+ captures carry
    # time-resolved step-time / h2d series (telemetry_essentials.history)
    # next to the point-in-time numbers. BENCH_TSDB=0 opts out.
    tsdb_sampler = None
    if os.environ.get("BENCH_TSDB", "1") == "1":
        from dcnn_tpu.obs.tsdb import TimeSeriesStore, TsdbSampler
        tsdb_sampler = TsdbSampler(
            TimeSeriesStore(retention=4096),
            interval_s=float(os.environ.get("BENCH_TSDB_INTERVAL",
                                            "0.25"))).start()

    root = os.path.dirname(os.path.abspath(__file__))
    # batch 2048 default: bigger batches fill conv tiles better and
    # amortize weight-grad reductions. Not re-measured on the current
    # installation.
    batch = int(os.environ.get("BENCH_BATCH", "2048"))
    steps = int(os.environ.get("BENCH_STEPS", "40"))
    # 5 reps: dispatch and fence noise in a rep's wall is strictly
    # additive, so best-of-N is the estimator
    reps = int(os.environ.get("BENCH_REPS", "5"))
    data_format = os.environ.get("BENCH_FORMAT", "NHWC")
    profile_dir = os.environ.get("BENCH_PROFILE")
    # default 40 steps per dispatch through the in-jit multi-step loop
    # (one launch per chunk)
    chunk = int(os.environ.get("BENCH_CHUNK", "40"))

    (img_per_sec, sec_per_step, tflops, pipeline_ips, h2d_gbps,
     resident_ips, streaming_ips, overlap_eff, phases,
     streaming_timeline) = run_config(
        batch, steps, reps, data_format, profile_dir, chunk=chunk,
        pipeline=True)

    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    # the CPU (reachable only through JAX_PLATFORMS=cpu) has no row in a
    # device peak table and gets no MFU; an unknown TPU kind is an error
    peak = _peak_tflops(device_kind) if platform == "tpu" else None
    precision = os.environ.get("DCNN_PRECISION", "bf16").lower()
    mfu_formula = (round(tflops / peak, 4)
                   if peak and precision in ("fast", "bf16") else None)
    # headline `mfu` is now the XLA cost-analysis figure (the switch PR 6
    # deferred "next release"): what the compiled program actually costs,
    # post-fusion, instead of the model's forward_complexity()x3 estimate.
    # `mfu_formula` stays as the secondary key — it is the series the
    # r01-r05 trajectory gated on, and obs/regress.py gates it (with an
    # `mfu` fallback for pre-switch captures) alongside `mfu_analytic`.
    from dcnn_tpu.obs.xla import analytic_mfu
    xc = phases.get("xla_cost") or {}
    mfu_analytic = (analytic_mfu(xc.get("flops_per_img"), img_per_sec, peak)
                    if peak and precision in ("fast", "bf16") else None)
    mfu = (round(mfu_analytic, 4) if mfu_analytic is not None
           else mfu_formula)

    baseline_kind, baseline = _load_measured_baseline(root)
    if baseline is not None:
        vs_baseline = round(img_per_sec / baseline["img_per_sec"], 3)
    else:
        vs_baseline = None

    bench_model = os.environ.get("BENCH_MODEL", "resnet18")
    out = {
        "metric": f"{bench_model}_tiny_imagenet_train_images_per_sec",
        "value": round(img_per_sec, 1),
        "unit": "images/sec/chip",
        "vs_baseline": vs_baseline,
        "baseline": (
            {"kind": baseline_kind,
             "img_per_sec": baseline["img_per_sec"],
             "device": baseline.get("device_name"),
             "host": baseline.get("host")}
            if baseline is not None else "unmeasured"),
        "sec_per_step": round(sec_per_step, 4),
        "model_tflops_per_sec": round(tflops, 2),
        "mfu": mfu,
        "mfu_formula": mfu_formula,
        "mfu_analytic": (round(mfu_analytic, 4)
                         if mfu_analytic is not None else None),
        "roofline_bytes_per_flop": xc.get("bytes_per_flop"),
        "platform": platform,
        "device_kind": device_kind,
        "device_count": len(jax.devices()),
        "batch": batch,
        "format": data_format,
        "precision": precision,
        "steps_per_dispatch": chunk,
        # headline feed path: HBM-resident epochs (zero steady-state H2D)
        "pipeline_img_per_sec": (round(resident_ips, 1)
                                 if resident_ips is not None else None),
        "feed_efficiency": (round(resident_ips / img_per_sec, 3)
                            if resident_ips is not None else None),
        # host-feed path for datasets that exceed HBM (prefetch + chunked
        # staging)
        "host_feed_img_per_sec": (round(pipeline_ips, 1)
                                  if pipeline_ips is not None else None),
        "host_feed_efficiency": (round(pipeline_ips / img_per_sec, 3)
                                 if pipeline_ips is not None else None),
        "h2d_gbps": round(h2d_gbps, 3) if h2d_gbps is not None else None,
        # streaming feed for datasets > HBM (double-buffered uint8 shards;
        # wall ~ max(T_feed, T_compute) — overlap 1.0 = perfect hiding)
        "streaming_img_per_sec": (round(streaming_ips, 1)
                                  if streaming_ips is not None else None),
        "streaming_overlap_efficiency": (round(overlap_eff, 3)
                                         if overlap_eff is not None else None),
        "streaming_timeline": streaming_timeline,
        # per-phase walls of the headline measurement (variance accounting:
        # RESULTS.md "variance budget" section)
        "phases": phases,
    }

    # deployment-graph inference: BN-folded bf16 vs int8 PTQ (default-on so
    # the driver capture carries the number; BENCH_INT8=0 opts out)
    if os.environ.get("BENCH_INT8", "1") == "1":
        res = int8_inference_section(data_format)
        if res is None:  # roofline gate refused (see int8_inference_section)
            out["infer_bf16_img_per_sec"] = None
            out["infer_int8_img_per_sec"] = None
            out["int8_speedup_x"] = None
        else:
            bf16_ips, int8_ips = res
            out["infer_bf16_img_per_sec"] = round(bf16_ips, 1)
            out["infer_int8_img_per_sec"] = round(int8_ips, 1)
            out["int8_speedup_x"] = round(int8_ips / bf16_ips, 3)

    # uint8-first feed wire: measured wire bytes/rates + per-codec ratios
    # (default-on — sub-second; BENCH_WIRE=0 opts out)
    if os.environ.get("BENCH_WIRE", "1") == "1":
        out["feed_wire"] = feed_wire_section(streaming_timeline)

    # online serving: latency-vs-offered-load curve through the dynamic
    # batcher (opt-in — real open-loop traffic adds ~3x
    # BENCH_SERVE_SECONDS of wall per run)
    if os.environ.get("BENCH_SERVE", "0") == "1":
        out["serving"] = serve_section(data_format)

    # robustness has a measured cost: checkpoint save/restore overhead
    # (opt-in; cheap — a few MB of state written a few times)
    if os.environ.get("BENCH_FAULTS", "0") == "1":
        out["resilience"] = faults_section()

    # telemetry-driven autoscaler: the diurnal-soak gates (opt-in but
    # nearly free — the soak runs on a fake clock, zero real sleeps)
    if os.environ.get("BENCH_AUTOSCALE", "0") == "1":
        out["autoscale"] = autoscale_section()

    # continuous-batching decode vs naive batch-of-one (opt-in — a
    # ~dozen tiny-model compiles plus a few thousand decode steps)
    if os.environ.get("BENCH_DECODE", "0") == "1":
        out["decode"] = decode_section()

    if os.environ.get("BENCH_MATRIX"):
        from dcnn_tpu.core.precision import set_precision
        # the main run already measured the (data_format, precision) cell
        matrix = {f"{data_format}_{precision}": {
            "img_per_sec": round(img_per_sec, 1), "tflops": round(tflops, 2)}}
        for fmt in ("NHWC", "NCHW"):
            for prec in ("bf16", "fast", "parity"):
                if f"{fmt}_{prec}" in matrix:
                    continue
                set_precision(prec)  # read at trace time; run_config re-jits
                ips, _, tf, *_rest = run_config(batch, max(steps // 2, 5),
                                                2, fmt)
                matrix[f"{fmt}_{prec}"] = {
                    "img_per_sec": round(ips, 1), "tflops": round(tf, 2)}
        set_precision(precision)
        out["matrix"] = matrix

    # always-persisted telemetry essentials (unconditionally cheap — no
    # tracing required): compile counters, HBM watermark, h2d gauges, the
    # cost-analysis series. This is the block that makes BENCH_r06+
    # captures regression-gate-ready without the BENCH_OBS=1 trace
    # artifact.
    from dcnn_tpu.obs import get_registry
    from dcnn_tpu.obs.xla import sample_hbm

    reg = get_registry()
    hbm = sample_hbm(reg) or {}
    snap = reg.snapshot()
    out["telemetry_essentials"] = {
        "compile_total": snap.get("compile_total", 0),
        "compile_seconds_total": round(
            float(snap.get("compile_seconds_total", 0.0)), 3),
        "compile_cache_hit": out["phases"].get("compile_cache_hit"),
        "hbm_peak_bytes": hbm.get("hbm_peak_bytes"),
        "hbm_bytes_in_use": hbm.get("hbm_bytes_in_use"),
        "hbm_bytes_limit": hbm.get("hbm_bytes_limit"),
        "h2d_gbps": out.get("h2d_gbps"),
        "h2d_gbps_effective": (streaming_timeline or {}).get(
            "h2d_gbps_effective"),
        "wire_bytes_per_image": (streaming_timeline or {}).get(
            "wire_bytes_per_image"),
        "logical_gbps": (streaming_timeline or {}).get("logical_gbps"),
        "train_step_bytes_per_flop": snap.get("train_step_bytes_per_flop"),
        "serve_flops_per_sample": snap.get("serve_flops_per_sample"),
    }

    # goodput block (obs/goodput.py): attribute the capture's whole span
    # stream to buckets and classify it — the "where did the wall go"
    # verdict next to the raw numbers. Needs the tracer (BENCH_OBS=1);
    # absent otherwise, and the regress MetricSpec skips pre-r06
    # captures instead of lying (skip-not-lie).
    if obs_on:
        from dcnn_tpu.obs import get_tracer as _get_tracer
        from dcnn_tpu.obs.goodput import summarize as _goodput_summarize
        gp = _goodput_summarize(_get_tracer().events())
        out["telemetry_essentials"]["goodput"] = {
            "wall_s": round(gp["wall_s"], 3),
            "buckets": {b: round(v, 3)
                        for b, v in gp["buckets"].items()},
            "unattributed_s": round(gp["unattributed_s"], 3),
            "goodput_fraction": round(gp["goodput_fraction"], 4),
            "verdict": gp["verdict"],
        }

    # time-resolved history block: stop the capture-long sampler, take a
    # final pass (the last values always land), persist the JSONL next to
    # the capture, and embed the compact min/mean/max stats the regress
    # gate can anchor on
    if tsdb_sampler is not None:
        from dcnn_tpu.obs.tsdb import series_stats
        tsdb_sampler.stop()
        try:
            tsdb_sampler.sample_once()
        except Exception:
            pass  # a broken provider must not cost the capture
        store = tsdb_sampler.store
        history_path = os.environ.get("BENCH_TSDB_PATH",
                                      "/tmp/dcnn_bench_history.jsonl")
        try:
            store.persist(history_path)
        except OSError:
            history_path = None
        out["telemetry_essentials"]["history"] = {
            "path": history_path,
            "series": len(store.series_names()),
            "samples": store.samples,
            "step_s": series_stats(store.range("bench_step_seconds_last")),
            "h2d_gbps": series_stats(store.range("h2d_gbps")),
            "goodput_fraction": series_stats(
                store.range("goodput_fraction")),
        }

    if obs_on:
        from dcnn_tpu.obs import get_tracer
        from dcnn_tpu.obs.trace import merge_shards

        tracer = get_tracer()
        tracer.process_name = "bench"
        # sync ring-saturation accounting onto the registry BEFORE the
        # snapshot below (the scrape surfaces do the same per request)
        tracer.export_gauges(reg)
        trace_path = os.environ.get("BENCH_OBS_TRACE",
                                    "/tmp/dcnn_bench_trace.json")
        # the capture's trace evidence is the MERGED artifact: export the
        # JSONL shard (the per-process format distributed runs produce),
        # then run it through the same merge path a multi-process fleet
        # uses — trace_file stays Perfetto-loadable either way, and the
        # shard file next to it drops into a fleet-wide merge untouched
        shard_path = trace_path + ".shard.jsonl"
        tracer.export_jsonl(shard_path)
        merge_summary = merge_shards([shard_path], trace_path)
        out["telemetry"] = {
            "trace_file": trace_path,
            "trace_shards": [shard_path],
            "merged": {k: merge_summary[k]
                       for k in ("events", "trace_ids",
                                 "events_dropped_by_writers")},
            "events": len(tracer),
            "events_dropped": tracer.dropped,
            "spans": tracer.span_counts(),
            "metrics": get_registry().snapshot(),
        }

    # bench-history regression gate (dcnn_tpu/obs/regress.py;
    # benchmarks/compare.py is the standalone CLI): this run's numbers
    # against the trailing BENCH_r*.json window, embedded in the capture
    # so every BENCH_r06+ file carries its own verdict. Informational
    # here — the CLI is where a CI job turns it into an exit code.
    from dcnn_tpu.obs.regress import gate_current
    out["regressions"] = gate_current(out, root)

    print(json.dumps(out))
    failed = _failed_sections(out)
    if failed:
        sys.exit(f"bench.py: section(s) failed or were skipped: "
                 f"{', '.join(failed)}")


if __name__ == "__main__":
    # the script's default, set before dcnn_tpu is first imported (main);
    # not at import: a test that imports bench would hand it to every
    # subprocess it starts later
    os.environ.setdefault("DCNN_PRECISION", "bf16")
    main()
