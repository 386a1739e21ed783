"""chip_smoke.py — the quickest proof that the program still starts on the chip.

    python3 chip_smoke.py

One process, no arguments, no network, no git; data and weights come from
seeds. It drives the main paths once at the full width of ResNet-18
Tiny-ImageNet through the entry points a user calls, checks what comes out,
and stops at the first phase that fails (no phase is wrapped in a handler
that lets the run go on):

1. trainer  — ``examples/tiny_imagenet_trainer.train`` (``common.setup``,
   ``TrainingConfig.load_from_env``, AdamW + WarmupCosineAnnealing,
   ``PrefetchLoader``) in bf16 at batch 2048: a few optimizer steps and one
   evaluation pass, then one ``STEPS_PER_DISPATCH`` chunk through
   ``make_multi_step``;
2. server   — the same model through ``InferenceEngine.from_model`` (folded
   bf16, int8 w8a8, int8 with fp32 glue), each behind a ``DynamicBatcher``
   answering a few dozen requests of mixed sizes, against a direct
   ``model.apply`` of the same transformed graph; bit-identity across
   buckets wherever the engine promises it;
3. kernels  — the Pallas ``flash_attention`` forward and gradient at two
   geometries against ``attention``, with the Pallas custom calls asserted
   from the compiled text;
4. four chips — data-parallel step, compiled GPipe/1F1B pipeline and the
   in-process pipeline coordinator, when four devices are there.

It sets no platform and fails at once unless ``jax.default_backend()`` is
``tpu``. The last line of stdout is one JSON object naming the device as JAX
reports it. ``tests/test_bring_up.py`` calls the same phase functions at
tiny sizes under ``JAX_PLATFORMS=cpu`` (Pallas in interpret mode).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import tempfile
import threading
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
# the example drivers are plain scripts that import each other by module
# name (``from common import ...``); the phases do the same
for _p in (os.path.join(_ROOT, "examples"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def _environ(**values):
    """The env a user would export before the example trainer, restored
    afterwards (the tests call the phases in-process)."""
    old = {k: os.environ.get(k) for k in values}
    os.environ.update({k: str(v) for k, v in values.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _precision(mode: str):
    from dcnn_tpu.core.precision import get_precision_mode, set_precision

    old = get_precision_mode()
    set_precision(mode)
    try:
        yield
    finally:
        set_precision(old)


def _memory_stat(device, key: str):
    """``device.memory_stats()[key]``; the TPU must report it (the CPU
    backend, which only the tests run this on, has no memory stats)."""
    stats = device.memory_stats()
    if device.platform == "tpu":
        check(stats is not None and key in stats,
              f"{device}: memory_stats() reports no {key}")
    return None if not stats else stats.get(key)


def _leaf_devices(tree) -> set:
    import jax

    return {d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------

def _fit(model_name, batch, steps, steps_per_dispatch, snapshot_dir):
    """One run of the example trainer on ``steps`` seeded synthetic batches
    (one epoch) plus one evaluation batch; returns what it checked."""
    import math

    import jax
    import numpy as np
    import tiny_imagenet_trainer
    from common import setup

    from dcnn_tpu.data import SyntheticClassificationLoader
    from dcnn_tpu.models import create_model

    with _environ(BATCH_SIZE=batch, EPOCHS=1, PROGRESS_INTERVAL=1,
                  STEPS_PER_DISPATCH=steps_per_dispatch,
                  SNAPSHOT_DIR=snapshot_dir):
        cfg = setup(f"chip_smoke trainer (steps_per_dispatch="
                    f"{steps_per_dispatch})")
    check(cfg.batch_size == batch
          and cfg.steps_per_dispatch == steps_per_dispatch,
          f"TrainingConfig.load_from_env did not pick up the run: {cfg}")
    model = create_model(model_name)
    shape, classes = tuple(model.input_shape), model.output_shape()[0]
    train = SyntheticClassificationLoader(
        steps * batch, shape, classes, batch_size=batch, seed=cfg.seed)
    val = SyntheticClassificationLoader(
        batch, shape, classes, batch_size=batch, seed=cfg.seed + 1)
    # the state the trainer starts from (same model, same key), to prove
    # the optimizer moved it
    p0, _ = model.init(jax.random.PRNGKey(cfg.seed))
    p0 = jax.tree_util.tree_map(np.asarray, p0)

    ts, trainer = tiny_imagenet_trainer.train(cfg, model_name, train, val)

    platform = jax.devices()[0].platform
    h = trainer.history[-1]
    # the epoch mean is finite iff every step's loss was: a NaN or an inf
    # in any term survives the weighted sum
    check(math.isfinite(h["train_loss"]),
          f"train loss not finite on every step: {h['train_loss']}")
    check(h["val_loss"] is not None and math.isfinite(h["val_loss"])
          and 0.0 <= h["val_acc"] <= 1.0, f"evaluation pass: {h}")
    check(int(ts.step) == steps, f"{int(ts.step)} optimizer steps, "
                                 f"expected {steps}")
    check((trainer.multi_step is not None) == (steps_per_dispatch > 1),
          "steps_per_dispatch did not select the in-jit multi-step loop")
    changed = [bool(np.any(np.asarray(a) != b)) for a, b in zip(
        jax.tree_util.tree_leaves(ts.params), jax.tree_util.tree_leaves(p0))]
    check(any(changed), "no parameter changed")
    where = _leaf_devices(ts)
    check(where and all(d.platform == platform for d in where),
          f"train state leaves live on {sorted(map(str, where))}, "
          f"not all on {platform}")
    peak = _memory_stat(jax.devices()[0], "peak_bytes_in_use")
    return {"steps": steps, "train_loss": round(h["train_loss"], 4),
            "val_loss": round(h["val_loss"], 4), "val_acc": h["val_acc"],
            "params_changed": f"{sum(changed)}/{len(changed)} leaves",
            "state_on": sorted(map(str, where)), "peak_bytes_in_use": peak}


def phase_trainer(model_name="resnet18_tiny_imagenet", batch=2048, steps=3,
                  chunk=4, precision="bf16"):
    with _precision(precision), tempfile.TemporaryDirectory() as snap:
        stepwise = _fit(model_name, batch, steps, 1, snap)
        chunked = _fit(model_name, batch, chunk, chunk, snap)
    return {"per-step": stepwise, "one chunk": chunked}


# ---------------------------------------------------------------------------
# phase 2: the classification server
# ---------------------------------------------------------------------------

def _serve(label, engine, reference, probe, rng, n_burst, float_tol):
    """Requests through a DynamicBatcher over ``engine``: one request at a
    time at every bucket size (so every bucket provably runs, each carrying
    ``probe`` as row 0), then a concurrent burst of mixed sizes."""
    import numpy as np

    from dcnn_tpu.serve.batcher import DynamicBatcher

    shape = engine.input_shape
    batcher = DynamicBatcher(engine, max_wait_ms=2.0,
                             queue_capacity=4 * engine.max_batch * n_burst)
    answered = []  # (x, logits)

    def ask(x):
        y = batcher.submit(x).result(timeout=300)
        answered.append((x, np.asarray(y)))
        return answered[-1][1]

    probe_rows = []
    for b in engine.bucket_sizes:  # alone in the queue -> runs in bucket b
        x = rng.normal(size=(b, *shape)).astype(np.float32)
        x[0] = probe
        probe_rows.append(ask(x)[0])

    sizes = [int(s) for s in rng.integers(
        1, max(engine.max_batch // 2, 1) + 1, size=n_burst)]
    burst = [rng.normal(size=(s, *shape)).astype(np.float32) for s in sizes]
    for x in burst[::3]:
        x[0] = probe
    threads = [threading.Thread(target=ask, args=(x,)) for x in burst]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    check(not any(t.is_alive() for t in threads), f"{label}: a client hung")
    batcher.drain(timeout=300)
    snap = batcher.metrics.snapshot()

    n_requests = len(engine.bucket_sizes) + n_burst
    check(len(answered) == n_requests,
          f"{label}: {len(answered)} of {n_requests} requests answered")
    check(snap["requests_shed"] == 0, f"{label}: {snap['requests_shed']} shed")
    rows = sum(x.shape[0] for x, _ in answered)
    check(snap["requests_completed"] == rows == snap["requests_submitted"],
          f"{label}: rows dropped: {snap}")
    # one direct apply over every answered row (one shape, one compile)
    want = reference(np.concatenate([x for x, _ in answered]))
    worst, off = 0.0, 0
    for x, y in answered:
        check(y.shape[0] == x.shape[0] and np.all(np.isfinite(y)),
              f"{label}: bad logits {y.shape}")
        worst = max(worst, _rel_err(y, want[off:off + x.shape[0]]))
        off += x.shape[0]
    check(worst <= float_tol,
          f"{label}: logits differ from a direct apply by {worst:.3e} "
          f"(tolerance {float_tol:.0e})")
    # the same sample in every bucket (and wherever the burst put it)
    rows0 = probe_rows + [y[0] for x, y in answered[len(probe_rows):]
                          if np.array_equal(x[0], probe)]
    spread = max(float(np.max(np.abs(r - rows0[0]))) for r in rows0)
    if engine.batch_invariant:
        check(spread == 0.0,
              f"{label}: the same sample got different logits in different "
              f"buckets, up to {spread:.3e} apart (engine.batch_invariant "
              f"promises bit-identity)")
    return {"requests": n_requests, "rows": rows,
            "buckets_run": engine.bucket_sizes, "batches": snap["batches"],
            "max_err_vs_direct_apply": float(f"{worst:.3e}"), "shed": 0,
            "batch_invariant": engine.batch_invariant,
            "same_sample_served": len(rows0),
            "same_sample_spread_across_buckets": spread}


def phase_server(model_name="resnet18_tiny_imagenet", max_batch=32,
                 n_burst=40, precision="bf16", seed=0):
    """Three engines over one seeded model: the BN-folded float graph and
    the int8 w8a8 graph in ``precision`` (the graphs of record), then the
    int8 graph with fp32 glue — the configuration in which the engine
    promises bit-identical logits across buckets on every backend
    (``serve/engine.py``)."""
    import jax
    import numpy as np

    from dcnn_tpu.models import create_model
    from dcnn_tpu.nn import fold_batchnorm, quantize_model
    from dcnn_tpu.serve.engine import InferenceEngine

    model = create_model(model_name)
    params, state = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    shape = tuple(model.input_shape)
    calib = rng.normal(size=(64, *shape)).astype(np.float32)
    probe = rng.normal(size=shape).astype(np.float32)
    out = {}
    # (label, precision of the float glue, int8?, must promise invariance?)
    for label, mode, int8, promised in (
            ("folded", precision, False, False),
            ("int8", precision, True, False),
            ("int8 fp32-glue", "parity", True, True)):
        with _precision(mode):
            kw = dict(fold=True, int8_calib=calib) if int8 else dict(fold=True)
            gm, gp, gs = (quantize_model(model, params, state, calib) if int8
                          else fold_batchnorm(model, params, state))
            engine = InferenceEngine.from_model(
                model, params, state, max_batch=max_batch, name=label, **kw)
            check(engine.batch_invariant or not promised,
                  f"{label}: this engine must promise batch invariance")
            direct = jax.jit(
                lambda x, gm=gm, gp=gp, gs=gs:
                gm.apply(gp, gs, x, training=False)[0])
            out[label] = _serve(
                label, engine, lambda x: np.asarray(direct(x)), probe, rng,
                n_burst, float_tol=3e-2 if mode == "bf16" else 1e-4)
    return out


# ---------------------------------------------------------------------------
# phase 3: the flash-attention kernels
# ---------------------------------------------------------------------------

def phase_kernels(geometries=((4, 8, 4096, 64), (8, 8, 8192, 64)),
                  interpret=False, dtype="bfloat16", seed=0):
    """Causal ``flash_attention`` forward and gradient per (b, h, S, d),
    against ``attention`` on as many leading heads as a materialised S x S
    score matrix allows. The second default geometry is the two backward
    kernels' scoped-VMEM frontier (``ops/attention._flash_backward``); both
    now take the fused backward, whose VMEM limit follows its tiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dcnn_tpu.ops.attention import (_fused_backward_fits, attention,
                                        flash_attention)

    out = {}
    for (b, h, s, d) in geometries:
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v, w = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                      .astype(dtype) for kk in keys)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   interpret=True if interpret else None)

        def loss(fn, q, k, v, w):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * w.astype(jnp.float32))

        fwd = jax.jit(flash)
        bwd = jax.jit(jax.grad(lambda q, k, v: loss(flash, q, k, v, w),
                               argnums=(0, 1, 2)))
        if not interpret:
            # the router is not trusted: the compiled program itself must
            # hold the forward and the fused (or dQ and dK/dV) Mosaic kernels
            text = bwd.lower(q, k, v).compile().as_text()
            n_calls = text.count('custom_call_target="tpu_custom_call"')
            want = 2 if _fused_backward_fits(s, d) else 3
            check(n_calls >= want,
                  f"b{b} h{h} S{s} d{d}: {n_calls} tpu_custom_call(s) in the "
                  f"compiled gradient, expected {want} (Pallas path not taken)")
        o = fwd(q, k, v)
        grads = bwd(q, k, v)
        jax.block_until_ready((o, grads))
        check(o.shape == q.shape and bool(jnp.all(jnp.isfinite(
            o.astype(jnp.float32)))), f"S{s}: forward not finite")

        # reference on the leading heads whose S x S fp32 scores fit 512 MB
        n_ref = max(1, min(h, (512 << 20) // (4 * s * s)))
        cut = lambda a: a[:1, :n_ref].astype(jnp.float32)
        qr, kr, vr, wr = cut(q), cut(k), cut(v), cut(w)
        ref = lambda q, k, v: attention(q, k, v, causal=True)
        o_ref = jax.jit(ref)(qr, kr, vr)
        g_ref = jax.jit(jax.grad(lambda q, k, v: loss(ref, q, k, v, wr),
                                 argnums=(0, 1, 2)))(qr, kr, vr)
        tol = 3e-2 if dtype == "bfloat16" else 1e-4
        errs = {"out": _rel_err(cut(o), o_ref)}
        for name, g, gr in zip(("dq", "dk", "dv"), grads, g_ref):
            check(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))),
                  f"S{s}: {name} not finite")
            errs[name] = _rel_err(cut(g), gr)
        check(max(errs.values()) <= tol,
              f"b{b} h{h} S{s} d{d}: flash vs attention {errs} "
              f"(tolerance {tol:.0e})")
        out[f"b{b} h{h} S{s} d{d}"] = {
            "pallas_custom_calls": None if interpret else n_calls,
            "heads_compared": n_ref,
            "max_rel_err": {k_: float(f"{v_:.2e}") for k_, v_ in errs.items()}}
    return out


# ---------------------------------------------------------------------------
# phase 4: four chips in one process
# ---------------------------------------------------------------------------

def phase_four_chips(model_name="resnet18_tiny_imagenet", dp_batch=2048,
                     microbatch=64, precision="bf16", seed=0):
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pipeline_trainer
    from common import setup
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dcnn_tpu.core.mesh import DATA_AXIS, STAGE_AXIS, make_mesh
    from dcnn_tpu.models import create_model
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.optim import SGD, AdamW
    from dcnn_tpu.parallel import (HeteroCompiledPipeline,
                                   make_data_parallel_train_step, replicate)
    from dcnn_tpu.parallel.pipeline import train_pipeline_epoch
    from dcnn_tpu.train.trainer import TrainState, create_train_state

    devs = jax.devices()[:4]
    check(len(devs) == 4, f"needs 4 devices, found {len(devs)}")
    platform = devs[0].platform
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    out = {}

    def on_all_four(tree, what):
        where = _leaf_devices(tree)
        check(where == set(devs),
              f"{what}: parameters live on {sorted(map(str, where))}, "
              f"expected all of {[str(d) for d in devs]}")
        in_use = [_memory_stat(d, "bytes_in_use") for d in devs]
        check(platform != "tpu" or all(in_use),
              f"{what}: a chip shows no bytes in use: {in_use}")
        return in_use

    with _precision(precision):
        # (a) the phase-1 step, data-parallel over a 4-device 'data' mesh
        mesh = make_mesh((4,), (DATA_AXIS,), devices=devs)
        model = create_model(model_name)
        shape, classes = tuple(model.input_shape), model.output_shape()[0]
        opt = AdamW(1e-3, weight_decay=1e-4)
        ts = create_train_state(model, opt, key)
        ts = TrainState(*(replicate(t, mesh) for t in (
            ts.params, ts.state, ts.opt_state, ts.step)))
        x = jax.device_put(
            rng.normal(size=(dp_batch, *shape)).astype(np.float32),
            NamedSharding(mesh, P(DATA_AXIS)))
        y = jax.device_put(
            np.eye(classes, dtype=np.float32)[
                rng.integers(0, classes, dp_batch)],
            NamedSharding(mesh, P(DATA_AXIS)))
        step = make_data_parallel_train_step(model, softmax_cross_entropy,
                                             opt, mesh)
        ts, loss, _ = step(ts, x, y, key, 1e-3)
        loss = float(loss)
        check(math.isfinite(loss), f"data-parallel loss {loss}")
        out["data_parallel"] = {
            "loss": round(loss, 4),
            "bytes_in_use": on_all_four(ts.params, "data-parallel")}

        # (b) compiled pipeline: GPipe and 1F1B in one jit each, 4 stages x
        # 4 microbatches, same init and batch -> same loss
        smesh = make_mesh((4,), (STAGE_AXIS,), devices=devs)
        hx = jnp.asarray(rng.normal(
            size=(4, microbatch, *shape)).astype(np.float32))
        hy = jnp.asarray(np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, size=(4, microbatch))])
        sgd = SGD(0.05, momentum=0.9)
        losses = {}
        for name in ("gpipe", "1f1b"):
            pipe = HeteroCompiledPipeline(create_model(model_name), 4, 4,
                                          smesh)
            fp, fs = pipe.init(key)
            make = (pipe.make_train_step if name == "gpipe"
                    else pipe.make_train_step_1f1b)
            fp, _, fs, hloss, _ = make(softmax_cross_entropy, sgd)(
                fp, sgd.init(fp), fs, hx, hy, jax.random.PRNGKey(2),
                jnp.float32(0.05))
            losses[name] = float(hloss)
            check(math.isfinite(losses[name]), f"{name} loss {hloss}")
            in_use = on_all_four(fp, f"compiled {name}")
        tol = 1e-2 if precision == "bf16" else 1e-5
        check(abs(losses["1f1b"] - losses["gpipe"])
              <= tol * max(1.0, abs(losses["gpipe"])),
              f"1F1B loss {losses['1f1b']} != GPipe loss {losses['gpipe']}")
        out["compiled_pipeline"] = {**losses, "bytes_in_use": in_use}

        # (c) the host-driven coordinator as examples/pipeline_trainer.py
        # builds it, one batch
        batch = 4 * microbatch
        with _environ(BATCH_SIZE=batch, NUM_MICROBATCHES=4):
            cfg = setup("chip_smoke pipeline_trainer (NUM_STAGES=4)")
        coord, loader = pipeline_trainer.build(cfg, model_name, 4,
                                               num_samples=batch)
        ploss, _ = train_pipeline_epoch(coord, loader, cfg.learning_rate,
                                        jax.random.PRNGKey(1), "semi_async")
        check(math.isfinite(ploss), f"pipeline coordinator loss {ploss}")
        out["pipeline_coordinator"] = {
            "loss": round(float(ploss), 4),
            "partitions": [list(p) for p in coord.partitions],
            "bytes_in_use": on_all_four(
                [st.params for st in coord.stages], "pipeline coordinator")}
        coord.close()
    return out


# ---------------------------------------------------------------------------

def run_phase(name, fn, cache_dir, **kw):
    from dcnn_tpu.utils.compile_cache import cache_entries

    print(f"\n##### phase {name} #####", flush=True)
    before = cache_entries(cache_dir)
    t0 = time.perf_counter()
    checked = fn(**kw)
    wall = time.perf_counter() - t0
    # entries are "<module name>-<key>-cache": say which programs compiled
    added = collections.Counter(
        n.rsplit("-", 2)[0] for n in cache_entries(cache_dir) - before)
    print(f"phase {name}: ok  wall {wall:.1f} s  persistent-cache entries "
          f"added {sum(added.values())} {dict(added) or ''}", flush=True)
    print(f"phase {name} checked: {json.dumps(checked)}", flush=True)


def main() -> int:
    if not os.path.isdir(os.path.join(_ROOT, "dcnn_tpu")):
        print("chip_smoke.py drives the program of its own checkout; there "
              "is no dcnn_tpu/ next to it", file=sys.stderr)
        return 1
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke.py: JAX came up on backend {backend!r} "
              f"({jax.devices()[0].device_kind}), not 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    from importlib import metadata

    import jaxlib

    from dcnn_tpu import native
    from dcnn_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    native.available()
    print(f"device: {json.dumps(device)}")
    print(f"versions: jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {metadata.version('libtpu')}  python "
          f"{sys.version.split()[0]}")
    print(f"compile cache: {cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    print(f"native library: {native.status()}", flush=True)

    t0 = time.perf_counter()
    run_phase("1 trainer", phase_trainer, cache_dir)
    run_phase("2 server", phase_server, cache_dir)
    run_phase("3 kernels", phase_kernels, cache_dir)
    if len(jax.devices()) >= 4:
        run_phase("4 four chips", phase_four_chips, cache_dir)
    else:
        print(f"\nphase 4 four chips: not run: {len(jax.devices())} "
              f"device(s)", flush=True)
    print(f"\nall phases ok in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
