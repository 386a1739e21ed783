"""CLI: ``python -m dcnn_tpu.aot``.

Operational surface for the executable cache:

- default: list committed entries (key, label, avals, size, age, hits);
- ``--gc [--keep K]``: keep-K LRU sweep;
- ``--prewarm SRC``: populate a cache before deploy — build an
  :class:`~dcnn_tpu.serve.engine.InferenceEngine` (every serve bucket
  compiles and commits) from ``SRC`` = a ``save_checkpoint`` directory
  or a model-zoo name (``resnet18_tiny_imagenet`` …), optionally plus a
  train-step executable with ``--train-batch``. A router fleet spun up
  against the same cache dir then starts in seconds (docs/deployment.md
  §5).

Exit codes (the ``dcnn_tpu.analysis`` convention): 0 = success, 1 = the
requested operation failed, 2 = usage/internal error. ``--json`` emits
machine-readable reports.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .warm import aot_dir, enabled_root


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m dcnn_tpu.aot",
        description="AOT executable cache: list / gc / prewarm")
    p.add_argument("--dir", default=None,
                   help="cache ROOT (executables under <dir>/aot); "
                        "default: AOT_CACHE, then DCNN_COMPILE_CACHE, "
                        "then <checkout>/.jax_cache")
    p.add_argument("--json", action="store_true",
                   help="emit JSON instead of a table")
    p.add_argument("--gc", action="store_true",
                   help="remove all but the --keep most-recently-used "
                        "entries")
    p.add_argument("--keep", type=int, default=None,
                   help="retention for --gc (default: AOT_CACHE_KEEP "
                        "env or 64)")
    p.add_argument("--prewarm", metavar="SRC", default=None,
                   help="populate the cache: SRC is a checkpoint dir "
                        "(train.save_checkpoint layout) or a model-zoo "
                        "name")
    p.add_argument("--max-batch", type=int, default=32,
                   help="serve bucket cap for --prewarm (default 32)")
    p.add_argument("--no-fold", action="store_true",
                   help="skip BN folding in the prewarmed serve graph")
    p.add_argument("--train-batch", type=int, default=0,
                   help="also prewarm a train-step executable at this "
                        "batch size (0 = serve buckets only)")
    p.add_argument("--seed", type=int, default=0,
                   help="init seed for zoo models (default 0)")
    return p


def _resolve_root(arg_dir):
    explicit = enabled_root(arg_dir)
    if explicit is not None:
        return explicit
    from ..utils.compile_cache import resolve_cache_root
    return resolve_cache_root()


def _load_source(src: str, seed: int):
    """(model, params, state) from a checkpoint dir or a zoo name."""
    import jax

    if os.path.isdir(src):
        from ..train.checkpoint import load_checkpoint
        model, params, state, _, _, _ = load_checkpoint(src, seed=seed)
        return model, params, state
    from ..models import MODEL_ZOO, create_model
    if src not in MODEL_ZOO:
        known = ", ".join(sorted(MODEL_ZOO))
        raise ValueError(f"{src!r} is neither a checkpoint dir nor a "
                         f"zoo model (known: {known})")
    model = create_model(src)
    params, state = model.init(jax.random.PRNGKey(seed))
    return model, params, state


def _prewarm(cache, args) -> dict:
    import jax

    from ..serve.engine import InferenceEngine
    model, params, state = _load_source(args.prewarm, args.seed)
    engine = InferenceEngine.from_model(
        model, params, state, fold=not args.no_fold,
        max_batch=args.max_batch, warmup=False, aot_cache=cache)
    report = {
        "source": args.prewarm,
        "buckets": engine.bucket_sizes,
        "bucket_stats": {str(b): s for b, s in
                         engine.compile_stats.items()},
    }
    if args.train_batch > 0:
        from ..optim import Adam
        from ..ops.losses import softmax_cross_entropy
        from ..train import make_train_step
        from ..train.trainer import create_train_state
        from .keys import digest, train_step_key_material
        from .warm import warm_or_compile
        import jax.numpy as jnp

        opt = Adam(1e-3)
        ts = create_train_state(model, opt, jax.random.PRNGKey(args.seed))
        step = make_train_step(model, softmax_cross_entropy, opt)
        b = args.train_batch
        n_out = model.output_shape()[-1]
        xx = jax.ShapeDtypeStruct((b, *model.input_shape), jnp.float32)
        yy = jax.ShapeDtypeStruct((b, n_out), jnp.float32)
        rr = jax.ShapeDtypeStruct((2,), jnp.uint32)
        # the canonical Trainer key material (lr is stripped inside, so
        # the prewarmed entry hits for ANY base learning rate)
        cfg = digest(train_step_key_material(model, opt,
                                             softmax_cross_entropy))
        _, info = warm_or_compile(step, ts, xx, yy, rr, 1e-3, cache=cache,
                                  what="train", config=cfg, donate=(0,))
        report["train_step"] = info
    return report


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        root = _resolve_root(args.dir)
    except Exception as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from .cache import ExecutableCache
    try:
        cache = ExecutableCache(aot_dir(root), keep=args.keep)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.prewarm:
        try:
            report = _prewarm(cache, args)
        except Exception as e:
            print(f"prewarm failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps({"dir": cache.root, "prewarm": report},
                             indent=2))
        else:
            hits = sum(1 for s in report["bucket_stats"].values()
                       if s.get("aot_hit"))
            print(f"prewarmed {args.prewarm}: serve buckets "
                  f"{report['buckets']} ({hits} already cached) "
                  f"-> {cache.root}")
            if "train_step" in report:
                ti = report["train_step"]
                state = "hit" if ti["hit"] else "compiled+committed"
                print(f"train step @ batch {args.train_batch}: {state}")
        return 0

    if args.gc:
        removed = cache.gc(args.keep)
        if args.json:
            print(json.dumps({"dir": cache.root, "removed": removed,
                              "kept": len(cache.entries())}))
        else:
            print(f"gc: removed {removed}, kept {len(cache.entries())} "
                  f"({cache.root})")
        return 0

    rows = cache.entries()
    if args.json:
        print(json.dumps({"dir": cache.root, "entries": rows}, indent=2))
        return 0
    if not rows:
        print(f"{cache.root}: empty")
        return 0
    print(f"{cache.root}: {len(rows)} entries")
    print(f"{'key':16}  {'what':10} {'size':>10}  {'age':>8}  "
          f"{'hits':>5}  avals")
    for r in rows:
        if "error" in r:
            print(f"{r['key'][:16]:16}  {r['error']}")
            continue
        size = r.get("size") or 0
        mb = f"{size / 1e6:.1f}MB"
        age = r.get("age_s") or 0.0
        age_h = f"{age / 3600:.1f}h" if age >= 3600 else f"{age:.0f}s"
        print(f"{r['key'][:16]:16}  {r.get('what', ''):10} {mb:>10}  "
              f"{age_h:>8}  {r.get('hits', 0):>5}  {r.get('avals', '')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
