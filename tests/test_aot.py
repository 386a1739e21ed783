"""AOT executable cache tests (dcnn_tpu/aot/).

Contracts pinned here:

- key derivation is stable across processes and sensitive to donation /
  precision / config (an under-keyed hit would serve the wrong program);
- commit/lookup round-trips through the checksum MANIFEST; a bit-flipped
  payload is quarantined and transparently recompiled (the
  CheckpointManager torn-checkpoint contract, applied to executables);
- a stale-version entry (jaxlib bump) is a miss, never a crash;
- keep-K GC retains the most-recently-used entries;
- ``aot.commit`` / ``aot.load`` FaultPlan points drive the failure paths
  (crash-before-commit leaves no entry; a load fault degrades to a
  recompile);
- the warm path is bit-identical to the compiled path, and — the
  acceptance headline — an executable compiled and cached in process A
  is loaded in fresh process B with **no compile events** and
  bit-identical outputs, for both the train step and a serve engine's
  bucket set;
- Trainer / InferenceEngine / pipeline wiring is on only when asked, and
  default runs see the exact pre-subsystem behavior.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcnn_tpu.aot import (ExecutableCache, WarmCallable, cache_key, digest,
                          maybe_warm, warm_or_compile)
from dcnn_tpu.aot.keys import backend_fingerprint, callable_id
from dcnn_tpu.nn import SequentialBuilder
from dcnn_tpu.obs.registry import MetricsRegistry
from dcnn_tpu.optim import Adam, SGD
from dcnn_tpu.ops.losses import softmax_cross_entropy
from dcnn_tpu.resilience import FaultPlan
from dcnn_tpu.resilience.faults import InjectedCrash
from dcnn_tpu.train import make_train_step
from dcnn_tpu.train.trainer import Trainer, create_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model():
    return (SequentialBuilder("aot_t").input((6,))
            .dense(16).activation("relu").dense(4).build())


def _data(batch=8):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 6)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[rng.integers(0, 4, batch)])
    return x, y


def _step_setup():
    model = _model()
    opt = Adam(1e-3)
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, softmax_cross_entropy, opt)
    cfg = digest({"model": model.get_config(), "opt": opt.get_config(),
                  "loss": callable_id(softmax_cross_entropy)})
    return model, opt, ts, step, cfg


def _warm(step, ts, x, y, cache, cfg, reg=None):
    return warm_or_compile(step, ts, x, y, jax.random.PRNGKey(1), 1e-3,
                           cache=cache, what="train", config=cfg,
                           donate=(0,), registry=reg)


# ------------------------------------------------------------------- keys

def test_cache_key_stable_and_sensitive():
    _, _, ts, _, cfg = _step_setup()
    x, y = _data()
    args = (ts, x, y, jax.random.PRNGKey(1), 1e-3)
    k1, m1 = cache_key(args, config=cfg, donate=(0,))
    k2, _ = cache_key(args, config=cfg, donate=(0,))
    assert k1 == k2
    # donation, config, and avals each change the key
    assert cache_key(args, config=cfg, donate=())[0] != k1
    assert cache_key(args, config="other", donate=(0,))[0] != k1
    x2, y2 = _data(batch=4)
    assert cache_key((ts, x2, y2, jax.random.PRNGKey(1), 1e-3),
                     config=cfg, donate=(0,))[0] != k1
    # the material records what went in (MANIFEST debuggability)
    assert m1["donate"] == [0] and m1["config"] == cfg
    assert m1["fingerprint"]["jaxlib"]


def test_callable_id_has_no_addresses():
    cid = callable_id(softmax_cross_entropy)
    assert "0x" not in cid and "softmax_cross_entropy" in cid
    import functools
    cid2 = callable_id(functools.partial(softmax_cross_entropy))
    assert "partial" in cid2 and "0x" not in cid2


def test_callable_id_bound_method_folds_in_owner_config():
    """Two SequentialStageStacks whose blocks differ must key their bound
    ``stage_fn`` differently even when every param shape coincides — the
    qualname alone is 'SequentialStageStack.stage_fn' for both, and a
    collision would silently serve the wrong architecture."""
    from dcnn_tpu.nn.layers import GroupNormLayer
    from dcnn_tpu.parallel import SequentialStageStack

    shape = (16, 8, 8)
    s4 = SequentialStageStack(GroupNormLayer(4, 16), 2, shape)
    s8 = SequentialStageStack(GroupNormLayer(8, 16), 2, shape)
    i4, i8 = callable_id(s4.stage_fn), callable_id(s8.stage_fn)
    assert i4 != i8
    assert "0x" not in i4 and "0x" not in i8
    # stable across instances with the same config (no per-object state)
    s4b = SequentialStageStack(GroupNormLayer(4, 16), 2, shape)
    assert callable_id(s4b.stage_fn) == i4


def test_train_step_key_material_lr_invariant_and_shared():
    """The canonical train-step key (keys.train_step_key_material) must
    hit across base-lr variants (lr is a runtime argument, not key
    material — a prewarmed fleet must not pay the compile wall for
    Adam(3e-4) vs Adam(1e-3)) while still splitting on kind and on real
    optimizer hyperparameters."""
    from dcnn_tpu.aot.keys import optimizer_id, train_step_key_material

    model = _model()
    m1 = train_step_key_material(model, Adam(1e-3), softmax_cross_entropy)
    m2 = train_step_key_material(model, Adam(3e-4), softmax_cross_entropy)
    assert digest(m1) == digest(m2)
    assert "learning_rate" not in json.dumps(m1)
    m3 = train_step_key_material(model, Adam(1e-3), softmax_cross_entropy,
                                 kind="multi_step")
    assert digest(m1) != digest(m3)
    m4 = train_step_key_material(model, Adam(1e-3, beta1=0.8),
                                 softmax_cross_entropy)
    assert digest(m1) != digest(m4)
    assert digest(m1) != digest(train_step_key_material(
        model, SGD(1e-3), softmax_cross_entropy))
    # optimizer_id falls back to type identity without get_config
    class Bare:
        pass
    assert "Bare" in optimizer_id(Bare())


# ------------------------------------------------------- cache mechanics

def test_untrusted_root_refused(tmp_path):
    """Hits pickle.loads executable bytes, so a root another user could
    have planted or can SWAP OUT must be refused (callers degrade to
    uncached compilation): world-writable non-sticky mode — on the root
    or any ancestor — or foreign ownership. Sticky world-writable
    (``/tmp`` itself, 1777) is trusted: the kernel forbids other users
    renaming entries they don't own. Fresh roots are created 0700."""
    ww = tmp_path / "ww"
    ww.mkdir()
    os.chmod(ww, 0o777)
    with pytest.raises(ValueError, match="world-writable"):
        ExecutableCache(str(ww))
    # a 0700 root under a world-writable NON-sticky parent: the parent's
    # owner can replace the whole root between check and load
    nested = ww / "aot"
    with pytest.raises(ValueError, match="world-writable"):
        ExecutableCache(str(nested))
    # ... but under a sticky 1777 parent (the /tmp shape) it is fine
    sticky = tmp_path / "sticky"
    sticky.mkdir()
    os.chmod(sticky, 0o1777)
    ExecutableCache(str(sticky / "aot"))
    if hasattr(os, "getuid") and os.getuid() == 0:
        foreign = tmp_path / "foreign"
        foreign.mkdir()
        os.chown(foreign, 12345, 12345)
        with pytest.raises(ValueError, match="owned by uid"):
            ExecutableCache(str(foreign))
    fresh = tmp_path / "fresh"
    ExecutableCache(str(fresh))
    assert (os.stat(fresh).st_mode & 0o777) == 0o700


def test_commit_lookup_roundtrip_and_idempotence(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"), registry=MetricsRegistry())
    assert cache.commit("k" * 64, b"payload-bytes", {"what": "t"})
    assert cache.lookup("k" * 64) == b"payload-bytes"
    # second writer loses gracefully (a sibling process already committed)
    assert not cache.commit("k" * 64, b"payload-bytes", {"what": "t"})
    rows = cache.entries()
    assert len(rows) == 1 and rows[0]["what"] == "t"
    assert rows[0]["hits"] == 1  # the lookup above


def test_bitflip_quarantined_and_recompiled(tmp_path):
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path / "aot"), registry=reg)
    _, opt, ts, step, cfg = _step_setup()
    x, y = _data()
    exe, info = _warm(step, ts, x, y, cache, cfg, reg)
    assert info["committed"] and not info["hit"]
    key = info["key"]
    # corrupt the committed payload in place (the canonical fixture)
    FaultPlan(seed=3).bit_flip(str(tmp_path / "aot" / key / "payload.bin"))
    ts2 = create_train_state(_model(), opt, jax.random.PRNGKey(0))
    step2 = make_train_step(_model(), softmax_cross_entropy, opt)
    with pytest.warns(UserWarning, match="quarantined"):
        exe2, info2 = _warm(step2, ts2, x, y, cache, cfg, reg)
    # transparently recompiled AND recommitted under the same key
    assert not info2["hit"] and info2["committed"] and info2["key"] == key
    assert reg.snapshot().get("aot_quarantined_total") == 1
    corrupt = [n for n in os.listdir(tmp_path / "aot")
               if n.startswith("corrupt-")]
    assert len(corrupt) == 1
    # and the fresh entry now hits
    ts3 = create_train_state(_model(), opt, jax.random.PRNGKey(0))
    step3 = make_train_step(_model(), softmax_cross_entropy, opt)
    _, info3 = _warm(step3, ts3, x, y, cache, cfg, reg)
    assert info3["hit"]


def test_stale_version_entry_is_miss_not_crash(tmp_path):
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path / "aot"), registry=reg)
    _, opt, ts, step, cfg = _step_setup()
    x, y = _data()
    _, info = _warm(step, ts, x, y, cache, cfg, reg)
    key = info["key"]
    # doctor the MANIFEST to look like another jaxlib's entry (a
    # hand-copied cache dir / key-schema drift simulation)
    mp = tmp_path / "aot" / key / "MANIFEST.json"
    m = json.loads(mp.read_text())
    m["material"]["fingerprint"]["jaxlib"] = "0.0.0"
    mp.write_text(json.dumps(m))
    assert cache.lookup(key, fingerprint=backend_fingerprint()) is None
    assert reg.snapshot().get("aot_stale_total") == 1
    # skipped, not quarantined: the entry is intact for its own version
    assert (tmp_path / "aot" / key / "payload.bin").exists()


def test_keep_k_gc_retains_most_recently_used(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"), keep=10)
    for i in range(5):
        assert cache.commit(f"key{i:061d}", f"p{i}".encode(), {"what": "t"})
    cache.lookup("key" + "0" * 61)  # bump entry 0's LRU position
    removed = cache.gc(keep=2)
    assert removed == 3
    kept = {r["key"] for r in cache.entries()}
    assert "key" + "0" * 61 in kept and len(kept) == 2


def test_gc_validates_keep(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"))
    with pytest.raises(ValueError):
        cache.gc(keep=0)
    with pytest.raises(ValueError):
        ExecutableCache(str(tmp_path / "aot2"), keep=0)


# ------------------------------------------------------------ fault points

def test_commit_crash_leaves_no_entry(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"))
    _, _, ts, step, cfg = _step_setup()
    x, y = _data()
    with FaultPlan().arm("aot.commit", exc=InjectedCrash):
        with pytest.raises(InjectedCrash):
            _warm(step, ts, x, y, cache, cfg)
    assert cache.entries() == []
    # after the "restart": a clean run commits normally
    ts2 = create_train_state(_model(), Adam(1e-3), jax.random.PRNGKey(0))
    step2 = make_train_step(_model(), softmax_cross_entropy, Adam(1e-3))
    _, info = _warm(step2, ts2, x, y, cache, cfg)
    assert info["committed"]


def test_commit_fault_degrades_to_uncached_compile(tmp_path):
    reg = MetricsRegistry()
    cache = ExecutableCache(str(tmp_path / "aot"), registry=reg)
    _, _, ts, step, cfg = _step_setup()
    x, y = _data()
    with FaultPlan().arm("aot.commit"):
        exe, info = _warm(step, ts, x, y, cache, cfg, reg)
    assert not info["committed"] and cache.entries() == []
    assert reg.snapshot().get("aot_fallback_total") == 1
    out = exe(ts, x, y, jax.random.PRNGKey(1), 1e-3)
    assert np.isfinite(float(out[1]))  # the executable still works


def test_load_fault_degrades_to_recompile(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"))
    _, _, ts, step, cfg = _step_setup()
    x, y = _data()
    _, info = _warm(step, ts, x, y, cache, cfg)
    assert info["committed"]
    ts2 = create_train_state(_model(), Adam(1e-3), jax.random.PRNGKey(0))
    step2 = make_train_step(_model(), softmax_cross_entropy, Adam(1e-3))
    with FaultPlan().arm("aot.load"):
        exe, info2 = _warm(step2, ts2, x, y, cache, cfg)
    assert not info2["hit"]  # the fault made it a miss, not an error
    out = exe(ts2, x, y, jax.random.PRNGKey(1), 1e-3)
    assert np.isfinite(float(out[1]))


# ------------------------------------------------------------ warm dispatch

def test_warm_hit_is_bit_identical_to_compiled(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"))
    _, opt, _, step, cfg = _step_setup()
    x, y = _data()
    ts_a = create_train_state(_model(), opt, jax.random.PRNGKey(0))
    exe_a, info_a = _warm(step, ts_a, x, y, cache, cfg)
    out_a = exe_a(ts_a, x, y, jax.random.PRNGKey(1), 1e-3)
    step_b = make_train_step(_model(), softmax_cross_entropy, opt)
    ts_b = create_train_state(_model(), opt, jax.random.PRNGKey(0))
    exe_b, info_b = _warm(step_b, ts_b, x, y, cache, cfg)
    assert not info_a["hit"] and info_b["hit"]
    out_b = exe_b(ts_b, x, y, jax.random.PRNGKey(1), 1e-3)
    for a, b in zip(jax.tree_util.tree_leaves(out_a),
                    jax.tree_util.tree_leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_warm_callable_dispatch_and_fallthrough(tmp_path):
    cache = ExecutableCache(str(tmp_path / "aot"))
    _, opt, _, step, cfg = _step_setup()
    wc = WarmCallable(step, cache, what="train", config=cfg, donate=(0,))
    x, y = _data()
    ts = create_train_state(_model(), opt, jax.random.PRNGKey(0))
    ts, loss, _ = wc(ts, x, y, jax.random.PRNGKey(1), 1e-3)
    assert wc.last_info["committed"]
    # a second signature (different batch) falls through per-signature
    x2, y2 = _data(batch=4)
    ts, loss2, _ = wc(ts, x2, y2, jax.random.PRNGKey(1), 1e-3)
    assert len(wc._exes) == 2
    assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
    # .lower forwards (the pipeline HLO tests rely on this shape)
    assert hasattr(wc, "lower")


def test_maybe_warm_is_passthrough_when_disabled(monkeypatch):
    monkeypatch.delenv("AOT_CACHE", raising=False)
    jitted = jax.jit(lambda a: a + 1)
    assert maybe_warm(jitted, what="x") is jitted


def test_trainer_wiring_warm_starts(tmp_path):
    from dcnn_tpu.core.config import TrainingConfig

    root = str(tmp_path)
    cfg = TrainingConfig(aot_cache_dir=root, snapshot_dir=None)
    x, y = _data()
    t1 = Trainer(_model(), SGD(0.05), "softmax_crossentropy", cfg)
    assert isinstance(t1.train_step, WarmCallable)
    ts1 = create_train_state(t1.model, t1.optimizer, jax.random.PRNGKey(0))
    ts1, loss1, _ = t1.train_step(ts1, x, y, jax.random.PRNGKey(1), 0.05)
    assert t1.train_step.last_info["committed"]
    # a "restarted" trainer warm-starts from the committed executable
    t2 = Trainer(_model(), SGD(0.05), "softmax_crossentropy", cfg)
    ts2 = create_train_state(t2.model, t2.optimizer, jax.random.PRNGKey(0))
    ts2, loss2, _ = t2.train_step(ts2, x, y, jax.random.PRNGKey(1), 0.05)
    assert t2.train_step.last_info["hit"]
    np.testing.assert_array_equal(np.asarray(loss1), np.asarray(loss2))
    # default config: the plain jitted step, no wrapper
    t3 = Trainer(_model(), SGD(0.05), "softmax_crossentropy",
                 TrainingConfig(snapshot_dir=None))
    assert not isinstance(t3.train_step, WarmCallable)


def test_engine_buckets_hit_across_rebuilds(tmp_path):
    from dcnn_tpu.serve.engine import InferenceEngine

    cache = ExecutableCache(str(tmp_path / "aot"))
    model = _model()
    params, state = model.init(jax.random.PRNGKey(0))
    eng1 = InferenceEngine.from_model(model, params, state, fold=False,
                                      max_batch=4, warmup=False,
                                      aot_cache=cache)
    assert all("aot_hit" in s for s in eng1.compile_stats.values())
    eng2 = InferenceEngine.from_model(model, params, state, fold=False,
                                      max_batch=4, warmup=False,
                                      aot_cache=cache)
    assert all(s["aot_hit"] for s in eng2.compile_stats.values())
    x = np.asarray(_data(batch=3)[0])
    np.testing.assert_array_equal(np.asarray(eng1.infer(x)),
                                  np.asarray(eng2.infer(x)))
    # DIFFERENT weights must not hit the first engine's entries
    params2, state2 = model.init(jax.random.PRNGKey(9))
    eng3 = InferenceEngine.from_model(model, params2, state2, fold=False,
                                      max_batch=4, warmup=False,
                                      aot_cache=cache)
    assert not any(s["aot_hit"] for s in eng3.compile_stats.values())


def test_engine_refuses_cache_without_weights_digest(tmp_path):
    from dcnn_tpu.serve.engine import InferenceEngine

    cache = ExecutableCache(str(tmp_path / "aot"))
    model = _model()
    params, state = model.init(jax.random.PRNGKey(0))

    def apply_fn(x):
        return model.apply(params, state, x, training=False)[0]

    with pytest.warns(UserWarning, match="aot_config"):
        eng = InferenceEngine(apply_fn, model.input_shape, max_batch=2,
                              warmup=False, aot_cache=cache)
    assert not any("aot_hit" in s for s in eng.compile_stats.values())
    assert cache.entries() == []


def test_engine_default_is_uncached(monkeypatch):
    from dcnn_tpu.serve.engine import InferenceEngine

    monkeypatch.delenv("AOT_CACHE", raising=False)
    model = _model()
    params, state = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine.from_model(model, params, state, fold=False,
                                     max_batch=2, warmup=False)
    assert not any("aot_hit" in s for s in eng.compile_stats.values())


def test_compiled_pipeline_dispatcher_with_cache(tmp_path, monkeypatch):
    from dcnn_tpu.core.mesh import STAGE_AXIS, make_mesh
    from dcnn_tpu.nn import Conv2DLayer, GroupNormLayer, ResidualBlock
    from dcnn_tpu.parallel.compiled_pipeline import (
        SequentialStageStack, make_compiled_pipeline_train_step,
        shard_stacked)

    monkeypatch.setenv("AOT_CACHE", str(tmp_path))
    S, MB = 2, 2
    mesh = make_mesh((S,), (STAGE_AXIS,), devices=jax.devices()[:S])
    block = ResidualBlock(layers=[Conv2DLayer(2, 3, 1, 1, name="c0"),
                                  GroupNormLayer(2, name="g0")],
                          shortcut=[], activation="relu")
    stack = SequentialStageStack(block, S, (2, 4, 4))
    params = stack.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    mb_x = jnp.asarray(rng.normal(size=(MB, 2, 2, 4, 4)).astype(np.float32))
    mb_y = jnp.asarray(rng.normal(size=(MB, 2, 2, 4, 4)).astype(np.float32))
    loss_fn = lambda p, t: jnp.mean((p - t) ** 2)  # noqa: E731

    def one(opt):
        step = make_compiled_pipeline_train_step(
            stack.stage_fn, loss_fn, opt, S, MB, mesh)
        ps = shard_stacked(params, mesh)
        _, _, loss, _ = step(ps, opt.init(ps), mb_x, mb_y, jnp.float32(0.05))
        return float(loss)

    # two independently-built dispatchers (second may deserialize from
    # cache or fall back if the sharded executable can't serialize on
    # this backend — both paths must be numerically identical)
    l1, l2 = one(SGD(0.05)), one(SGD(0.05))
    assert l1 == l2 and np.isfinite(l1)


def test_elastic_solo_with_cache_matches_plain(tmp_path):
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data.loader import ArrayDataLoader, one_hot

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = one_hot(rng.integers(0, 4, 32), 4)

    def run(aot_root):
        cfg = TrainingConfig(
            epochs=1, learning_rate=0.05, seed=3, snapshot_dir=None,
            elastic=True, elastic_rank=0, elastic_microbatches=1,
            elastic_heartbeat_s=0.0, aot_cache_dir=aot_root)
        t = Trainer(_model(), SGD(0.05), "softmax_crossentropy", cfg)
        ts = create_train_state(t.model, t.optimizer,
                                jax.random.PRNGKey(cfg.seed))
        return t.fit(ts, ArrayDataLoader(x, y, batch_size=16, seed=7))

    plain = run(None)
    warm1 = run(str(tmp_path))   # seeds the cache
    warm2 = run(str(tmp_path))   # consumes it
    for a, b, c in zip(jax.tree_util.tree_leaves(plain.params),
                       jax.tree_util.tree_leaves(warm1.params),
                       jax.tree_util.tree_leaves(warm2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------------------------------------ CLI

def test_cli_list_gc_json(tmp_path, capsys):
    from dcnn_tpu.aot.__main__ import main

    root = str(tmp_path)
    cache = ExecutableCache(os.path.join(root, "aot"))
    _, _, ts, step, cfg = _step_setup()
    x, y = _data()
    _warm(step, ts, x, y, cache, cfg)

    assert main(["--dir", root, "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert len(report["entries"]) == 1
    row = report["entries"][0]
    assert row["what"] == "train" and row["size"] > 0
    assert row["avals"].startswith("f32[")

    assert main(["--dir", root]) == 0  # human listing renders
    assert "train" in capsys.readouterr().out

    assert main(["--dir", root, "--gc", "--keep", "1", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["removed"] == 0

    assert main(["--dir", root, "--prewarm", "no-such-model"]) == 1
    assert "prewarm failed" in capsys.readouterr().err


def test_cli_prewarm_zoo_model(tmp_path, capsys):
    from dcnn_tpu.aot.__main__ import main

    root = str(tmp_path)
    rc = main(["--dir", root, "--prewarm", "mnist_cnn", "--max-batch", "2",
               "--json"])
    out = capsys.readouterr().out
    if rc != 0:
        pytest.skip(f"zoo prewarm unavailable here: {out}")
    report = json.loads(out)
    assert report["prewarm"]["buckets"] == [1, 2]
    # second prewarm hits every bucket
    assert main(["--dir", root, "--prewarm", "mnist_cnn", "--max-batch",
                 "2", "--json"]) == 0
    report2 = json.loads(capsys.readouterr().out)
    assert all(s.get("aot_hit")
               for s in report2["prewarm"]["bucket_stats"].values())


# -------------------------------------------- the acceptance round trip

_SUBPROC = textwrap.dedent("""
    import json, os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax, jax.numpy as jnp
    sys.path.insert(0, {repo!r})
    from dcnn_tpu.aot import ExecutableCache, digest, warm_or_compile
    from dcnn_tpu.aot.keys import callable_id
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.obs.registry import MetricsRegistry
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.serve.engine import InferenceEngine
    from dcnn_tpu.train import make_train_step
    from dcnn_tpu.train.trainer import create_train_state

    cache_dir, out_path = sys.argv[1], sys.argv[2]
    reg = MetricsRegistry()
    cache = ExecutableCache(cache_dir, registry=reg)
    model = (SequentialBuilder("aot_rt").input((6,))
             .dense(16).activation("relu").dense(4).build())
    opt = Adam(1e-3)
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, softmax_cross_entropy, opt)
    cfg = digest({{"model": model.get_config(), "opt": opt.get_config(),
                   "loss": callable_id(softmax_cross_entropy)}})
    rng0 = np.random.default_rng(0)
    x = jnp.asarray(rng0.normal(size=(8, 6)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[rng0.integers(0, 4, 8)])
    exe, info = warm_or_compile(step, ts, x, y, jax.random.PRNGKey(1),
                                1e-3, cache=cache, what="train",
                                config=cfg, donate=(0,), registry=reg)
    new_ts, loss, logits = exe(ts, x, y, jax.random.PRNGKey(1), 1e-3)
    flat_params = np.concatenate(
        [np.asarray(l, np.float32).ravel()
         for l in jax.tree_util.tree_leaves(new_ts.params)])

    # serve bucket set over the same weights
    params, state = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine.from_model(model, params, state, fold=False,
                                     max_batch=4, warmup=False,
                                     aot_cache=cache, registry=reg)
    serve_logits = np.asarray(eng.infer(np.asarray(x[:3])))
    snap = reg.snapshot()
    json.dump({{
        "train_hit": info["hit"],
        "train_key": info["key"],
        "serve_hits": sum(1 for s in eng.compile_stats.values()
                          if s.get("aot_hit")),
        "serve_buckets": len(eng.bucket_sizes),
        "site_compile_s": sum(
            v for k, v in snap.items()
            if k.startswith("compile_") and k.endswith("_seconds_total")
            and k != "compile_seconds_total"),
        "aot_hits_total": int(snap.get("aot_hits_total", 0)),
        "loss": float(loss),
        "flat_params": flat_params.tolist(),
        "serve_logits": serve_logits.tolist(),
    }}, open(out_path, "w"))
""")


def test_subprocess_round_trip_bit_identical_no_recompile(tmp_path):
    """Acceptance: compile+commit in process A; a FRESH process B loads
    the executables with ZERO compile events and produces bit-identical
    train-step params/loss and serve logits — for the train step and the
    whole serve bucket set."""
    cache_dir = str(tmp_path / "aot")
    script = _SUBPROC.format(repo=REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("AOT_CACHE", None)

    def run(tag):
        out = str(tmp_path / f"{tag}.json")
        r = subprocess.run([sys.executable, "-c", script, cache_dir, out],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        with open(out) as f:
            return json.load(f)
    a = run("a")
    b = run("b")
    # process A compiled (train step + every bucket); B compiled NOTHING
    assert not a["train_hit"]
    assert b["train_hit"]
    assert b["serve_hits"] == b["serve_buckets"] == a["serve_buckets"]
    # the sites' own compile walls (compile_<what>_seconds_total): spent in
    # A, none in B. (compile_total itself is the process-wide listener's,
    # which also sees what model.init jits in either process.)
    assert a["site_compile_s"] > 0
    assert b["site_compile_s"] == 0         # no retrace-to-compile in B
    assert b["aot_hits_total"] == 1 + b["serve_buckets"]
    assert b["train_key"] == a["train_key"]  # cross-process key stability
    # bit-identical results
    assert a["loss"] == b["loss"]
    np.testing.assert_array_equal(np.asarray(a["flat_params"]),
                                  np.asarray(b["flat_params"]))
    np.testing.assert_array_equal(np.asarray(a["serve_logits"]),
                                  np.asarray(b["serve_logits"]))
