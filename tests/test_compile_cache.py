"""The one compile cache: JAX's persistent cache under
``dcnn_tpu/utils/compile_cache.py``.

First half, the session-integrity protocol: a process that corrupts its own
memory can mint a *structurally valid* cache entry whose replay crashes
every later process, so an entry only survives the enable-time sweep if the
session that minted it exited cleanly. Those tests drive the pure helpers
directly against tmp_path roots — no jax, no subprocesses, no sleeps.

Second half, what the program asks of the cache itself (``scratch_cache``
points JAX's cache at an empty directory for one test): every compile site
built a second time is a hit that returns the first build's bits; what
shapes the program (microbatch count, precision mode) is in the key and
what is an argument (``lr``) is not; a second process on the directory
writes nothing; a stale or torn directory costs a compile, not a crash.
"""

import contextlib
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jaxlib

from dcnn_tpu.obs import get_registry
from dcnn_tpu.obs.xla import install_compile_listener
from dcnn_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mint(root, stem, atime=True):
    with open(os.path.join(root, f"{stem}-cache"), "wb") as f:
        f.write(b"\x78\x9cpayload")
    if atime:
        with open(os.path.join(root, f"{stem}-atime"), "wb") as f:
            f.write(b"0")


def _mark_inflight(root, pid):
    d = os.path.join(root, cc._INFLIGHT)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, str(pid)), "w", encoding="utf-8") as f:
        f.write("")


@pytest.fixture(autouse=True)
def _isolated_sessions(monkeypatch):
    # never let a test leak registered roots into the process-wide
    # atexit commit (conftest enables the real cache for the suite)
    monkeypatch.setattr(cc, "_SESSIONS", {})


class TestManifestIO:
    def test_roundtrip(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, {"b-cache", "a-cache"})
        assert cc._read_committed(root) == {"a-cache", "b-cache"}

    def test_missing_manifest_reads_empty(self, tmp_path):
        assert cc._read_committed(str(tmp_path)) == set()

    def test_write_is_atomic_no_tmp_left_behind(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, {"a-cache"})
        assert [n for n in os.listdir(root) if ".tmp." in n] == []


class TestSweepUncommitted:
    def test_no_manifest_grandfathers_present_entries(self, tmp_path):
        root = str(tmp_path)
        _mint(root, "jit_fwd-aa")
        assert cc._sweep_uncommitted(root) == 0
        # wholesale-committed, like the pre-fingerprint rotate policy
        assert cc._read_committed(root) == {"jit_fwd-aa-cache"}
        assert os.path.exists(os.path.join(root, "jit_fwd-aa-cache"))

    def test_no_manifest_empty_root_still_arms_the_sweep(self, tmp_path):
        # first-ever session on a fresh root crashes after minting: the
        # empty manifest written at its enable is what lets the NEXT
        # session recognise those mints as uncommitted
        root = str(tmp_path)
        assert cc._sweep_uncommitted(root) == 0
        assert os.path.exists(os.path.join(root, cc._COMMITTED))
        _mint(root, "jit_update-poison")  # the crashed session's mint
        assert cc._sweep_uncommitted(root) == 1
        assert not os.path.exists(os.path.join(root,
                                               "jit_update-poison-cache"))

    def test_uncommitted_entry_from_dead_writer_swept(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, {"jit_fwd-ok-cache"})
        _mint(root, "jit_fwd-ok")
        _mint(root, "jit_update-poison")
        assert cc._sweep_uncommitted(root) == 1
        assert os.path.exists(os.path.join(root, "jit_fwd-ok-cache"))
        assert not os.path.exists(os.path.join(root,
                                               "jit_update-poison-cache"))
        # the -atime sibling goes with it
        assert not os.path.exists(os.path.join(root,
                                               "jit_update-poison-atime"))

    def test_live_other_enabler_blocks_sweep(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, set())
        _mint(root, "jit_bwd-fresh")
        _mark_inflight(root, 1)  # pid 1: always alive, never ours
        assert cc._sweep_uncommitted(root) == 0
        assert os.path.exists(os.path.join(root, "jit_bwd-fresh-cache"))

    def test_dead_enabler_marker_pruned_and_entry_swept(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, set())
        _mint(root, "jit_bwd-stale")
        dead = 2 ** 22 - 7  # beyond this box's pid space
        _mark_inflight(root, dead)
        assert cc._sweep_uncommitted(root) == 1
        assert not os.path.exists(os.path.join(root, cc._INFLIGHT,
                                               str(dead)))

    def test_own_pid_marker_does_not_block(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, set())
        _mint(root, "jit_fwd-mine")
        _mark_inflight(root, os.getpid())
        assert cc._sweep_uncommitted(root) == 1


class TestFinishSessions:
    def test_commits_only_new_names_and_prunes_absent(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, {"gone-cache", "kept-cache"})
        _mint(root, "kept")
        cc._SESSIONS[root] = cc.cache_entries(root)  # session start
        _mint(root, "minted-now")
        _mark_inflight(root, os.getpid())
        cc._finish_sessions()
        assert cc._read_committed(root) == {"kept-cache",
                                            "minted-now-cache"}
        # own inflight marker removed, registry drained
        assert not os.path.exists(os.path.join(root, cc._INFLIGHT,
                                               str(os.getpid())))
        assert cc._SESSIONS == {}

    def test_clean_exit_then_next_enable_keeps_entries(self, tmp_path):
        root = str(tmp_path)
        cc._write_committed(root, set())
        cc._SESSIONS[root] = cc.cache_entries(root)
        _mint(root, "jit_scan-warm")
        cc._finish_sessions()
        assert cc._sweep_uncommitted(root) == 0
        assert os.path.exists(os.path.join(root, "jit_scan-warm-cache"))


def test_missing_root_sweeps_nothing(tmp_path):
    assert cc._sweep_uncommitted(str(tmp_path / "nope")) == 0


class TestRegisterSession:
    def test_snapshot_and_marker(self, tmp_path):
        root = str(tmp_path)
        _mint(root, "preexisting")
        cc._register_session(root)
        assert cc._SESSIONS[root] == {"preexisting-cache"}
        assert os.path.exists(os.path.join(root, cc._INFLIGHT,
                                           str(os.getpid())))

    def test_idempotent_snapshot_not_retaken(self, tmp_path):
        root = str(tmp_path)
        cc._register_session(root)
        _mint(root, "after-register")
        cc._register_session(root)
        assert cc._SESSIONS[root] == set()


# ------------------------------------------------- the cache under the program

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@contextlib.contextmanager
def _cache_at(root):
    """JAX's persistent cache on ``root``, keeping every program (no
    compile-time or size threshold); the suite's own directory and
    thresholds (conftest) are put back on exit."""
    from jax._src import compilation_cache as jcc

    old = {n: getattr(jax.config, n) for n in _CACHE_OPTIONS}
    jcc.reset_cache()
    for n, v in zip(_CACHE_OPTIONS, (root, 0, -1)):
        jax.config.update(n, v)
    jax.clear_caches()
    install_compile_listener()
    try:
        yield root
    finally:
        jcc.reset_cache()
        for n, v in old.items():
            jax.config.update(n, v)


@pytest.fixture
def scratch_cache(tmp_path):
    with _cache_at(str(tmp_path / "jax_cache")) as root:
        yield root


def _hits_misses():
    reg = get_registry()
    return (reg.counter("compile_cache_hits_total").value,
            reg.counter("compile_cache_misses_total").value)


def _compiles():
    return get_registry().counter("compile_total").value


def _bits(*trees):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(trees)]


def _built_twice(build, root):
    """``build()`` (construct the site and run it once), every in-memory
    executable dropped, ``build()`` again. The second build must be served
    from ``root``: every compile a hit, no entry written. Returns both
    builds' results."""
    _, m0 = _hits_misses()
    first = build()
    _, m1 = _hits_misses()
    assert m1 > m0, "the first build wrote no entry: nothing to hit"
    listing = sorted(os.listdir(root))
    jax.clear_caches()
    (h1, _), c1 = _hits_misses(), _compiles()
    second = build()
    (h2, m2), c2 = _hits_misses(), _compiles()
    assert h2 > h1, "the rebuilt site compiled instead of hitting"
    assert m2 == m1, f"the rebuilt site wrote {m2 - m1} new entries"
    # JAX counts a backend compile round its cache lookup, so a program
    # the cache could not hold would show here as a compile without a hit
    assert c2 - c1 == h2 - h1
    assert sorted(os.listdir(root)) == listing
    return first, second


def _assert_same_bits(first, second):
    assert len(first) == len(second) and first
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def _dense_model(name="cc_dense"):
    from dcnn_tpu.nn import SequentialBuilder
    return (SequentialBuilder(name).input((6,))
            .dense(16).activation("relu").dense(4).build())


def _dense_data(batch=8, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, 6)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[rng.integers(0, 4, batch)])
    return x, y


def _site_train_step(lr=1e-3, num_microbatches=1):
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.train import make_train_step
    from dcnn_tpu.train.trainer import create_train_state

    model, opt = _dense_model(), Adam(1e-3)
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    x, y = _dense_data()
    step = make_train_step(model, softmax_cross_entropy, opt,
                           num_microbatches=num_microbatches)
    ts, loss, logits = step(ts, x, y, jax.random.PRNGKey(1), lr)
    return _bits(loss, logits, ts.params)


def _site_multi_step():
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.train.trainer import create_train_state, make_multi_step

    model, opt = _dense_model(), Adam(1e-3)
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    xs, ys = zip(*(_dense_data(seed=k) for k in range(3)))
    multi = make_multi_step(model, softmax_cross_entropy, opt)
    ts, loss = multi(ts, jnp.stack(xs), jnp.stack(ys),
                     jax.random.PRNGKey(1), 1e-3)
    return _bits(loss, ts.params)


def _resident_setup():
    from dcnn_tpu.data import DeviceDataset
    from dcnn_tpu.nn import SequentialBuilder

    rng = np.random.default_rng(2)
    y = rng.integers(0, 4, size=32)
    x = np.clip(y[:, None, None, None] * 50 + 20
                + rng.normal(0, 10, size=(32, 8, 8, 1)), 0, 255)
    model = (SequentialBuilder(name="cc_cnn", data_format="NHWC")
             .input((8, 8, 1))
             .conv2d(8, 3, padding=1).batchnorm().activation("relu")
             .maxpool2d(2).flatten().dense(4).build())
    return model, DeviceDataset(x.astype(np.uint8), y.astype(np.int64), 4,
                                batch_size=8)


def _site_resident_epoch():
    from dcnn_tpu.data.device_dataset import resident_epoch
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.train.trainer import create_train_state

    model, ds = _resident_setup()
    opt = SGD(0.05)
    ts, loss = resident_epoch(model, softmax_cross_entropy, opt, ds)(
        create_train_state(model, opt, jax.random.PRNGKey(3)),
        ds.x_staged, ds.y, jax.random.PRNGKey(7), 0.05)
    return _bits(loss, ts.params, ts.state)


def _site_eval_step():
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.train.trainer import make_eval_step

    model = _dense_model()
    params, state = model.init(jax.random.PRNGKey(0))
    x, y = _dense_data()
    return _bits(make_eval_step(model, softmax_cross_entropy)(
        params, state, x, y))


def _site_compiled_gpipe():
    """The assertion tier-1 failed on from the seed until PR 30, on the
    cache that serves it: a two-stage compiled GPipe step built twice."""
    from dcnn_tpu.core.mesh import STAGE_AXIS, make_mesh
    from dcnn_tpu.nn import Conv2DLayer, GroupNormLayer, ResidualBlock
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel.compiled_pipeline import (
        SequentialStageStack, make_compiled_pipeline_train_step,
        shard_stacked)

    S, MB = 2, 2
    mesh = make_mesh((S,), (STAGE_AXIS,), devices=jax.devices()[:S])
    block = ResidualBlock(layers=[Conv2DLayer(2, 3, 1, 1, name="c0"),
                                  GroupNormLayer(2, name="g0")],
                          shortcut=[], activation="relu")
    stack = SequentialStageStack(block, S, (2, 4, 4))
    rng = np.random.default_rng(0)
    mb_x = jnp.asarray(rng.normal(size=(MB, 2, 2, 4, 4)).astype(np.float32))
    mb_y = jnp.asarray(rng.normal(size=(MB, 2, 2, 4, 4)).astype(np.float32))
    opt = SGD(0.05)
    step = make_compiled_pipeline_train_step(
        stack.stage_fn, lambda p, t: jnp.mean((p - t) ** 2), opt, S, MB,
        mesh)
    ps = shard_stacked(stack.init(jax.random.PRNGKey(0)), mesh)
    ps, _, loss, outs = step(ps, opt.init(ps), mb_x, mb_y, jnp.float32(0.05))
    assert np.isfinite(float(loss))
    return _bits(loss, outs, ps)


def _site_compiled_1f1b():
    from dcnn_tpu.core.mesh import STAGE_AXIS, make_mesh
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.ops.losses import softmax_cross_entropy
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel.compiled_pipeline import HeteroCompiledPipeline

    S, M, mb = 2, 2, 2
    mesh = make_mesh((S,), (STAGE_AXIS,), devices=jax.devices()[:S])
    model = (SequentialBuilder("cc_gn_stack").input((3, 8, 8))
             .conv2d(8, 3, 1, 1).groupnorm(4).activation("relu")
             .conv2d(8, 3, 1, 1).groupnorm(4).activation("relu")
             .flatten().dense(10).build())
    rng = np.random.default_rng(5)
    mb_x = jnp.asarray(rng.normal(size=(M, mb, 3, 8, 8)).astype(np.float32))
    mb_y = jnp.asarray(np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, M * mb)].reshape(M, mb, 10))
    pipe = HeteroCompiledPipeline(model, S, M, mesh)
    opt = SGD(0.05)
    fp, fs = pipe.init(jax.random.PRNGKey(0))
    fp, _, fs, loss, logits = pipe.make_train_step_1f1b(
        softmax_cross_entropy, opt)(fp, opt.init(fp), fs, mb_x, mb_y,
                                    jax.random.PRNGKey(9), jnp.float32(0.05))
    return _bits(loss, logits, fp, fs)


def _site_pipeline_stage():
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.parallel import InProcessPipelineCoordinator

    model = (SequentialBuilder("cc_pipe").input((1, 8, 8))
             .conv2d(4, 3, 1, 1).activation("relu").flatten()
             .dense(16).activation("relu").dense(10).build())
    coord = InProcessPipelineCoordinator(
        model, SGD(0.05), "softmax_crossentropy", num_stages=2,
        num_microbatches=2)
    coord.deploy_stages(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 1, 8, 8)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[[0, 1, 2, 3]]
    loss, logits = coord.train_batch_sync(x, y, 0.05, jax.random.PRNGKey(9))
    return _bits(loss, logits, [st.params for st in coord.stages])


def _site_elastic_solo():
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data.loader import ArrayDataLoader, one_hot
    from dcnn_tpu.optim import SGD
    from dcnn_tpu.train.trainer import Trainer, create_train_state

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = one_hot(rng.integers(0, 4, 32), 4)
    cfg = TrainingConfig(
        epochs=1, learning_rate=0.05, seed=3, snapshot_dir=None,
        elastic=True, elastic_rank=0, elastic_microbatches=1,
        elastic_heartbeat_s=0.0)
    t = Trainer(_dense_model(), SGD(0.05), "softmax_crossentropy", cfg)
    ts = create_train_state(t.model, t.optimizer,
                            jax.random.PRNGKey(cfg.seed))
    return _bits(t.fit(ts, ArrayDataLoader(x, y, batch_size=16,
                                           seed=7)).params)


_SITES = {
    "make_train_step": _site_train_step,
    "make_multi_step": _site_multi_step,
    "resident_epoch": _site_resident_epoch,
    "make_eval_step": _site_eval_step,
    "compiled_pipeline_gpipe": _site_compiled_gpipe,
    "compiled_pipeline_1f1b": _site_compiled_1f1b,
    "pipeline_stage_step": _site_pipeline_stage,
    "elastic_fit_solo": _site_elastic_solo,
}


@pytest.mark.parametrize("site", list(_SITES))
def test_compile_site_rebuilt_is_a_hit_with_the_same_bits(site,
                                                          scratch_cache):
    _assert_same_bits(*_built_twice(_SITES[site], scratch_cache))


# ---------------------------------------------------------- serving buckets

def _serve_model():
    from dcnn_tpu.nn import SequentialBuilder
    return (SequentialBuilder(name="cc_srv", data_format="NHWC")
            .input((8, 8, 3))
            .conv2d(4, 3, padding=1).batchnorm().activation("relu")
            .maxpool2d(2).flatten().dense(5).build())


@pytest.fixture(scope="module", params=["float", "int8"])
def engine_pair(request, tmp_path_factory):
    """An ``InferenceEngine`` (buckets 1, 2, 4, 8) and the same engine
    built again on the first one's cache directory."""
    from dcnn_tpu.serve import InferenceEngine

    model = _serve_model()
    params, state = model.init(jax.random.PRNGKey(0), model.input_shape)
    rng = np.random.default_rng(0)
    kw = {}
    if request.param == "int8":
        kw["int8_calib"] = jnp.asarray(
            rng.normal(size=(16, 8, 8, 3)).astype(np.float32))
    root = str(tmp_path_factory.mktemp(f"engine_{request.param}"))
    with _cache_at(root):
        first, second = _built_twice(
            lambda: InferenceEngine.from_model(model, params, state,
                                               max_batch=8, **kw), root)
    return first, second, rng.normal(size=(8, 8, 8, 3)).astype(np.float32)


@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
def test_engine_bucket_rebuilt_is_a_hit_with_the_same_bits(engine_pair,
                                                           bucket):
    """The fixture held the rebuilt engine to a hit for every compile and
    no new entry; here, each bucket's session answers the first engine's
    bits and reports its wall time."""
    first, second, pool = engine_pair
    assert first.bucket_sizes == second.bucket_sizes == [1, 2, 4, 8]
    x = pool[:bucket]
    np.testing.assert_array_equal(np.asarray(first.run_padded(x)),
                                  np.asarray(second.run_padded(x)))
    for eng in (first, second):
        stats = eng.compile_stats[bucket]
        assert stats["compile_s"] > 0 and stats["warmup_s"] >= 0
        assert not [k for k in stats if "aot" in k or "deserialize" in k]


@pytest.fixture(scope="module")
def decode_pair(tmp_path_factory):
    """A ``DecodeEngine`` (batch buckets 1, 2 by page buckets 1, 2) and
    the same engine built again on the first one's cache directory."""
    from dcnn_tpu.models.decoder import MHADecoder
    from dcnn_tpu.serve.decode import DecodeEngine

    model = MHADecoder(vocab_size=13, embed_dim=16, num_heads=2,
                       num_layers=2, max_seq_len=32)
    params = model.init(jax.random.PRNGKey(0))
    root = str(tmp_path_factory.mktemp("engine_decode"))
    with _cache_at(root):
        return _built_twice(
            lambda: DecodeEngine(model, params, max_slots=2, page_size=4,
                                 max_pages_per_seq=2, num_pages=8), root)


@pytest.mark.parametrize("bucket", [(1, 1), (1, 2), (2, 1), (2, 2)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_decode_bucket_rebuilt_is_a_hit_with_the_same_bits(decode_pair,
                                                           bucket):
    first, second = decode_pair
    assert sorted(first.compile_stats) == sorted(second.compile_stats) == [
        (1, 1), (1, 2), (2, 1), (2, 2)]
    b, mp = bucket
    tokens = np.arange(3, 3 + b, dtype=np.int32)
    positions = np.zeros((b,), np.int32)
    table = 1 + np.arange(b * mp, dtype=np.int32).reshape(b, mp)
    outs = []
    for eng in (first, second):
        pool = jnp.zeros(eng.pool.k.shape, eng.pool.dtype)
        outs.append(_bits(eng.run_step(tokens, positions, table, pool,
                                       pool)))
        stats = eng.compile_stats[bucket]
        assert stats["compile_s"] > 0
        assert not [k for k in stats if "aot" in k]
    _assert_same_bits(*outs)


# ------------------------------------------------------ what a key must get right

def _train_step_then(root, second):
    """The train step, then (in-memory executables dropped) ``second()``:
    (first bits, second bits, hits, entries the second build wrote)."""
    first = _site_train_step()
    before = cc.cache_entries(root)
    jax.clear_caches()
    h0, _ = _hits_misses()
    out = second()
    h1, _ = _hits_misses()
    return first, out, h1 - h0, cc.cache_entries(root) - before


def test_key_leaves_lr_out_it_is_an_argument(scratch_cache):
    first, second, hits, wrote = _train_step_then(
        scratch_cache, lambda: _site_train_step(lr=5e-2))
    assert hits > 0 and wrote == set()
    # the hit ran the other lr: it went in as an argument, not a constant
    assert not np.array_equal(first[-1], second[-1])


def test_key_holds_the_microbatch_count(scratch_cache):
    _, second, _, wrote = _train_step_then(
        scratch_cache, lambda: _site_train_step(num_microbatches=2))
    assert len(wrote) == 1 and wrote.pop().startswith("jit_step-")
    assert np.isfinite(second[0])


def test_key_holds_the_precision_mode(scratch_cache):
    """A hit across modes would train parity's program under bf16."""
    from dcnn_tpu.core.precision import get_precision_mode, set_precision

    def in_the_other_mode():
        mode = get_precision_mode()
        set_precision("bf16" if mode != "bf16" else "parity")
        try:
            return _site_train_step()
        finally:
            set_precision(mode)

    first, second, _, wrote = _train_step_then(scratch_cache,
                                               in_the_other_mode)
    assert any(n.startswith("jit_step-") for n in wrote)
    assert not np.array_equal(first[1], second[1])


# -------------------------------------------------- across processes and crashes

_TRAIN_ONCE = textwrap.dedent("""
    from dcnn_tpu.utils import enable_compile_cache
    root = enable_compile_cache(0)
    from test_compile_cache import _hits_misses, _site_train_step
    loss = _site_train_step()[0]
    print(root, float(loss).hex(), *map(int, _hits_misses()))
""")


def test_second_process_on_the_directory_writes_nothing_same_loss(tmp_path):
    root = str(tmp_path / "shared")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join((REPO, os.path.join(REPO, "tests"))),
               JAX_COMPILATION_CACHE_DIR=root)

    def run():
        out = subprocess.run([sys.executable, "-c", _TRAIN_ONCE], env=env,
                             cwd=REPO, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        where, loss, hits, misses = out.stdout.split()
        assert where == root
        return loss, int(hits), int(misses), cc.cache_entries(root)

    loss_a, _, misses_a, entries_a = run()
    assert misses_a == len(entries_a) > 0
    loss_b, hits_b, misses_b, entries_b = run()
    assert entries_b == entries_a and misses_b == 0 and hits_b == misses_a
    assert loss_b == loss_a


@contextlib.contextmanager
def _own_cache(monkeypatch, root):
    """``root`` as the program's own cache directory (no
    ``JAX_COMPILATION_CACHE_DIR``): ``enable_compile_cache`` stamps, sweeps
    and registers it. Yields a function that enables it as a new process
    would."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", root)

    def enable():
        from jax._src import compilation_cache as jcc
        jcc.reset_cache()
        jax.clear_caches()
        cc._SESSIONS.clear()
        assert cc.enable_compile_cache(0) == root

    with _cache_at(root):
        yield enable


def _quarantined():
    return get_registry().counter("compile_cache_quarantined_total").value


def test_another_runtimes_root_is_dropped_and_the_step_compiles(
        tmp_path, monkeypatch):
    root = str(tmp_path / "own")
    os.makedirs(root)
    marker = os.path.join(root, ".runtime-fingerprint")
    with open(marker, "w", encoding="utf-8") as f:
        f.write("jax=0.0.0 jaxlib=0.0.0\n")
    _mint(root, "jit_step-minted-by-another-jaxlib")
    with _own_cache(monkeypatch, root) as enable:
        enable()
        assert cc.cache_entries(root) == set()
        with open(marker, encoding="utf-8") as f:
            assert f.read().split() == [f"jax={jax.__version__}",
                                        f"jaxlib={jaxlib.__version__}"]
        bits = _site_train_step()
    assert np.isfinite(bits[0])
    assert any(n.startswith("jit_step-") for n in cc.cache_entries(root))


def test_dead_writers_truncated_payload_is_swept_and_the_step_compiles(
        tmp_path, monkeypatch):
    root = str(tmp_path / "own")
    with _own_cache(monkeypatch, root) as enable:
        enable()
        first = _site_train_step()
        minted = cc.cache_entries(root)
        torn = [n for n in minted if n.startswith("jit_step-")]
        assert len(torn) == 1
        # the writer dies: its payload half written, its session never
        # committed, its marker left behind under a pid that is gone
        path = os.path.join(root, torn[0])
        with open(path, "rb") as f:
            payload = f.read()
        with open(path, "wb") as f:
            f.write(payload[:len(payload) // 2])
        os.rename(os.path.join(root, cc._INFLIGHT, str(os.getpid())),
                  os.path.join(root, cc._INFLIGHT, str(2 ** 22 - 7)))
        swept = _quarantined()
        enable()
        assert _quarantined() - swept == len(minted)
        assert cc.cache_entries(root) == set()
        second = _site_train_step()
        assert cc.cache_entries(root) == minted
    _assert_same_bits(first, second)
