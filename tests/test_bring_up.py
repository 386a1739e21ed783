"""What the program does before and around its first compile: where the
compile cache lives, which backend an entry point accepts, which native
library it loads, and ``chip_smoke.py`` itself (refusal on the CPU; its phase
functions at tiny sizes, Pallas in interpret mode).
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_path, env, cwd=REPO, timeout=300):
    argv = ([sys.executable, code_or_path] if os.path.isfile(code_or_path)
            else [sys.executable, "-c", code_or_path])
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


def _env(**overrides):
    """The parent's env with the keys under test pinned: a value sets,
    ``None`` removes."""
    env = dict(os.environ, PYTHONPATH=REPO)
    for k, v in overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


# ------------------------------------------------------------ compile cache

_ENABLE = ("import jax\n"
           "from dcnn_tpu.utils import enable_compile_cache\n"
           "print(enable_compile_cache())\n"
           "print(jax.config.jax_compilation_cache_dir)\n")


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's directory stays that one and
    the helper neither stamps, sweeps nor deletes anything in it — not even
    what its own protocol would call torn or stale."""
    d = tmp_path / "theirs"
    d.mkdir()
    (d / "jit_torn-cache").write_bytes(b"no atime sibling")
    (d / ".runtime-fingerprint").write_text("jax=0.0.0 jaxlib=0.0.0\n")
    before = {p.name: p.read_bytes() for p in d.iterdir()}
    out = _run(_ENABLE, _env(JAX_COMPILATION_CACHE_DIR=str(d),
                             JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(d), str(d)]
    assert {p.name: p.read_bytes() for p in d.iterdir()} == before


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache from any process and any cwd — and the
    two old knobs no longer move it."""
    want = os.path.join(REPO, ".jax_cache")
    env = _env(JAX_COMPILATION_CACHE_DIR=None, JAX_PLATFORMS="cpu",
               AOT_CACHE=str(tmp_path / "aot"),
               DCNN_COMPILE_CACHE=str(tmp_path / "legacy"))
    outs = [_run(_ENABLE, env, cwd=cwd) for cwd in (REPO, str(tmp_path))]
    for out in outs:
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split() == [want, want]
    assert not (tmp_path / "aot").exists()
    assert not (tmp_path / "legacy").exists()


# ---------------------------------------------------------- platform guard

def test_require_tpu_raises_on_a_cpu_nobody_asked_for(monkeypatch):
    from dcnn_tpu.core.device import require_tpu

    assert jax.default_backend() == "cpu"  # conftest
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    require_tpu("test")  # the one route to the CPU
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="backend 'cpu'.*not 'tpu'"):
        require_tpu("test")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # fell back: still no
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        require_tpu("test")


def test_bench_main_is_guarded(monkeypatch):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import bench

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="bench.py.*backend 'cpu'"):
        bench.main()


def test_example_trainer_without_a_chip_exits_nonzero(tmp_path):
    """No chip and no JAX_PLATFORMS: the trainer names the backend it found
    and stops, instead of training on the CPU and exiting 0."""
    out = _run(os.path.join(REPO, "examples", "tiny_imagenet_trainer.py"),
               _env(JAX_PLATFORMS=None, EPOCHS="1",
                    SNAPSHOT_DIR=str(tmp_path)), cwd=str(tmp_path))
    assert out.returncode != 0
    assert "backend 'cpu'" in out.stderr and "not 'tpu'" in out.stderr
    assert "epoch 1" not in out.stdout


# ---------------------------------------------------------- native library

def test_native_library_from_another_machine_is_not_loaded(monkeypatch):
    """The file name is keyed by sources, flags and host CPU: under another
    host's key the library this machine built is simply not found, and with
    no compiler the answer is 'absent', said out loud."""
    from dcnn_tpu import native

    if not native.available():
        pytest.skip(f"native library {native.status()}")
    mine = native._so_path(native._sources())
    assert os.path.isfile(mine)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_status", "not loaded yet")
    monkeypatch.setattr(native, "_host_cpu", lambda: "some other machine")
    monkeypatch.setattr(native, "_build", lambda so, srcs: "g++ is missing")
    theirs = native._so_path(native._sources())
    assert theirs != mine
    assert native.lib() is None
    assert native.status() == "absent: g++ is missing"
    # consumers degrade to numpy, bit-identically
    src = np.arange(12).reshape(4, 3)
    assert np.array_equal(native.gather_rows(src, np.array([2, 0])),
                          src[[2, 0]])


# --------------------------------------------------------------- chip smoke

def test_chip_smoke_refuses_the_cpu():
    """On the CPU — even the deliberate JAX_PLATFORMS=cpu — the smoke runs
    nothing, prints no result and exits non-zero."""
    out = _run(os.path.join(REPO, "chip_smoke.py"),
               _env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "backend 'cpu'" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(str(tmp_path / "chip_smoke.py"),
               _env(JAX_PLATFORMS="cpu", PYTHONPATH=None), cwd=str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.fixture
def smoke():
    """``chip_smoke`` with the examples dir resolving its own ``common``,
    not benchmarks/common which other tests may have loaded first."""
    saved = sys.modules.pop("common", None)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import chip_smoke

    ex_dir = os.path.join(REPO, "examples")
    sys.path.insert(0, ex_dir)
    try:
        yield chip_smoke
    finally:
        sys.path.remove(ex_dir)
        for name in ("common", "tiny_imagenet_trainer", "pipeline_trainer"):
            sys.modules.pop(name, None)
        if saved is not None:
            sys.modules["common"] = saved


# the smallest zoo model with BatchNorm (fold, int8) and enough layers for
# four stages: the tests exercise the smoke's code, not ResNet-18's compile
TINY = "mnist_cnn"


def test_chip_smoke_trainer_phase_tiny(smoke, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    got = smoke.phase_trainer(model_name=TINY, batch=8, steps=3, chunk=2)
    assert got["per-step"]["steps"] == 3 and got["one chunk"]["steps"] == 2
    from dcnn_tpu.core.precision import get_precision_mode
    assert get_precision_mode() == "parity"  # restored


def test_chip_smoke_server_phase_tiny(smoke):
    got = smoke.phase_server(model_name=TINY, max_batch=4, n_burst=6)
    assert got["folded"]["buckets_run"] == [1, 2, 4]
    for label in ("int8", "int8 fp32-glue"):  # on the CPU both promise it
        assert got[label]["batch_invariant"]
        assert got[label]["same_sample_served"] >= 3
        assert got[label]["same_sample_spread_across_buckets"] == 0.0
        assert got[label]["shed"] == 0


def test_chip_smoke_kernels_phase_interpret(smoke):
    got = smoke.phase_kernels(geometries=((1, 2, 64, 16),), interpret=True)
    assert set(got["b1 h2 S64 d16"]["max_rel_err"]) == {"out", "dq", "dk",
                                                        "dv"}


def test_chip_smoke_four_chips_phase_tiny(smoke, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    got = smoke.phase_four_chips(model_name=TINY, dp_batch=8, microbatch=2)
    assert set(got) == {"data_parallel", "compiled_pipeline",
                        "pipeline_coordinator"}

