"""Utils tests: compression wire format, env config, hardware info
(reference ``include/utils/`` + ``include/pipeline/compression_impl/``;
SURVEY.md §2.5)."""

import numpy as np
import pytest

from dcnn_tpu.utils.compression import (
    MetaCompressor, RawCompressor, ZlibCompressor,
)
from dcnn_tpu.utils.env import get_env, load_env_file
from dcnn_tpu.utils.hardware import HardwareInfo, get_memory_usage_kb


# -- compression (meta_compressor.hpp:10-35 codec-id framing) --

def test_meta_compressor_roundtrip_all_codecs():
    mc = MetaCompressor()
    payload = bytes(range(256)) * 100
    for codec in mc.codecs.values():
        blob = mc.compress(payload, codec)
        assert blob[0] == codec.codec_id          # wire: 1-byte codec id
        assert mc.decompress(blob) == payload     # dispatch by id


def test_meta_compressor_cross_codec_decompress():
    """A blob compressed with any registered codec decompresses through the
    SAME MetaCompressor regardless of its default — the codec id on the wire
    decides (the worker-deployment contract for mixed-codec peers)."""
    zl = MetaCompressor(default=ZlibCompressor())
    raw = MetaCompressor(default=RawCompressor())
    payload = b"activation bytes" * 512
    assert raw.decompress(zl.compress(payload)) == payload
    assert zl.decompress(raw.compress(payload)) == payload


def test_meta_compressor_unknown_codec():
    mc = MetaCompressor()
    blob = bytearray(mc.compress(b"x" * 64))
    blob[0] = 250
    with pytest.raises(ValueError, match="unknown codec"):
        mc.decompress(bytes(blob))


@pytest.mark.parametrize("dtype", [np.float32, np.int64, np.uint8])
def test_array_framing_roundtrip(dtype):
    """Tensor framing (binary_serializer.hpp:27-35: rank + dims + data)."""
    mc = MetaCompressor()
    arr = (np.arange(2 * 3 * 4) % 7).astype(dtype).reshape(2, 3, 4)
    back = mc.decompress_array(mc.compress_array(arr))
    assert back.dtype == arr.dtype and back.shape == arr.shape
    np.testing.assert_array_equal(back, arr)


# -- env config (env.hpp:41-140) --

def test_load_env_file_parsing(tmp_path, monkeypatch):
    p = tmp_path / ".env"
    p.write_text("# comment\n\nA_KEY = 42\nB_KEY='quoted value'\n"
                 "C_KEY=\"dq\"\nmalformed line\n")
    # setenv-then-delenv records the keys' original absence on monkeypatch's
    # restore stack, so the direct os.environ writes load_env_file makes are
    # cleaned up at teardown instead of leaking into later tests
    for k in ("A_KEY", "B_KEY", "C_KEY"):
        monkeypatch.setenv(k, "placeholder")
        monkeypatch.delenv(k)
    assert load_env_file(str(p)) is True
    assert get_env("A_KEY", 0) == 42
    assert get_env("B_KEY", "") == "quoted value"
    assert get_env("C_KEY", "") == "dq"
    # no-override semantics: existing env wins unless override=True
    monkeypatch.setenv("A_KEY", "7")
    load_env_file(str(p))
    assert get_env("A_KEY", 0) == 7
    load_env_file(str(p), override=True)
    assert get_env("A_KEY", 0) == 42


def test_load_env_file_missing():
    assert load_env_file("/nonexistent/.env") is False


def test_get_env_typed(monkeypatch):
    monkeypatch.setenv("X_INT", "5")
    monkeypatch.setenv("X_FLOAT", "2.5")
    monkeypatch.setenv("X_BOOL", "YES")
    monkeypatch.setenv("X_BAD", "notanint")
    assert get_env("X_INT", 0) == 5
    assert get_env("X_FLOAT", 0.0) == 2.5
    assert get_env("X_BOOL", False) is True
    assert get_env("MISSING_KEY", "fallback") == "fallback"
    with pytest.raises(ValueError):
        get_env("X_BAD", 0)
    # explicit cast wins over default-type parsing
    assert get_env("X_INT", 0, cast=float) == 5.0


# -- hardware info (hardware_info.hpp; slimmed per SURVEY §2.5) --

def test_hardware_info_collect_keys():
    info = HardwareInfo.collect()
    assert info["host"]["cpu_count"] >= 1
    assert info["host"]["ram_total_kb"] > 0
    assert isinstance(info["devices"], list) and info["devices"]
    assert info["default_backend"]
    assert get_memory_usage_kb() > 0


def test_hard_fence_tree_shapes_and_dtypes():
    """hard_fence must handle every leaf shape/dtype the framework fences:
    multi-leaf trees, typed PRNG keys (extended dtype), bools/ints, scalars,
    empty leaves, and plain numpy leaves (review r5 regressions)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dcnn_tpu.core.fence import hard_fence

    hard_fence({})                                   # empty tree
    hard_fence(jnp.ones(3))                          # single leaf
    hard_fence({"a": jnp.ones(3), "b": jnp.zeros((2, 2)),
                "c": jnp.asarray(1), "d": jnp.asarray(True),
                "e": jnp.ones(0), "f": np.ones(2),
                "rng": jax.random.key(0),            # extended dtype
                "rngs": jax.random.split(jax.random.key(1), 3)})


def test_hard_fence_cross_device_tree():
    """Leaves committed to different devices fence without a jit
    mixed-device error (PipelineCoordinator.join's shape of tree)."""
    import jax
    import jax.numpy as jnp

    from dcnn_tpu.core.fence import hard_fence

    devs = jax.devices()
    if len(devs) < 2:
        import pytest
        pytest.skip("needs 2 devices")
    tree = [jax.device_put(jnp.ones(3) * i, devs[i % len(devs)])
            for i in range(4)]
    hard_fence(tree)
