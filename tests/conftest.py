"""Test config: force the JAX CPU backend with 8 virtual devices.

Mirrors the reference's test strategy of exercising multi-stage machinery
in-process without real hardware (SURVEY.md §4.7): the pipeline/sharding test
suites run over an 8-device CPU mesh exactly as they would over a v5e-8.
Must run before jax is imported anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache): recompiling every jitted step dominates test time;
# the cache makes reruns near-instant.
from dcnn_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
