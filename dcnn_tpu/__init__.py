"""dcnn_tpu — a TPU-native deep-learning framework.

A from-scratch JAX/XLA/Pallas framework with the capabilities of the reference
C++/CUDA framework tungphambasement/DCNN (``tnn``): an NCHW CNN layer library
with Sequential container + builder + JSON config, optimizers/losses/schedulers,
data loaders + augmentations, checkpointing, per-layer profiling, and — as the
distributed core — microbatched pipeline parallelism (sync / semi-async /
compiled 1F1B over a TPU mesh) plus data-parallel sharding via ``jax.sharding``.

Design stance (see SURVEY.md §7): idiomatic JAX — jit-compiled pure functions,
pytree parameters, functional optimizers, ``shard_map`` over a device Mesh with
XLA collectives over ICI — not a translation of the reference's mutable
object-per-layer CUDA design.
"""

__version__ = "0.1.0"

from .utils.env import get_env as _get_env

if _get_env("DCNN_DEBUG", False):
    # the 'debug build' switch (reference ENABLE_DEBUG -> ASan,
    # CMakeLists.txt:22): numeric sanitizers on for the whole process
    from .core.debug import enable_debug_mode as _edm

    _edm()

from . import core, nn, obs, ops, optim

__all__ = ["core", "nn", "obs", "ops", "optim", "__version__"]
