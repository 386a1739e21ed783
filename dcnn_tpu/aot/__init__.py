"""Ahead-of-time executable cache — kill the compile wall.

A cold XLA compile of the headline train step costs far more than the
steps it then runs; every serve-replica spin-up, hot-swap rejoin, and
elastic reshard re-jit pays the same class of tax. The
reference framework never compiles (hand-written kernels dispatch
instantly); this subsystem gives the JAX reproduction the same
operational property the way Pathways-style systems do — compile once,
persist the lowered executable, and let every later process deserialize
it instead of retracing and recompiling (Barham et al., 2022).

Pieces (see each module's docstring for contracts):

- :mod:`~dcnn_tpu.aot.keys` — no-trace cache keys over (jaxlib/XLA
  version, device/topology fingerprint, input avals, precision mode,
  donation signature, closed-over-config digest);
- :mod:`~dcnn_tpu.aot.cache` — :class:`ExecutableCache`: checksum
  MANIFEST, atomic commits, cross-process locking, keep-K LRU GC,
  corrupt-entry quarantine;
- :mod:`~dcnn_tpu.aot.warm` — :func:`warm_or_compile`,
  :class:`WarmCallable`, env-gated :func:`maybe_warm`.

Wired into the four compile walls: ``Trainer`` train/multi steps
(``TrainingConfig.aot_cache_dir`` / ``AOT_CACHE``), ``serve/engine``
per-bucket sessions (replica fleets + hot-swap), ``parallel/elastic``
reshard re-jits, and the ``parallel/compiled_pipeline`` dispatchers.
CLI: ``python -m dcnn_tpu.aot`` (list / ``--gc`` / ``--prewarm``).
Everything is OFF unless ``AOT_CACHE`` (or an explicit dir) is set.
"""

from .cache import ExecutableCache
from .keys import backend_fingerprint, cache_key, digest, digest_arrays
from .warm import (WarmCallable, aot_dir, enabled_root, get_cache,
                   maybe_warm, warm_or_compile)

__all__ = [
    "ExecutableCache", "WarmCallable", "warm_or_compile", "maybe_warm",
    "get_cache", "enabled_root", "aot_dir", "cache_key", "digest",
    "digest_arrays", "backend_fingerprint",
]
