"""Warm-start compiled executables from the persistent cache.

The public surface of the subsystem:

- :func:`warm_or_compile` — given a jitted callable and its call avals,
  return a ready executable: **hit** = deserialize the cached bytes
  (sub-second, no trace, no XLA compile), **miss** = trace + compile as
  usual, then serialize and atomically commit for every later process.
  Backends whose executables can't (de)serialize fall back to plain
  compilation — the answer is always a working executable, the cache is
  only ever an accelerant.
- :class:`WarmCallable` — a drop-in wrapper around a jitted callable that
  runs :func:`warm_or_compile` once per argument signature and then
  dispatches straight to the loaded executable; unknown signatures fall
  through per-signature, so shape-polymorphic callers keep working.
- :func:`get_cache` / :func:`maybe_warm` — env-gated plumbing: the store's
  root is ``utils.compile_cache.resolve_cache_root`` (``AOT_CACHE`` >
  ``DCNN_COMPILE_CACHE`` > ``<checkout>/.jax_cache``; jax's own
  compilation cache is placed separately), with executables under
  ``<root>/aot``; the subsystem is OFF unless ``AOT_CACHE`` is set or a
  call site passes an explicit dir, so default runs and tier-1 behave
  exactly as before.

Hit/miss/deserialize-time accounting flows through
``obs.xla.record_aot`` (``aot_hits_total`` / ``aot_misses_total`` /
``aot_deserialize_seconds_total`` …) and compiles through the existing
``obs.xla.record_compile`` counters, so the 149.9 s wall this subsystem
kills stays a scrapeable series either way.
"""

from __future__ import annotations

import contextlib
import os
import pickle
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..resilience.faults import InjectedCrash
from .cache import ExecutableCache
from .keys import backend_fingerprint, cache_key, short_avals

_CACHES: Dict[str, ExecutableCache] = {}  # one instance (and sweep) per dir


def enabled_root(explicit: Optional[str] = None) -> Optional[str]:
    """The cache root when the subsystem is enabled, else ``None``.
    Explicit beats ``AOT_CACHE``; ``DCNN_COMPILE_CACHE`` alone does NOT
    enable AOT (it only names a fallback root for the CLI — see
    ``utils.compile_cache.resolve_cache_root``)."""
    if explicit:
        return explicit
    return os.environ.get("AOT_CACHE", "").strip() or None


def aot_dir(root: str) -> str:
    """Executables live under ``<root>/aot`` — beside (never inside) the
    XLA persistent-cache files at the root itself."""
    return os.path.join(root, "aot")


def get_cache(explicit: Optional[str] = None, *,
              keep: Optional[int] = None,
              registry=None) -> Optional[ExecutableCache]:
    """The process-shared :class:`ExecutableCache` for the resolved root,
    or ``None`` when the subsystem is disabled."""
    root = enabled_root(explicit)
    if root is None:
        return None
    d = os.path.abspath(aot_dir(root))
    cache = _CACHES.get(d)
    if cache is None:
        cache = ExecutableCache(d, keep=keep, registry=registry)
        _CACHES[d] = cache
    return cache


def _serializer():
    from jax.experimental import serialize_executable as se
    return se


def _dump(compiled) -> bytes:
    """Payload = the serialized executable plus the ids of the devices it
    was compiled for, in assignment order: ``deserialize_and_load`` would
    otherwise load it for *every* device of the backend and the first call
    fails with "expected N shards"."""
    blob, in_tree, out_tree = _serializer().serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((blob, in_tree, out_tree, ids))


def _load(payload: bytes):
    import jax

    blob, in_tree, out_tree, ids = pickle.loads(payload)
    by_id = {d.id: d for d in jax.devices()}
    return _serializer().deserialize_and_load(
        blob, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids])


def _serialize_validated(compiled) -> Optional[bytes]:
    """Serialize ``compiled`` and prove the payload loads back, or
    ``None``. The load-back is not paranoia: XLA:CPU executables that
    were themselves *served from the persistent compilation cache*
    serialize to payloads missing their jitted symbols ("Symbols not
    found" at deserialize) — committing one would poison the cache for
    every later process, so nothing is committed until the bytes have
    deserialized once right here."""
    try:
        payload = _dump(compiled)
        _load(payload)
    except InjectedCrash:
        raise
    except Exception:
        return None
    return payload


@contextlib.contextmanager
def _persistent_cache_bypassed():
    """Force the next ``compile()`` to be a true cold compile (whose
    executable serializes completely — see :func:`_serialize_validated`):
    detach jax's persistent compilation cache AND drop the in-memory
    executable caches, which otherwise hand back the same
    incompletely-serializable executable in 10 ms. ``clear_caches`` makes
    other live jitted fns re-trace on their next call (served from the
    persistent text cache once it is re-attached) — a one-time cost paid
    only on this rare recovery path, never in steady state. The config
    toggle is a process global: a concurrent compile on another thread
    would at worst skip the text cache once or fail this retry's
    validation again (→ fallback, no commit) — never an incorrect
    commit."""
    import jax

    old = getattr(jax.config, "jax_compilation_cache_dir", None)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.clear_caches()
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def warm_or_compile(jitted: Any, *args: Any,
                    cache: ExecutableCache,
                    what: str = "",
                    config: Optional[Any] = None,
                    donate: Tuple[int, ...] = (),
                    extra: Optional[Dict[str, Any]] = None,
                    registry=None) -> Tuple[Callable, Dict[str, Any]]:
    """Return ``(executable, info)`` for ``jitted`` at the avals of
    ``args`` (concrete arrays or ``jax.ShapeDtypeStruct`` specs).

    ``config`` must digest everything ``jitted`` closes over that shapes
    the compiled program (model config, optimizer hyperparameters, loss
    identity, weights for serving graphs — see ``keys.py``); ``donate``
    is the jit's donate_argnums. ``info`` carries ``key``, ``hit``,
    ``deserialize_s`` / ``compile_s``, and ``committed``."""
    from ..obs.xla import record_aot, record_compile

    fp = backend_fingerprint()
    key, material = cache_key(args, config=config, donate=donate,
                              extra=extra, fingerprint=fp)
    info: Dict[str, Any] = {"key": key, "hit": False, "committed": False}

    payload = None
    try:
        payload = cache.lookup(key, fingerprint=fp)
    except InjectedCrash:
        raise
    except Exception:
        payload = None  # unreadable cache == miss; compilation still works
    if payload is not None:
        t0 = time.perf_counter()
        try:
            exe = _load(payload)
        except InjectedCrash:
            raise
        except Exception as e:
            # checksum-valid bytes that won't load here: quarantine and
            # fall through to a fresh compile under the same key
            cache.quarantine(key, f"deserialize failed: {type(e).__name__}")
        else:
            dt = time.perf_counter() - t0
            record_aot("hit", dt, registry=registry)
            info.update({"hit": True, "deserialize_s": round(dt, 4)})
            return exe, info

    record_aot("miss", registry=registry)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    record_compile(compile_s, what=what, registry=registry)
    info["compile_s"] = round(compile_s, 4)
    payload = _serialize_validated(compiled)
    if payload is None:
        # most likely this compile was served from the persistent TEXT
        # cache, whose executables don't re-serialize completely on CPU
        # backends: pay one true cold compile to obtain committable
        # bytes (the whole point of being here is that every LATER
        # process skips this wall)
        try:
            with _persistent_cache_bypassed():
                t0 = time.perf_counter()
                compiled2 = jitted.lower(*args).compile()
                record_compile(time.perf_counter() - t0, what=what,
                               registry=registry)
            payload = _serialize_validated(compiled2)
            if payload is not None:
                compiled = compiled2
        except InjectedCrash:
            raise
        except Exception:
            payload = None
    if payload is None:
        # backend without executable serialization (or a full/odd disk):
        # the compiled executable is still perfectly usable, this process
        # just can't seed the cache
        record_aot("fallback", registry=registry)
    else:
        try:
            info["committed"] = cache.commit(key, payload, meta={
                "what": what, "avals": short_avals(material),
                "material": material})
        except InjectedCrash:
            raise
        except Exception:
            record_aot("fallback", registry=registry)
    return compiled, info


class WarmCallable:
    """AOT-warmed dispatch around one jitted callable.

    The first call at each argument signature runs
    :func:`warm_or_compile`; later calls dispatch straight to the loaded
    executable. Any failure in the warm path (a backend that can't
    deserialize, a cache dir that vanished) permanently falls back to the
    wrapped jit for that signature — the wrapper can slow down, never
    break. Execution errors from the chosen executable propagate
    untouched."""

    def __init__(self, jitted: Any, cache: ExecutableCache, *,
                 what: str = "", config: Optional[Any] = None,
                 donate: Tuple[int, ...] = (),
                 extra: Optional[Dict[str, Any]] = None, registry=None):
        self._jitted = jitted
        self._cache = cache
        self._what = what
        self._config = config
        self._donate = tuple(donate)
        self._extra = extra
        self._registry = registry
        self._exes: Dict[Any, Any] = {}     # sig tuple -> executable
        self.last_info: Optional[Dict[str, Any]] = None
        self.__wrapped__ = jitted

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    @staticmethod
    def _sig(args: Tuple[Any, ...]) -> Any:
        """Hashable per-call dispatch signature. This runs on EVERY call
        of the wrapped step (once per training batch), so it must stay
        cheap: direct ``.shape``/``.dtype`` attribute reads for array
        leaves (no ShapedArray construction, no JSON) with
        ``shaped_abstractify`` only for the rare non-array leaf (Python
        scalars like lr). The full ``aval_signature`` JSON form is only
        computed on the once-per-signature warm path (inside
        ``warm_or_compile``'s key derivation)."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                a = jax.api_util.shaped_abstractify(leaf)
                shape, dtype = a.shape, a.dtype
                weak = bool(getattr(a, "weak_type", False))
            else:
                weak = bool(getattr(leaf, "weak_type", False))
            sig.append((tuple(shape), str(dtype), weak))
        return treedef, tuple(sig)

    def __call__(self, *args):
        try:
            sig = self._sig(args)
        except Exception:
            return self._jitted(*args)
        exe = self._exes.get(sig)
        if exe is None:
            try:
                exe, self.last_info = warm_or_compile(
                    self._jitted, *args, cache=self._cache, what=self._what,
                    config=self._config, donate=self._donate,
                    extra=self._extra, registry=self._registry)
            except InjectedCrash:
                raise
            except Exception:
                exe = self._jitted
            self._exes[sig] = exe
        return exe(*args)

    def __repr__(self) -> str:
        return (f"WarmCallable({self._what or 'jit'}, "
                f"signatures={len(self._exes)}, cache={self._cache.root!r})")


def maybe_warm(jitted: Any, *, what: str = "",
               config: Optional[Any] = None,
               donate: Tuple[int, ...] = (),
               extra: Optional[Dict[str, Any]] = None,
               cache_dir: Optional[str] = None,
               registry=None) -> Any:
    """Wrap ``jitted`` in a :class:`WarmCallable` when the subsystem is
    enabled (``AOT_CACHE`` env or an explicit ``cache_dir``); otherwise
    return it unchanged. The zero-risk wiring helper the pipeline
    dispatchers use."""
    try:
        cache = get_cache(cache_dir, registry=registry)
    except Exception:
        return jitted
    if cache is None:
        return jitted
    return WarmCallable(jitted, cache, what=what, config=config,
                        donate=donate, extra=extra, registry=registry)
