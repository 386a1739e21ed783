"""Shared persistent-XLA-compile-cache setup.

One helper for every entry point that compiles (``examples/common.py``,
``bench.py``, ``benchmarks/``, ``tests/conftest.py``, ``chip_smoke.py``), so
reruns hit the cache and the thresholds do not drift between call sites.

Where jax's cache lives (:func:`enable_compile_cache`):

1. ``JAX_COMPILATION_CACHE_DIR`` set — jax already reads it; the directory
   belongs to whoever exported it. The helper sets no directory in code and
   neither stamps, rotates, sweeps nor deletes anything in it.
2. unset — ``<checkout>/.jax_cache`` (:data:`DEFAULT_CACHE_DIR`): one fixed
   path, the same from every process and working directory, because the
   path must not move between runs for entries to be found again. The
   program owns this directory, so the session-integrity protocol below
   (fingerprint stamp, crashed-writer sweep) runs on it. There is no
   torn-entry sweep: the installed jax writes no ``-atime`` sibling unless
   eviction is on, so "payload without sibling" would match every entry.
"""

from __future__ import annotations

import atexit
import os
import signal

# <checkout>/.jax_cache — dcnn_tpu/utils/compile_cache.py is three levels
# below the checkout root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_entries(root: str) -> "set[str]":
    """Names of the compiled-executable payloads in a jax cache directory
    (empty for a directory that does not exist yet)."""
    try:
        return {n for n in os.listdir(root) if n.endswith("-cache")}
    except OSError:
        return set()


def _rotate_if_stale(root: str, fingerprint: str) -> None:
    """Drop a cache root whose entries were minted by a different
    runtime. A persistent cache entry is a serialized XLA executable:
    replaying one compiled by another jaxlib (container image bump
    between sessions) — or torn by a process that died mid-write —
    crashes at *execution* time with allocator-state-dependent signals,
    which is far worse than a cold compile. The fingerprint file is the
    cheap guard for the version half of that risk; a mismatch (or an
    unreadable root) rotates the directory aside rather than trusting
    it."""
    import shutil

    marker = os.path.join(root, ".runtime-fingerprint")
    try:
        with open(marker, "r", encoding="utf-8") as f:
            if f.read().strip() == fingerprint:
                return
    except OSError:
        # no marker yet: fresh root, or a pre-fingerprint cache — keep
        # its entries and stamp it below (rotation applies only to a
        # *mismatched* stamp, where staleness is proven)
        pass
    if os.path.isdir(root) and os.path.exists(marker):
        # fingerprint present but wrong: entries are for another runtime
        try:
            shutil.rmtree(root)
        except OSError:
            return  # shared/busy dir: leave it; jax will still function
    try:
        os.makedirs(root, exist_ok=True)
        tmp = marker + f".tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(fingerprint + "\n")
        os.replace(tmp, marker)
    except OSError:
        pass  # unwritable root: cache writes will no-op too


# -- session-integrity protocol (quarantine of crashed writers) ---------
#
# A process that corrupts its own memory (a jaxlib SIGSEGV/SIGABRT) can
# serialize a *structurally valid* executable whose replay crashes every
# LATER process at dispatch time — observed live: a
# single stale ``jit_update-*`` entry minted by a crashing test run made
# an otherwise-green suite segfault on ~60% of runs until the entry was
# deleted, and each crashed run can mint more such entries (the
# infection sustains itself across sessions). No structural check can
# see this (the bytes decompress fine), so the guard is provenance: an
# entry only survives if the process that minted it EXITED CLEANLY.
#
#   <root>/.committed      names of ``*-cache`` payloads whose minting
#                          session finished cleanly (atexit / SIGTERM)
#   <root>/.inflight/<pid> live marker per enabling process — a sweep
#                          never deletes while another enabler is alive
#                          (its fresh entries are uncommitted by design)
#
# A root with entries but no manifest is grandfathered (same policy as
# the pre-fingerprint case in ``_rotate_if_stale``): its entries are
# committed wholesale rather than dropped, so existing warm caches keep
# hitting; the protocol protects every mint from then on.

_COMMITTED = ".committed"
_INFLIGHT = ".inflight"

# root -> names of ``*-cache`` payloads present when the session began
_SESSIONS: "dict[str, set[str]]" = {}
_HOOKS_INSTALLED = False


def _read_committed(root: str) -> "set[str]":
    try:
        with open(os.path.join(root, _COMMITTED), encoding="utf-8") as f:
            return {ln.strip() for ln in f if ln.strip()}
    except OSError:
        return set()


def _write_committed(root: str, names: "set[str]") -> None:
    path = os.path.join(root, _COMMITTED)
    tmp = path + f".tmp.{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            f.write("".join(n + "\n" for n in sorted(names)))
        os.replace(tmp, path)
    except OSError:
        pass  # unwritable root: cache writes no-op too


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True  # EPERM: alive, someone else's
    return True


def _other_live_enablers(root: str) -> bool:
    """True if another live process has this root enabled. Dead markers
    (crashed or SIGKILLed enablers) are pruned on the way."""
    d = os.path.join(root, _INFLIGHT)
    try:
        names = os.listdir(d)
    except OSError:
        return False
    alive = False
    for n in names:
        try:
            pid = int(n)
        except ValueError:
            continue
        if pid == os.getpid():
            continue
        if _pid_alive(pid):
            alive = True
        else:
            try:
                os.unlink(os.path.join(d, n))
            except OSError:
                pass
    return alive


def _sweep_uncommitted(root: str) -> int:
    """Quarantine entries whose minting session never exited cleanly.

    Skipped entirely while another live enabler shares the root (its
    current mints are legitimately uncommitted); with no manifest at all
    the present entries are grandfathered-committed instead of dropped."""
    present = cache_entries(root)
    if not os.path.exists(os.path.join(root, _COMMITTED)):
        # grandfather a pre-protocol root (possibly empty: the write
        # still matters — it arms the sweep for entries minted by a
        # first session that then crashes)
        _write_committed(root, present)
        return 0
    if not present:
        return 0
    if _other_live_enablers(root):
        return 0
    committed = _read_committed(root)
    n = 0
    for name in present - committed:
        for victim in (name, f"{name[:-len('-cache')]}-atime"):
            try:
                os.unlink(os.path.join(root, victim))
            except OSError:
                pass
        n += 1
    return n


def _finish_sessions() -> None:
    """Clean-exit hook: commit every entry minted during this session
    (present now, absent at enable time), prune names whose files are
    gone, drop the inflight marker."""
    for root, before in list(_SESSIONS.items()):
        present = cache_entries(root)
        _write_committed(root, (_read_committed(root)
                                | (present - before)) & present)
        try:
            os.unlink(os.path.join(root, _INFLIGHT, str(os.getpid())))
        except OSError:
            pass
    _SESSIONS.clear()


def _on_sigterm(signum, frame):  # pragma: no cover - exercised via kill
    # a TERM kill (runner timeout) is an orderly death, not memory
    # corruption: commit the session so the cache stays warm, then die
    # with the default disposition so the exit code stays truthful
    _finish_sessions()
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    os.kill(os.getpid(), signal.SIGTERM)


def _register_session(root: str) -> None:
    global _HOOKS_INSTALLED
    if root in _SESSIONS:
        return
    _SESSIONS[root] = cache_entries(root)
    try:
        os.makedirs(os.path.join(root, _INFLIGHT), exist_ok=True)
        # existence-only marker: content is irrelevant, a torn write is
        # indistinguishable from a whole one
        marker = os.path.join(root, _INFLIGHT, str(os.getpid()))
        with open(marker, "w", encoding="utf-8") as f:  # dcnn: disable=AT01
            f.write("")
    except OSError:
        pass
    if not _HOOKS_INSTALLED:
        _HOOKS_INSTALLED = True
        atexit.register(_finish_sessions)
        try:
            # chain only onto the default disposition — never fight a
            # handler the host application installed
            if signal.getsignal(signal.SIGTERM) is signal.SIG_DFL:
                signal.signal(signal.SIGTERM, _on_sigterm)
        except (ValueError, OSError):
            pass  # non-main thread / exotic platform: atexit still covers


def enable_compile_cache(min_compile_secs: float = 0.5) -> str:
    """Turn jax's persistent compilation cache on and return its directory
    (which one: module docstring). Call before the first compile: jax binds
    the cache to its directory on first use. Idempotent."""
    import jax
    import jaxlib

    theirs = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    root = theirs or DEFAULT_CACHE_DIR
    if not theirs:
        # the program's own directory: guard it, then point jax at it
        _rotate_if_stale(root, f"jax={jax.__version__} "
                               f"jaxlib={jaxlib.__version__}")
        swept = _sweep_uncommitted(root)
        _register_session(root)
        from ..obs import get_registry
        get_registry().counter(
            "compile_cache_quarantined_total",
            "cache entries dropped as torn or minted by a session that "
            "never exited cleanly").inc(swept)
        jax.config.update("jax_compilation_cache_dir", root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return root
