"""Session-integrity protocol of the shared persistent-compile-cache.

Background (dcnn_tpu/utils/compile_cache.py): a process that corrupts
its own memory can mint a *structurally valid* cache entry whose replay
crashes every later process, so an entry only survives the enable-time
sweep if the session that minted it exited cleanly. These tests drive
the pure helpers directly against tmp_path roots — no jax, no
subprocesses, no sleeps.
"""

import os

import pytest

from dcnn_tpu.utils import compile_cache as cc


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
