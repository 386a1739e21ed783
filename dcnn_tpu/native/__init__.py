"""Native (C++) host-side kernels with ctypes bindings.

Reference equivalent: the reference's entire data layer is native C++
(``include/data_loading/``); here native code accelerates the host input
pipeline that feeds the TPU — CSV parse, label-record decode, u8→f32
normalize — chunk-parallel over hardware threads (``src/dataio.cpp``).

``lib()`` returns the loaded library, building it with g++ on first use
next to this file. The file name carries a digest of the sources, the
compiler flags and the host CPU (``-march=native`` code is only valid on
the CPU it was built for), so a library left behind by other sources or
carried in from another machine is simply not found and a fresh one is
built. Every consumer must fall back to the numpy path when ``available()``
is False — the framework never hard-requires the toolchain; ``status()``
says which of the two happened and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_DIR, "src")
_FLAGS = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
          "-pthread"]


def _sources() -> list:
    try:
        return sorted(
            os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
            if f.endswith(".cpp"))
    except OSError:
        return []


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the first CPU's model and
    feature flags (``/proc/cpuinfo``), else the coarse platform strings."""
    try:
        with open("/proc/cpuinfo", "r", encoding="utf-8") as f:
            keep = [ln.strip() for ln in f.read().split("\n\n")[0].splitlines()
                    if ln.split(":")[0].strip() in
                    ("vendor_id", "cpu family", "model", "model name",
                     "flags", "Features", "CPU implementer", "CPU part")]
        if keep:
            return "\n".join(keep)
    except OSError:
        pass
    return f"{platform.machine()} {platform.processor()}"


def _so_path(srcs: list) -> str:
    """``libdcnn_native.<key>.so`` for these sources on this host."""
    h = hashlib.sha256()
    for path in srcs:
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_cpu().encode())
    return os.path.join(_DIR, f"libdcnn_native.{h.hexdigest()[:16]}.so")


_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_status = "not loaded yet"


def status() -> str:
    """How the last ``lib()`` call ended: ``built``, ``loaded`` (a library
    with this host's key was already there) or ``absent: <why>``."""
    return _status


def _build(so: str, srcs: list) -> Optional[str]:
    """Compile ``srcs`` into ``so``; returns None on success, else why not.
    Compiles to a process-unique temp path and renames into place: rename
    is atomic, so concurrent first-use builds (multihost spawns N identical
    processes) can never CDLL a partially written .so."""
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, *srcs, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return None
    except FileNotFoundError:
        why = "g++ is missing"
    except subprocess.CalledProcessError as e:
        why = f"g++ failed (exit {e.returncode})"
    except (subprocess.SubprocessError, OSError) as e:
        why = f"build failed ({type(e).__name__})"
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return why


def _absent(why: str) -> None:
    global _build_failed, _status
    _build_failed = True
    _status = f"absent: {why}"


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _status
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    srcs = _sources()
    if not srcs:
        _absent("no sources")
        return None
    so = _so_path(srcs)
    how = "loaded"
    if not os.path.isfile(so):
        why = _build(so, srcs)
        if why is not None:
            _absent(why)
            return None
        how = "built"
    try:
        l = ctypes.CDLL(so)
    except OSError as e:
        _absent(f"dlopen failed ({e})")
        return None
    _status = how
    l.dcnn_u8_to_f32.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_float]
    l.dcnn_u8_to_f32.restype = None
    l.dcnn_decode_label_records.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32)]
    l.dcnn_decode_label_records.restype = ctypes.c_int
    l.dcnn_parse_label_csv.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_float, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32)]
    l.dcnn_parse_label_csv.restype = ctypes.c_int64
    # the file name's key covers the sources, so every symbol they define
    # is in a library found under it
    for fn in ("dcnn_lz4_compress", "dcnn_lz4_decompress"):
        getattr(l, fn).argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
        getattr(l, fn).restype = ctypes.c_int64
    l.dcnn_lz4_compress_bound.argtypes = [ctypes.c_int64]
    l.dcnn_lz4_compress_bound.restype = ctypes.c_int64
    l.dcnn_lz4_compress_hc.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int32]
    l.dcnn_lz4_compress_hc.restype = ctypes.c_int64
    for fn in ("dcnn_byte_shuffle", "dcnn_byte_unshuffle"):
        getattr(l, fn).argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, ctypes.c_int32]
        getattr(l, fn).restype = ctypes.c_int
    l.dcnn_gather_rows.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64]
    l.dcnn_gather_rows.restype = ctypes.c_int
    _lib = l
    return _lib


def _u8ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def byte_shuffle(data: bytes, typesize: int,
                 inverse: bool = False) -> Optional[bytes]:
    """Blosc-style byte-plane (un)shuffle. None if the lib is unavailable;
    raises on length % typesize != 0."""
    l = lib()
    if l is None:
        return None
    src = np.frombuffer(data, np.uint8)
    dst = np.empty(len(data), np.uint8)
    fn = l.dcnn_byte_unshuffle if inverse else l.dcnn_byte_shuffle
    if fn(_u8ptr(src), _u8ptr(dst), src.size, typesize) != 0:
        raise ValueError(f"byte_shuffle: {len(data)} % typesize {typesize}")
    return dst.tobytes()


def lz4_available() -> bool:
    return lib() is not None


def lz4_compress(data: bytes, level: int = 0) -> Optional[bytes]:
    """LZ4 block-format compress (native). ``level`` 0 = greedy single-probe
    matcher; >= 1 = HC hash-chain search (deeper with higher levels, same
    block format — the decoder cannot tell them apart). None if the lib is
    unavailable."""
    l = lib()
    if l is None:
        return None
    src = np.frombuffer(data, np.uint8)
    dst = np.empty(int(l.dcnn_lz4_compress_bound(len(data))), np.uint8)
    if level > 0:
        n = l.dcnn_lz4_compress_hc(_u8ptr(src), src.size, _u8ptr(dst),
                                   dst.size, level)
    else:
        n = l.dcnn_lz4_compress(_u8ptr(src), src.size, _u8ptr(dst), dst.size)
    if n < 0:
        raise ValueError("lz4 compress: destination bound overflow")
    return dst[:n].tobytes()


def lz4_decompress(data: bytes, raw_size: int) -> Optional[bytes]:
    """LZ4 block-format decompress into exactly raw_size bytes (native).
    None if the lib is unavailable; raises on malformed input."""
    l = lib()
    if l is None:
        return None
    src = np.frombuffer(data, np.uint8)
    dst = np.empty(raw_size, np.uint8)
    n = l.dcnn_lz4_decompress(_u8ptr(src), src.size, _u8ptr(dst), raw_size)
    if n != raw_size:
        raise ValueError(f"lz4 decompress: malformed stream (rc={n})")
    return dst.tobytes()


def available() -> bool:
    return lib() is not None


def gather_available() -> bool:
    return lib() is not None


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Row gather ``src[idx]`` — chunk-parallel native memcpy when the
    library is available, numpy fancy indexing otherwise. Bit-identical to
    ``src[idx]`` either way (the kernel is a pure per-row memcpy), which the
    streaming feed's numerics-parity guarantee depends on. Indices must be
    in ``[0, len(src))`` — negatives raise IndexError on BOTH paths (the
    native kernel cannot wrap, and allowing numpy wrap-around only in the
    fallback would make behavior toolchain-dependent)."""
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, np.int64)
    if idx.ndim != 1:
        raise ValueError(f"gather_rows needs a 1-D index, got {idx.ndim}-D")
    if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= src.shape[0]):
        raise IndexError(
            f"gather_rows: index out of range [0, {src.shape[0]})")
    l = lib()
    if l is None or src.ndim == 0:
        return src[idx]
    row_bytes = src.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    if row_bytes == 0:  # zero-size trailing dims: nothing to copy natively
        return src[idx]
    dst = np.empty((idx.size, *src.shape[1:]), src.dtype)
    rc = l.dcnn_gather_rows(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        idx.size, row_bytes, src.shape[0])
    if rc != 0:
        raise IndexError(
            f"gather_rows: index out of range for axis 0 of size "
            f"{src.shape[0]}")
    return dst


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0) -> np.ndarray:
    """Normalize a uint8 array to float32 (native if possible)."""
    src = np.ascontiguousarray(src, np.uint8)
    l = lib()
    if l is None:
        return src.astype(np.float32) * scale
    dst = np.empty(src.shape, np.float32)
    l.dcnn_u8_to_f32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        src.size, scale)
    return dst


def decode_label_records(raw: np.ndarray, n: int, skip_bytes: int,
                         label_index: int, img_bytes: int):
    """Decode n ``[labels…][pixels…]`` records → (images f32 scaled 1/255,
    labels int32). Returns None if the native library is unavailable."""
    l = lib()
    if l is None:
        return None
    raw = np.ascontiguousarray(raw, np.uint8)
    images = np.empty((n, img_bytes), np.float32)
    labels = np.empty((n,), np.int32)
    rc = l.dcnn_decode_label_records(
        raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), raw.size, n,
        skip_bytes, label_index, img_bytes,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        raise ValueError("record buffer too small for requested decode")
    return images, labels


def parse_label_csv(path: str, pixels_per_row: int, skip_header: bool = True,
                    scale: float = 1.0 / 255.0):
    """Parse a ``label,pix…`` CSV → (pixels f32 scaled, labels int32), or
    None if the native library is unavailable."""
    l = lib()
    if l is None:
        return None
    with open(path, "rb") as f:
        text = f.read()
    # upper bound on rows: number of newlines + 1
    max_rows = text.count(b"\n") + 1
    pixels = np.empty((max_rows, pixels_per_row), np.float32)
    labels = np.empty((max_rows,), np.int32)
    rows = l.dcnn_parse_label_csv(
        text, len(text), pixels_per_row, 1 if skip_header else 0, scale,
        max_rows,
        pixels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rows < 0:
        # The fast parser only accepts integer pixels (the MNIST CSV format);
        # anything else (float pixels, padded commas) defers to the tolerant
        # numpy fallback in the caller rather than rejecting the file.
        return None
    return pixels[:rows].copy(), labels[:rows].copy()
