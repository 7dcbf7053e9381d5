"""Native (C++) control-plane codec, loaded via ctypes.

Builds `framing.cpp` with g++ on first use into a shared object NAMED BY THE
SOURCE'S CONTENT HASH (`_maggy_native.<sha256 prefix>.so`, git-ignored, next
to the source), so a binary can only ever be loaded by the source it was
built from — a tree copied with a stale or foreign `.so` rebuilds instead of
trusting it. Every entry point has a pure-Python fallback so the framework
works without a toolchain; a failed build says so once. See framing.cpp for
what/why.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import hmac as _py_hmac
import os
import subprocess
import threading
import warnings

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "framing.cpp")

_lib = None
_lock = threading.Lock()
_build_attempted = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, "_maggy_native.{}.so".format(digest))


def _build(so: str) -> bool:
    # Compile to a per-pid temp path then rename: os.rename is atomic, so
    # concurrent runner processes never dlopen a partially written .so.
    tmp = os.path.join(_HERE, ".build.{}.{}".format(
        os.getpid(), os.path.basename(so)))
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        warnings.warn(
            "native codec not built ({!r}); using the pure-Python codec"
            .format(getattr(e, "stderr", None) or e), stacklevel=3)
        return False
    # Binaries of other source versions (and the pre-hash name) are dead.
    for stale in glob.glob(os.path.join(_HERE, "_maggy_native*.so")):
        if stale != so:
            try:
                os.unlink(stale)
            except OSError:
                pass
    return True


def get_lib():
    """The loaded native library, or None (fallback mode)."""
    global _lib, _build_attempted
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            if _build_attempted:
                return None
            _build_attempted = True
            if not _build(so):
                return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.maggy_hmac_sha256.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_char_p]
        lib.maggy_hmac_sha256.restype = None
        lib.maggy_digest_eq.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
        lib.maggy_digest_eq.restype = ctypes.c_int
        lib.maggy_frame_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_size_t]
        lib.maggy_frame_scan.restype = ctypes.c_long
        lib.maggy_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.maggy_crc32c.restype = ctypes.c_uint32
        lib.maggy_tfrecord_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_long, ctypes.c_int]
        lib.maggy_tfrecord_scan.restype = ctypes.c_long
        _lib = lib
        return _lib


def hmac_sha256(key: bytes, msg: bytes) -> bytes:
    lib = get_lib()
    if lib is None:
        return _py_hmac.new(key, msg, hashlib.sha256).digest()
    out = ctypes.create_string_buffer(32)
    lib.maggy_hmac_sha256(key, len(key), msg, len(msg), out)
    return out.raw


def frame_scan(buf, key: bytes, max_frame: int) -> int:
    """Scan one frame: >0 total size consumed (valid), 0 incomplete,
    -1 oversized, -2 bad HMAC. Pure-Python fallback mirrors framing.cpp."""
    lib = get_lib()
    if lib is not None:
        if isinstance(buf, bytearray):
            # Zero-copy view into the connection's reassembly buffer — this
            # runs once per frame on the server's single event-loop thread.
            cbuf = (ctypes.c_char * len(buf)).from_buffer(buf)
            return int(lib.maggy_frame_scan(cbuf, len(buf), key, len(key),
                                            max_frame))
        return int(lib.maggy_frame_scan(bytes(buf), len(buf), key, len(key),
                                        max_frame))
    header = 4 + 32
    if len(buf) < header:
        return 0
    length = int.from_bytes(buf[:4], "big")
    if length > max_frame:
        return -1
    if len(buf) < header + length:
        return 0
    mac = _py_hmac.new(key, bytes(buf[header:header + length]),
                       hashlib.sha256).digest()
    if not _py_hmac.compare_digest(mac, bytes(buf[4:header])):
        return -2
    return header + length


def crc32c(data: bytes):
    """Native crc32c (Castagnoli), or None when in fallback mode — the
    caller (maggy_tpu.train.tfrecord) owns the pure-Python table."""
    lib = get_lib()
    if lib is None:
        return None
    return int(lib.maggy_crc32c(data, len(data)))


def tfrecord_scan(data: bytes, verify: bool = True):
    """Offsets/lengths of every record payload in a TFRecord buffer, crc
    verified natively. Returns a list of (offset, length), or None in
    fallback mode. Raises ValueError on truncation/corruption."""
    lib = get_lib()
    if lib is None:
        return None
    # One entry per 16 bytes is a safe upper bound (min record = 16 bytes).
    cap = max(1, len(data) // 16)
    offs = (ctypes.c_int64 * cap)()
    lens = (ctypes.c_int64 * cap)()
    n = int(lib.maggy_tfrecord_scan(data, len(data), offs, lens, cap,
                                    1 if verify else 0))
    if n == -1:
        raise ValueError("Truncated TFRecord buffer")
    if n == -2:
        raise ValueError("Corrupt TFRecord crc")
    if n < 0:
        raise ValueError("TFRecord scan failed ({})".format(n))
    return [(offs[i], lens[i]) for i in range(n)]


def is_native() -> bool:
    return get_lib() is not None
