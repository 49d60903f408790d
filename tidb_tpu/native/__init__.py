"""Native (C++) runtime components.

Reference parity: the reference's storage/compute engines are native
(TiKV/Rust, TiFlash/C++ — SURVEY §2.2); here the host-side hot paths that
sit outside XLA — bulk row/key encoding and packed-row decoding — are C++
behind a ctypes C ABI, compiled on first use with the toolchain's g++.

Callers keep working on the pure-Python encoders when the library is
unavailable (``lib()`` returns None) — but never silently: a failed build or
load leaves a WARN event (``native.unavailable``) with the compiler's words,
because the Python encoders are a different program at bulk sizes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_mu = threading.Lock()
_lib = None
_tried = False

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src", "rowcodec.cc")
_OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
_FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def _artifact() -> str:
    """The .so path for THIS source + these flags: keyed by content hash, so
    a stale or foreign binary left in ``_build/`` (the directory is copied
    with the tree but never committed) can never load in its place."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_OUT_DIR, f"libtidbtpu_native-{h.hexdigest()[:16]}.so")


def _unavailable(reason: str) -> None:
    from tidb_tpu.utils import eventlog as _ev

    lg = _ev.on(_ev.WARN)
    if lg is not None:
        lg.emit(_ev.WARN, "native", "unavailable", reason=reason)


def _build() -> str | None:
    out = _artifact()
    if os.path.exists(out):
        return out
    os.makedirs(_OUT_DIR, exist_ok=True)
    # per-process tmp name: concurrent builders each publish a complete .so
    # atomically instead of interleaving writes into one shared tmp file
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, _SRC, "-o", tmp], check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.remove(tmp)
        except OSError:
            pass
        stderr = getattr(e, "stderr", None) or b""
        _unavailable(f"g++ build failed: {e!r} {stderr[-400:].decode(errors='replace')}")
        return None
    return out


def lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _tried
    with _mu:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("TIDB_TPU_NO_NATIVE"):
            return None
        path = _build()
        if path is None:
            return None
        try:
            lb = ctypes.CDLL(path)
        except OSError as e:
            _unavailable(f"dlopen failed: {e!r}")
            return None
        lb.tpu_encode_rows_size.restype = ctypes.c_int64
        lb.tpu_encode_rows_size.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_void_p,
        ]
        lb.tpu_encode_rows.restype = None
        lb.tpu_encode_rows.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lb.tpu_decode_fixed.restype = None
        lb.tpu_decode_fixed.argtypes = [
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
        ]
        _lib = lb
        return _lib
