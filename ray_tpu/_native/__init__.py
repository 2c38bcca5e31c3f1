"""Native (C++) components, compiled on demand.

The reference ships ~166K LoC of C++ under src/ray/ prebuilt by Bazel;
here the native layer is small enough to build lazily with the system
toolchain the first time it is needed, cached next to the source. If no
toolchain is available the callers fall back to pure-Python paths.

The library's file name carries a hash of its sources, so a library
left over from another tree is never what runs: a copy of the checkout
keeps no mtime order one could rely on, a content hash it does.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [os.path.join(_DIR, "plasma_store.cpp"),
            os.path.join(_DIR, "node_store.cpp"),
            os.path.join(_DIR, "gcs_kv.cpp")]

_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # False = tried and failed
_status = "not loaded"


def status() -> str:
    """How the last ``load()`` got the library: ``"built"`` (compiled
    now), ``"reused"`` (a build of exactly these sources was there),
    ``"unavailable"`` (callers are on their pure-Python paths) or
    ``"not loaded"``."""
    return _status


def _lib_path() -> str:
    digest = hashlib.sha256()
    for source in _SOURCES:
        with open(source, "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _DIR, f"libray_tpu_native-{digest.hexdigest()[:16]}.so")


def _build(lib_path: str) -> bool:
    # Built beside the target and renamed into place: daemons starting
    # together may all build, and none may dlopen a half-written file.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", *_SOURCES, "-o", tmp,
           "-lpthread", "-lrt"]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0 or not os.path.exists(tmp):
            return False
        os.replace(tmp, lib_path)
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libray_tpu_native*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except OSError:
                pass  # another process's; it removes its own
    return True


def load() -> "ctypes.CDLL | None":
    """Compile (if there is no build of these sources) and dlopen the
    native library.

    Returns None when the toolchain or build is unavailable; callers
    must degrade gracefully.
    """
    global _lib, _status
    with _lock:
        if _lib is not None:
            return _lib or None
        try:
            lib_path = _lib_path()
            _status = "reused"
            if not os.path.exists(lib_path):
                _status = "built"
                if not _build(lib_path):
                    raise OSError("native build failed")
            lib = ctypes.CDLL(lib_path)
        except OSError:
            _lib = False
            _status = "unavailable"
            return None

        u64, u32, p = ctypes.c_uint64, ctypes.c_uint32, ctypes.c_void_p
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.rt_store_create.restype = p
        lib.rt_store_create.argtypes = [ctypes.c_char_p, u64, u32]
        lib.rt_store_attach.restype = p
        lib.rt_store_attach.argtypes = [ctypes.c_char_p]
        lib.rt_store_detach.restype = None
        lib.rt_store_detach.argtypes = [p]
        lib.rt_store_destroy.restype = ctypes.c_int
        lib.rt_store_destroy.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_base.restype = u8p
        lib.rt_store_base.argtypes = [p]
        lib.rt_store_create_object.restype = u64
        lib.rt_store_create_object.argtypes = [p, ctypes.c_char_p, u64]
        lib.rt_store_seal.restype = ctypes.c_int
        lib.rt_store_seal.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_seal_pinned.restype = ctypes.c_int
        lib.rt_store_seal_pinned.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_get.restype = u64
        lib.rt_store_get.argtypes = [p, ctypes.c_char_p,
                                     ctypes.POINTER(u64)]
        lib.rt_store_peek.restype = u64
        lib.rt_store_peek.argtypes = [p, ctypes.c_char_p,
                                      ctypes.POINTER(u64)]
        lib.rt_store_release.restype = ctypes.c_int
        lib.rt_store_release.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_delete.restype = ctypes.c_int
        lib.rt_store_delete.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_contains.restype = ctypes.c_int
        lib.rt_store_contains.argtypes = [p, ctypes.c_char_p]
        lib.rt_store_stats.restype = None
        lib.rt_store_stats.argtypes = [p] + [ctypes.POINTER(u64)] * 5
        # Node object store (node_store.cpp, rt_ns_*).
        i64 = ctypes.c_int64
        lib.rt_ns_create.restype = p
        lib.rt_ns_create.argtypes = [u64, u64, ctypes.c_char_p]
        lib.rt_ns_destroy.restype = None
        lib.rt_ns_destroy.argtypes = [p]
        lib.rt_ns_put.restype = ctypes.c_int
        lib.rt_ns_put.argtypes = [p, ctypes.c_char_p, ctypes.c_char_p,
                                  u64, ctypes.c_int, ctypes.c_char_p]
        lib.rt_ns_read.restype = i64
        lib.rt_ns_read.argtypes = [p, ctypes.c_char_p, u64, u8p, u64,
                                   ctypes.POINTER(u64)]
        lib.rt_ns_size.restype = i64
        lib.rt_ns_size.argtypes = [p, ctypes.c_char_p]
        lib.rt_ns_free.restype = ctypes.c_int
        lib.rt_ns_free.argtypes = [p, ctypes.c_char_p, u32]
        lib.rt_ns_free_owner.restype = ctypes.c_int
        lib.rt_ns_free_owner.argtypes = [p, ctypes.c_char_p]
        lib.rt_ns_owners.restype = i64
        lib.rt_ns_owners.argtypes = [p, ctypes.c_char_p, u64]
        lib.rt_ns_stats.restype = None
        lib.rt_ns_stats.argtypes = [p, ctypes.POINTER(u64)]
        _lib = lib
        return lib
