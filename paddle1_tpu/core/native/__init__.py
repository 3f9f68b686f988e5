"""Native (C++) host runtime: blocking prefetch queue, shared-memory arena,
stats registry. See src/native.cc for the component map to the reference.

The library is never committed: it builds from src/native.cc on first use
(g++, ~1s) into a file named by the source's hash, so a copied tree can
neither ship nor pick up a binary of another source. Every consumer has a
pure-Python fallback; a failed build warns with the compiler's reason and
the framework runs on the fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

__all__ = ["available", "origin", "BoundedQueue", "ShmArena", "stat_add",
           "stat_set", "stat_get", "stat_dump"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "native.cc")
_CAPI_SRC = os.path.join(_HERE, "src", "capi.cc")
_CAPI_LIB = os.path.join(_HERE, "libpaddle1_capi.so")
_lib = None
_origin = "unavailable"  # "built" / "loaded" once _load() succeeds
_build_lock = threading.Lock()


def build_capi():
    """Build the C inference ABI (src/capi.cc → libpaddle1_capi.so):
    embedded-interpreter predictor for C/Go deployments (the reference's
    inference/capi analog). Returns the .so path or None."""
    import sysconfig
    with _build_lock:
        if os.path.exists(_CAPI_LIB) and (
                not os.path.exists(_CAPI_SRC) or
                os.path.getmtime(_CAPI_LIB) >= os.path.getmtime(_CAPI_SRC)):
            return _CAPI_LIB  # prebuilt .so shipped without src/
        if not os.path.exists(_CAPI_SRC):
            return None
        inc = sysconfig.get_paths()["include"]
        libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
        pyver = f"python{sysconfig.get_config_var('py_version_short')}"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
               _CAPI_SRC, "-o", _CAPI_LIB, f"-I{inc}", f"-L{libdir}",
               f"-l{pyver}", "-ldl", "-lm"]
        try:
            subprocess.run(cmd, check=True,  # noqa: lock-blocking — serializes the one-shot build
                           capture_output=True,
                           timeout=180)
            return _CAPI_LIB
        except Exception:
            return None


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_HERE, f"libpaddle1_native.{digest}.so")


def _build(lib_path: str) -> bool:
    # several processes (test workers) may build at once: each compiles
    # to a name of its own and renames into place, so none loads a file
    # another is still writing
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp, "-lrt"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        why = getattr(e, "stderr", None) or b""
        warnings.warn(
            f"native host runtime not built ({e!r} "
            f"{why.decode(errors='replace').strip()[-300:]}); using the "
            "pure-Python fallbacks")
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    global _lib, _origin
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        lib_path = _lib_path()
        built = not os.path.exists(lib_path)
        if built and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError:
            return None
        # signatures
        lib.pq_create.restype = ctypes.c_void_p
        lib.pq_create.argtypes = [ctypes.c_size_t]
        lib.pq_destroy.argtypes = [ctypes.c_void_p]
        lib.pq_put.restype = ctypes.c_int
        lib.pq_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                               ctypes.c_size_t, ctypes.c_int64]
        lib.pq_get.restype = ctypes.c_void_p
        lib.pq_get.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pq_size.restype = ctypes.c_size_t
        lib.pq_size.argtypes = [ctypes.c_void_p]
        lib.pq_close.argtypes = [ctypes.c_void_p]
        lib.buf_data.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.buf_data.argtypes = [ctypes.c_void_p]
        lib.buf_len.restype = ctypes.c_size_t
        lib.buf_len.argtypes = [ctypes.c_void_p]
        lib.buf_free.argtypes = [ctypes.c_void_p]
        lib.shm_arena_create.restype = ctypes.c_void_p
        lib.shm_arena_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.shm_arena_attach.restype = ctypes.c_void_p
        lib.shm_arena_attach.argtypes = [ctypes.c_char_p]
        lib.shm_arena_detach.argtypes = [ctypes.c_void_p]
        lib.shm_arena_unlink.argtypes = [ctypes.c_char_p]
        lib.shm_alloc.restype = ctypes.c_uint64
        lib.shm_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shm_ptr.restype = ctypes.c_void_p
        lib.shm_ptr.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shm_incref.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shm_decref.restype = ctypes.c_int64
        lib.shm_decref.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shm_arena_reset.argtypes = [ctypes.c_void_p]
        lib.shm_arena_used.restype = ctypes.c_uint64
        lib.shm_arena_used.argtypes = [ctypes.c_void_p]
        lib.shm_arena_size.restype = ctypes.c_uint64
        lib.shm_arena_size.argtypes = [ctypes.c_void_p]
        lib.stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.stat_set.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.stat_get.restype = ctypes.c_int64
        lib.stat_get.argtypes = [ctypes.c_char_p]
        lib.stat_dump.restype = ctypes.c_int64
        lib.stat_dump.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.c_int64]
        _origin = "built" if built else "loaded"
        _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def origin() -> str:
    """How this process got the library: ``"built"`` from source just
    now, ``"loaded"`` from an earlier build of the same source, or
    ``"unavailable"`` (no compiler — the pure-Python fallbacks run)."""
    _load()
    return _origin


# ---------------------------------------------------------------------------
# BoundedQueue — GIL-free blocking queue of pickled payloads.
# ---------------------------------------------------------------------------


class BoundedQueue:
    """Blocking byte-payload queue backed by the C++ MPMC queue; falls back
    to queue.Queue when the native lib is unavailable."""

    def __init__(self, capacity: int = 8):
        lib = _load()
        self._lib = lib
        if lib is not None:
            self._h = lib.pq_create(capacity)
            self._q = None
        else:
            import queue
            self._h = None
            self._q = queue.Queue(maxsize=capacity)

    def put(self, payload: bytes, timeout_ms: int = -1) -> bool:
        if self._lib is not None:
            rc = self._lib.pq_put(self._h, payload, len(payload), timeout_ms)
            if rc == -1:
                raise RuntimeError("queue closed")
            return rc == 0
        self._q.put(payload,
                    timeout=None if timeout_ms < 0 else timeout_ms / 1e3)
        return True

    def get(self, timeout_ms: int = -1):
        if self._lib is not None:
            h = self._lib.pq_get(self._h, timeout_ms)
            if not h:
                return None
            try:
                n = self._lib.buf_len(h)
                data = ctypes.string_at(self._lib.buf_data(h), n)
            finally:
                self._lib.buf_free(h)
            return data
        try:
            return self._q.get(
                timeout=None if timeout_ms < 0 else timeout_ms / 1e3)
        except Exception:
            return None

    def qsize(self) -> int:
        if self._lib is not None:
            return int(self._lib.pq_size(self._h))
        return self._q.qsize()

    def close(self):
        if self._lib is not None and self._h:
            self._lib.pq_close(self._h)

    def __del__(self):
        try:
            if self._lib is not None and self._h:
                self._lib.pq_close(self._h)
                self._lib.pq_destroy(self._h)
                self._h = None
        except Exception:
            pass


# ---------------------------------------------------------------------------
# ShmArena — zero-copy multiprocess tensor transfer.
# ---------------------------------------------------------------------------


class ShmArena:
    """Named shared-memory arena; numpy arrays move between processes as
    (offset, shape, dtype) descriptors (reference mmap_allocator.cc)."""

    def __init__(self, name: str, size: int = 1 << 28, create: bool = True):
        import numpy as np
        self._np = np
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.name = name.encode() if isinstance(name, str) else name
        if create:
            self._base = lib.shm_arena_create(self.name, size)
        else:
            self._base = lib.shm_arena_attach(self.name)
        if not self._base:
            raise RuntimeError(f"shm arena {name!r} mmap failed")
        # the creator's header is authoritative (attachers must not trust
        # their local default)
        self.size = int(lib.shm_arena_size(self._base))

    def put_array(self, arr) -> tuple:
        np = self._np
        arr = np.ascontiguousarray(arr)
        off = self._lib.shm_alloc(self._base, arr.nbytes)
        if off == 0:
            raise MemoryError("shm arena full")
        ctypes.memmove(self._lib.shm_ptr(self._base, off),
                       arr.ctypes.data, arr.nbytes)
        return (off, arr.shape, arr.dtype.str)

    def get_array(self, desc):
        np = self._np
        off, shape, dtype = desc
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        ptr = self._lib.shm_ptr(self._base, off)
        buf = (ctypes.c_uint8 * n).from_address(ptr)
        return np.frombuffer(buf, dtype=dtype).reshape(shape).copy()

    def incref(self, desc):
        """Extra reader share of a block (multi-consumer broadcast)."""
        self._lib.shm_incref(self._base, desc[0])

    def decref(self, desc):
        self._lib.shm_decref(self._base, desc[0])

    def reset(self):
        self._lib.shm_arena_reset(self._base)

    def used(self) -> int:
        return int(self._lib.shm_arena_used(self._base))

    def close(self, unlink: bool = False):
        if self._base:
            self._lib.shm_arena_detach(self._base)
            self._base = None
        if unlink:
            self._lib.shm_arena_unlink(self.name)


# ---------------------------------------------------------------------------
# Stats (monitor.h gauges)
# ---------------------------------------------------------------------------

_py_stats = {}
_py_stats_lock = threading.Lock()


def stat_add(name: str, v: int):
    lib = _load()
    if lib is not None:
        lib.stat_add(name.encode(), int(v))
    else:
        with _py_stats_lock:
            _py_stats[name] = _py_stats.get(name, 0) + int(v)


def stat_set(name: str, v: int):
    lib = _load()
    if lib is not None:
        lib.stat_set(name.encode(), int(v))
    else:
        with _py_stats_lock:
            _py_stats[name] = int(v)


def stat_get(name: str) -> int:
    lib = _load()
    if lib is not None:
        return int(lib.stat_get(name.encode()))
    with _py_stats_lock:
        return _py_stats.get(name, 0)


def stat_dump() -> dict:
    lib = _load()
    if lib is None:
        with _py_stats_lock:
            return dict(_py_stats)
    cap = 1 << 16
    names = ctypes.create_string_buffer(cap)
    vals = (ctypes.c_int64 * 1024)()
    n = lib.stat_dump(names, cap, vals, 1024)
    keys = names.value.decode().split("\n")[:n]
    return dict(zip(keys, vals[:n]))
