"""ctypes wrapper over the native shared-memory blocking queue.

Reference capability: the C++ LoDTensorBlockingQueue feeding the trainer
from reader threads/processes (paddle/fluid/operators/reader/,
SURVEY.md §2.2 io row). Numpy batches cross the worker→trainer boundary
as one memcpy each way (length-prefixed records with a tiny numpy
header), instead of a pickle round-trip through an mp.Queue.

The .so is built lazily with g++ the first time it's needed, from
``csrc/shm_queue.cpp`` as git holds it, into the checkout's ignored
cache directory (``utils.build_cache``), named by a hash of the source:
an edited source never loads a stale artifact, whatever the mtimes. A
failed build is an error where the queue is asked for, not a silent
change of transport.
"""
from __future__ import annotations

import ctypes
import hashlib
import io as _io
import mmap
import os
import struct
import subprocess
import tempfile
import threading

import numpy as np

__all__ = ["ShmQueue", "native_available"]

_LIB = None
_LIB_ERR = None
_BUILD_LOCK = threading.Lock()


def _build_lib():
    global _LIB, _LIB_ERR
    if _LIB is not None or _LIB_ERR is not None:
        return _LIB
    from paddle_tpu.utils.build_cache import cache_dir

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "csrc", "shm_queue.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    with _BUILD_LOCK:
        if _LIB is not None or _LIB_ERR is not None:
            return _LIB
        cache = cache_dir("native")
        os.makedirs(cache, exist_ok=True)  # tpulint: disable=blocking-under-lock (one-time double-checked build: the lock exists precisely to serialize the slow compile)
        so = os.path.join(cache, f"libshm_queue-{digest}.so")
        try:
            if not os.path.exists(so):
                tmp = so + f".tmp{os.getpid()}"
                subprocess.run(  # tpulint: disable=blocking-under-lock (one-time double-checked build: the lock exists precisely to serialize the slow compile)
                    ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src,
                     "-lpthread"],
                    check=True, capture_output=True)
                os.replace(tmp, so)  # tpulint: disable=blocking-under-lock (one-time double-checked build: the lock exists precisely to serialize the slow compile)
            lib = ctypes.CDLL(so)
            lib.shm_queue_init.restype = ctypes.c_uint64
            lib.shm_queue_init.argtypes = [ctypes.c_void_p,
                                           ctypes.c_uint64]
            lib.shm_queue_push.restype = ctypes.c_int
            lib.shm_queue_push.argtypes = [ctypes.c_void_p,
                                           ctypes.c_void_p,
                                           ctypes.c_uint64]
            lib.shm_queue_next_size.restype = ctypes.c_int64
            lib.shm_queue_next_size.argtypes = [ctypes.c_void_p]
            lib.shm_queue_pop.restype = ctypes.c_int64
            lib.shm_queue_pop.argtypes = [ctypes.c_void_p,
                                          ctypes.c_void_p,
                                          ctypes.c_uint64]
            lib.shm_queue_close.restype = None
            lib.shm_queue_close.argtypes = [ctypes.c_void_p]
            lib.shm_queue_next_size_timed.restype = ctypes.c_int64
            lib.shm_queue_next_size_timed.argtypes = [ctypes.c_void_p,
                                                      ctypes.c_int64]
            _LIB = lib
        except Exception as e:  # no compiler / no pthread etc.
            # remembered so every later ask fails the same way without
            # re-running the compiler; ShmQueue() raises it
            _LIB_ERR = e
            _LIB = None
    return _LIB


def native_available() -> bool:
    return _build_lib() is not None


def _is_ml_dtype(dt) -> bool:
    try:
        import ml_dtypes

        return isinstance(getattr(ml_dtypes, dt.name, None), type)
    except ImportError:
        return False


def _pack_tree(obj) -> bytes:
    """Encode a nested (tuple/list/dict) structure of numpy arrays as a
    header (np.save format per leaf) + raw bytes."""
    buf = _io.BytesIO()
    _pack_into(obj, buf)
    return buf.getvalue()


def _pack_into(obj, buf):
    if isinstance(obj, np.ndarray):
        dt = obj.dtype
        if dt.kind == "V" and dt.names is None and _is_ml_dtype(dt):
            # ml_dtypes extended types (bfloat16, fp8, int4) — np.save
            # cannot represent them (stores raw '|V2' that np.load hands
            # back as void): ship a same-width uint view tagged with the
            # real dtype name and restore the view on load. Genuine
            # void dtypes stay on the plain 'A' path, which round-trips
            # them as-is.
            name = dt.name.encode()
            buf.write(b"X" + struct.pack("<I", len(name)) + name)
            np.save(buf, obj.view(np.dtype(f"uint{dt.itemsize * 8}")),
                    allow_pickle=False)
            return
        buf.write(b"A")
        np.save(buf, obj, allow_pickle=False)
    elif isinstance(obj, tuple):
        buf.write(b"T" + struct.pack("<I", len(obj)))
        for v in obj:
            _pack_into(v, buf)
    elif isinstance(obj, list):
        buf.write(b"L" + struct.pack("<I", len(obj)))
        for v in obj:
            _pack_into(v, buf)
    elif isinstance(obj, dict):
        buf.write(b"D" + struct.pack("<I", len(obj)))
        for k, v in obj.items():
            kb = str(k).encode()
            buf.write(struct.pack("<I", len(kb)) + kb)
            _pack_into(v, buf)
    elif isinstance(obj, str):
        sb = obj.encode()
        buf.write(b"S" + struct.pack("<I", len(sb)) + sb)
    elif obj is None:
        buf.write(b"N")
    elif isinstance(obj, (bool, np.bool_)):
        buf.write(b"B" + (b"\x01" if obj else b"\x00"))
    elif isinstance(obj, (int, np.integer)):
        buf.write(b"I" + struct.pack("<q", int(obj)))
    elif isinstance(obj, (float, np.floating)):
        buf.write(b"F" + struct.pack("<d", float(obj)))
    else:
        raise TypeError(
            f"shm transport supports numpy arrays / scalars / nested "
            f"list-tuple-dict, got {type(obj)}")


def _unpack_from(buf):
    tag = buf.read(1)
    if tag == b"A":
        return np.load(buf, allow_pickle=False)
    if tag == b"X":
        n = struct.unpack("<I", buf.read(4))[0]
        name = buf.read(n).decode()
        import ml_dtypes

        raw = np.load(buf, allow_pickle=False)
        return raw.view(np.dtype(getattr(ml_dtypes, name)))
    if tag in (b"T", b"L"):
        n = struct.unpack("<I", buf.read(4))[0]
        items = [_unpack_from(buf) for _ in range(n)]
        return tuple(items) if tag == b"T" else items
    if tag == b"D":
        n = struct.unpack("<I", buf.read(4))[0]
        out = {}
        for _ in range(n):
            kl = struct.unpack("<I", buf.read(4))[0]
            k = buf.read(kl).decode()
            out[k] = _unpack_from(buf)
        return out
    if tag == b"S":
        n = struct.unpack("<I", buf.read(4))[0]
        return buf.read(n).decode()
    if tag == b"N":
        return None
    if tag == b"B":
        return buf.read(1) == b"\x01"
    if tag == b"I":
        return struct.unpack("<q", buf.read(8))[0]
    if tag == b"F":
        return struct.unpack("<d", buf.read(8))[0]
    raise ValueError(f"corrupt shm record (tag {tag!r})")


class ShmQueue:
    """Process-shared blocking queue over one anonymous mmap segment.

    Create BEFORE forking workers; the children inherit the mapping.
    put()/get() move structured numpy batches; close() wakes blocked
    readers/writers.
    """

    def __init__(self, capacity_bytes: int = 64 << 20):
        lib = _build_lib()
        if lib is None:
            raise RuntimeError(
                f"native shm queue unavailable: {_LIB_ERR}")
        self._lib = lib
        self._mm = mmap.mmap(-1, capacity_bytes)  # anonymous, shared
        self._addr = ctypes.addressof(
            ctypes.c_char.from_buffer(self._mm))
        cap = lib.shm_queue_init(self._addr, capacity_bytes)
        if cap == 0:
            raise RuntimeError("shm_queue_init failed")
        self.capacity = int(cap)

    def put(self, obj) -> None:
        data = _pack_tree(obj)
        rc = self._lib.shm_queue_push(self._addr, data, len(data))
        if rc == -2:
            raise ValueError(
                f"record of {len(data)} bytes exceeds queue capacity "
                f"{self.capacity}; raise capacity_bytes")
        if rc == -1:
            raise RuntimeError("shm queue closed")

    def get(self, timeout: float = None):
        if timeout is None:
            n = self._lib.shm_queue_next_size(self._addr)
        else:
            n = self._lib.shm_queue_next_size_timed(
                self._addr, int(timeout * 1000))
            if n == -3:
                import queue as _q

                raise _q.Empty
        if n < 0:
            raise EOFError("shm queue closed and drained")
        out = ctypes.create_string_buffer(int(n))
        got = self._lib.shm_queue_pop(self._addr, out, int(n))
        if got < 0:
            raise EOFError("shm queue closed and drained")
        return _unpack_from(_io.BytesIO(out.raw[:got]))

    def close(self) -> None:
        self._lib.shm_queue_close(self._addr)
