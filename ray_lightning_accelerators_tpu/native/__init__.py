"""Native runtime components (C++), surfaced over ctypes.

The reference's native machinery all lived in its dependencies — Ray's C++
core for object movement, torch's C++ DataLoader workers for input
(SURVEY.md §2.3).  This package is the in-repo, TPU-native equivalent:

- ``data_engine.cc`` — threaded gather/prefetch batcher (the input pipeline
  is the TPU bottleneck for small models, SURVEY.md §7.4).  Sampling stays
  in Python (ShardedSampler provides the index order), so batches are
  bit-identical to the pure-Python path; the engine parallelizes the gather.

The shared library is built on demand with ``g++`` (baked into the image)
and cached beside the sources under a name keyed on a hash of them; import
degrades gracefully when no toolchain is present (`available()` returns
False and callers fall back to Python).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from ..data.loader import ShardedSampler

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_BUILD_ERROR: Optional[str] = None


def _sources():
    return sorted(f for f in os.listdir(_DIR) if f.endswith(".cc"))


def _source_digest() -> str:
    """Hash of the ``.cc`` sources (names + contents): the cached
    library's identity.  A ``.so`` copied along with a tree (it is
    git-ignored, so nothing says where it came from) is only ever loaded
    when it was built from exactly these sources."""
    h = hashlib.sha256()
    for name in _sources():
        h.update(name.encode() + b"\0")
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()[:16]


def _out_path() -> str:
    name = f"_rla_native.{_source_digest()}.so"
    if os.access(_DIR, os.W_OK):
        return os.path.join(_DIR, name)
    return os.path.join(tempfile.gettempdir(),  # read-only install
                        f"{os.getuid()}{name}")


def _compile(out: str) -> None:
    srcs = [os.path.join(_DIR, f) for f in _sources()]
    tmp = f"{out}.tmp.{os.getpid()}"  # unique per process: concurrent-safe
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", tmp] + srcs
    if sys.platform.startswith("linux"):
        # shm_open/shm_unlink live in librt until glibc 2.34 (a no-op
        # stub after); without this the .so loads but shm symbols are
        # unresolved and the object store reports itself unavailable
        cmd.append("-lrt")
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed: {proc.stderr[-2000:]}")
    os.replace(tmp, out)  # atomic: last concurrent builder wins, all valid


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _BUILD_ERROR
    with _LOCK:
        if _LIB is not None or _BUILD_ERROR is not None:
            return _LIB
        try:
            out = _out_path()
            built = not os.path.exists(out)
            if built:
                _compile(out)
            try:
                lib = ctypes.CDLL(out)
            except OSError:
                if built:
                    raise
                _compile(out)  # cached .so unloadable (wrong arch): rebuild
                lib = ctypes.CDLL(out)
        except (OSError, RuntimeError) as e:
            _BUILD_ERROR = str(e)
            return None
        lib.rla_engine_create.restype = ctypes.c_void_p
        lib.rla_engine_create.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
            ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.rla_engine_start_epoch.restype = ctypes.c_int
        lib.rla_engine_start_epoch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long), ctypes.c_long]
        lib.rla_engine_next_batch.restype = ctypes.c_long
        lib.rla_engine_next_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)]
        lib.rla_engine_num_batches.restype = ctypes.c_long
        lib.rla_engine_num_batches.argtypes = [ctypes.c_void_p]
        lib.rla_engine_destroy.argtypes = [ctypes.c_void_p]
        lib.rla_shm_create.restype = ctypes.c_void_p
        lib.rla_shm_create.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib.rla_shm_open_ro.restype = ctypes.c_void_p
        lib.rla_shm_open_ro.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_long)]
        lib.rla_shm_unmap.restype = ctypes.c_int
        lib.rla_shm_unmap.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.rla_shm_unlink.restype = ctypes.c_int
        lib.rla_shm_unlink.argtypes = [ctypes.c_char_p]
        lib.rla_shm_errno.restype = ctypes.c_int
        lib.rla_shm_errno.argtypes = []
        _LIB = lib
        return _LIB


def lib() -> ctypes.CDLL:
    """The loaded native library; raises when unavailable."""
    loaded = _load()
    if loaded is None:
        raise RuntimeError(f"native library unavailable: {_BUILD_ERROR}")
    return loaded


def available() -> bool:
    """True when the native library is importable (builds it if needed)."""
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _BUILD_ERROR


def engine_compatible_arrays(arrays) -> bool:
    """Only flat-memory numeric/bool rows may be memcpy'd; object arrays
    hold PyObject* that must be refcounted."""
    return bool(arrays) and all(
        isinstance(a, np.ndarray) and not a.dtype.hasobject for a in arrays)


class DataEngine:
    """ctypes handle on the C++ batcher; yields tuples of numpy batches.

    Index order comes from a ShardedSampler (or any explicit index array via
    ``iter_indices``), so batches are bit-identical to the pure-Python
    DataLoader path — shuffling, rank slicing, and pad-by-wrap included.
    Single-consumer: iterate from one thread at a time.
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 shuffle: bool = True, drop_last: bool = True, seed: int = 0,
                 num_replicas: int = 1, rank: int = 0,
                 num_threads: Optional[int] = None, prefetch: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_BUILD_ERROR}")
        if not engine_compatible_arrays(arrays):
            raise TypeError("DataEngine needs numeric numpy arrays "
                            "(object dtypes cannot be memcpy'd)")
        self._lib = lib
        # keep contiguous copies alive for the engine's borrowed pointers
        self.arrays = [np.ascontiguousarray(a) for a in arrays]
        assert self.arrays and all(
            len(a) == len(self.arrays[0]) for a in self.arrays)
        self.batch_size = int(batch_size)
        self.drop_last = drop_last
        self.sampler = ShardedSampler(
            len(self.arrays[0]), num_replicas, rank, shuffle=shuffle,
            drop_last=drop_last, seed=seed)
        n = len(self.arrays)
        ptrs = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self.arrays])
        row_bytes = (ctypes.c_long * n)(
            *[int(np.prod(a.shape[1:], dtype=np.int64)) * a.itemsize
              for a in self.arrays])
        if num_threads is None:
            num_threads = min(8, max(2, (os.cpu_count() or 4) // 2))
        self._handle = lib.rla_engine_create(
            n, ptrs, row_bytes, len(self.arrays[0]), self.batch_size,
            int(drop_last), int(num_threads), int(prefetch))

    def iter_indices(self, indices: np.ndarray) \
            -> Iterator[Tuple[np.ndarray, ...]]:
        """Yield collated batches over an explicit row-index order."""
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        rc = self._lib.rla_engine_start_epoch(
            self._handle, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            len(idx))
        if rc != 0:
            raise IndexError("sampler produced out-of-range row index")
        while True:
            # fresh allocation per batch: callers may hold references across
            # iterations (same semantics as the Python collate path); the
            # expensive gather already happened in the engine threads
            out = [np.empty((self.batch_size,) + a.shape[1:], dtype=a.dtype)
                   for a in self.arrays]
            ptrs = (ctypes.c_void_p * len(out))(
                *[a.ctypes.data_as(ctypes.c_void_p).value for a in out])
            rows = self._lib.rla_engine_next_batch(self._handle, ptrs)
            if rows == 0:
                return
            batch = tuple(a if rows == self.batch_size else a[:rows]
                          for a in out)
            yield batch if len(batch) > 1 else batch[0]

    def epoch(self, epoch: int = 0) -> Iterator[Tuple[np.ndarray, ...]]:
        """Yield one epoch of batches under the built-in sampler."""
        self.sampler.set_epoch(epoch)
        yield from self.iter_indices(np.fromiter(self.sampler, np.int64))

    def num_batches(self) -> int:
        return int(self._lib.rla_engine_num_batches(self._handle))

    def close(self) -> None:
        h, self._handle = self._handle, None
        if h:
            self._lib.rla_engine_destroy(h)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
