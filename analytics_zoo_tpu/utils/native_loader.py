"""ctypes loader for the native data-path library (``native/``).

The reference consumed native code as prebuilt JNI artifacts
(``zoo-core-dist-*``, SURVEY.md §2.9); here ``native/zoo_data.cpp``
compiles on demand with the baked-in g++ and loads over a plain C ABI —
no JVM, no JNI, no packaging step.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Iterator, Optional

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "zoo_data.cpp")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libzoo_data.so")

_loaded: Optional["ZooDataLib"] = None
_load_failed = False


class ZooDataLib:
    """Typed wrapper over libzoo_data.so."""

    def __init__(self, path: str):
        lib = ctypes.CDLL(path)
        lib.zoo_crc32c.restype = ctypes.c_uint32
        lib.zoo_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_uint32]
        lib.zoo_tfrecord_open.restype = ctypes.c_void_p
        lib.zoo_tfrecord_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                          ctypes.c_char_p]
        lib.zoo_tfrecord_count.restype = ctypes.c_uint64
        lib.zoo_tfrecord_count.argtypes = [ctypes.c_void_p]
        lib.zoo_tfrecord_payload.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.zoo_tfrecord_payload.argtypes = [ctypes.c_void_p]
        lib.zoo_tfrecord_offsets.restype = ctypes.POINTER(ctypes.c_uint64)
        lib.zoo_tfrecord_offsets.argtypes = [ctypes.c_void_p]
        lib.zoo_tfrecord_close.argtypes = [ctypes.c_void_p]
        lib.zoo_arena_create.restype = ctypes.c_void_p
        lib.zoo_arena_create.argtypes = [ctypes.c_uint64]
        lib.zoo_arena_alloc.restype = ctypes.c_uint64
        lib.zoo_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.zoo_arena_base.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.zoo_arena_base.argtypes = [ctypes.c_void_p]
        lib.zoo_arena_capacity.restype = ctypes.c_uint64
        lib.zoo_arena_capacity.argtypes = [ctypes.c_void_p]
        lib.zoo_arena_used.restype = ctypes.c_uint64
        lib.zoo_arena_used.argtypes = [ctypes.c_void_p]
        lib.zoo_arena_reset.argtypes = [ctypes.c_void_p]
        lib.zoo_arena_destroy.argtypes = [ctypes.c_void_p]
        self._lib = lib

    # -- crc -------------------------------------------------------------
    def crc32c(self, data: bytes, crc: int = 0) -> int:
        return self._lib.zoo_crc32c(data, len(data), crc)

    # -- tfrecord --------------------------------------------------------
    def read_tfrecord(self, path: str,
                      verify_crc: bool = False) -> Iterator[bytes]:
        err = ctypes.create_string_buffer(256)
        handle = self._lib.zoo_tfrecord_open(
            path.encode(), int(verify_crc), err)
        if not handle:
            raise IOError(err.value.decode() or f"cannot read {path}")
        try:
            n = self._lib.zoo_tfrecord_count(handle)
            payload = self._lib.zoo_tfrecord_payload(handle)
            offsets = self._lib.zoo_tfrecord_offsets(handle)
            for i in range(n):
                start, end = offsets[i], offsets[i + 1]
                yield ctypes.string_at(
                    ctypes.addressof(payload.contents) + start,
                    end - start)
        finally:
            self._lib.zoo_tfrecord_close(handle)

    # -- arena -----------------------------------------------------------
    def arena(self, capacity: int) -> "HostArena":
        return HostArena(self, capacity)


class HostArena:
    """Host-RAM staging arena (the PMEM/DIRECT tier equivalent)."""

    def __init__(self, lib: ZooDataLib, capacity: int):
        self._lib = lib._lib
        self._handle = self._lib.zoo_arena_create(capacity)
        if not self._handle:
            raise MemoryError(f"cannot allocate {capacity}-byte arena")

    @property
    def capacity(self) -> int:
        return self._lib.zoo_arena_capacity(self._handle)

    @property
    def used(self) -> int:
        return self._lib.zoo_arena_used(self._handle)

    def store(self, data) -> "ArenaView":
        """Copy a numpy array / bytes into the arena; returns a view."""
        import numpy as np

        arr = np.ascontiguousarray(data)
        off = self._lib.zoo_arena_alloc(self._handle, arr.nbytes)
        if off == 2 ** 64 - 1:
            raise MemoryError("arena full")
        base = ctypes.addressof(
            self._lib.zoo_arena_base(self._handle).contents)
        ctypes.memmove(base + off, arr.ctypes.data, arr.nbytes)
        return ArenaView(self, off, arr.shape, arr.dtype)

    def view(self, offset: int, shape, dtype):
        import numpy as np

        base = ctypes.addressof(
            self._lib.zoo_arena_base(self._handle).contents)
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        buf = (ctypes.c_uint8 * nbytes).from_address(base + offset)
        return np.frombuffer(buf, dtype=dtype).reshape(shape)

    def reset(self):
        self._lib.zoo_arena_reset(self._handle)

    def close(self):
        if self._handle:
            self._lib.zoo_arena_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ArenaView:
    """A (shape, dtype) window into a HostArena."""

    def __init__(self, arena: HostArena, offset: int, shape, dtype):
        self.arena = arena
        self.offset = offset
        self.shape = tuple(shape)
        self.dtype = dtype

    def numpy(self):
        return self.arena.view(self.offset, self.shape, self.dtype)


def build_native(quiet: bool = True) -> bool:
    """Compile native/ with make; returns success."""
    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            capture_output=quiet, timeout=120)
        return proc.returncode == 0
    except (OSError, subprocess.TimeoutExpired):
        return False


def _stale() -> bool:
    """The library is missing or older than its source. ``native/build``
    is git-ignored, so a library that merely exists may come from another
    commit; what runs must be built from the source in this checkout."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def load_zoo_data(auto_build: bool = True) -> ZooDataLib:
    """Load the native library, (re)building it first when it is missing
    or older than ``native/zoo_data.cpp``.

    Raises ImportError when unavailable so call sites can fall back to
    pure python.
    """
    global _loaded, _load_failed
    if _loaded is not None:
        return _loaded
    if _load_failed:
        raise ImportError("native zoo_data previously failed to load")
    if _stale():
        if not (auto_build and os.path.exists(
                os.path.join(_NATIVE_DIR, "Makefile")) and build_native()):
            _load_failed = True
            raise ImportError(
                "libzoo_data.so missing or older than zoo_data.cpp "
                "(run `make -C native`)")
    try:
        _loaded = ZooDataLib(_LIB_PATH)
    except OSError as e:
        _load_failed = True
        raise ImportError(str(e)) from e
    return _loaded
