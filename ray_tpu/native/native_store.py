"""ctypes binding for the native C++ arena allocator (object_store.cc).

Compiled on demand with g++ (no pybind11 in the image — the C ABI + ctypes
route per the build constraints); the .so is cached next to the source,
keyed by the source's hash (``native.build``). `NativeArena` matches the `_PyArena`
interface (allocate/free/allocated_bytes) so `PlasmaStore` can swap it in
transparently (ray_tpu/_private/object_store.py:_make_arena).
"""

from __future__ import annotations

import ctypes

from ray_tpu import native

_lib = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(native.build("libraytpu_store", ".so", ["object_store.cc"]))
    lib.arena_create.argtypes = [ctypes.c_uint64]
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_allocate.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.arena_allocate.restype = ctypes.c_int64
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.arena_free.restype = ctypes.c_int64
    lib.arena_allocated_bytes.argtypes = [ctypes.c_void_p]
    lib.arena_allocated_bytes.restype = ctypes.c_uint64
    lib.arena_num_blocks.argtypes = [ctypes.c_void_p]
    lib.arena_num_blocks.restype = ctypes.c_uint64
    lib.arena_largest_free.argtypes = [ctypes.c_void_p]
    lib.arena_largest_free.restype = ctypes.c_uint64
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_destroy.restype = None
    _lib = lib
    return lib


class NativeArena:
    """Best-fit C++ offset allocator with O(log n) ops and coalescing
    (the reference's dlmalloc-over-shm analogue — plasma_allocator.cc)."""

    def __init__(self, capacity: int):
        self._lib = _load()
        self.capacity = capacity
        self._h = self._lib.arena_create(capacity)
        if not self._h:
            raise MemoryError("arena_create failed")

    def allocate(self, size: int) -> int:
        return int(self._lib.arena_allocate(self._h, max(1, size)))

    def free(self, offset: int):
        self._lib.arena_free(self._h, offset)

    def allocated_bytes(self) -> int:
        return int(self._lib.arena_allocated_bytes(self._h))

    def num_blocks(self) -> int:
        return int(self._lib.arena_num_blocks(self._h))

    def largest_free(self) -> int:
        return int(self._lib.arena_largest_free(self._h))

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.arena_destroy(h)
            except Exception:
                pass
