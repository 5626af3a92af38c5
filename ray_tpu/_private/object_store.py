"""Object plane: in-process memory store + plasma-style shared-memory store.

Mirrors the reference's two-tier object plane (reference:
src/ray/core_worker/store_provider/memory_store/, src/ray/object_manager/plasma/):
small/inline objects live in the owner's in-process memory store; large objects
live in a node-wide shared-memory arena, written and read zero-copy by every
worker process on the node via mmap. Allocation/seal metadata is coordinated by
the raylet's store service; the data plane never crosses a socket.

The arena allocator is native C++ when built (ray_tpu/native/object_store.cc),
with a Python first-fit fallback so the runtime works before compilation.
"""

from __future__ import annotations

import mmap
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from ray_tpu._private import internal_metrics
from ray_tpu._private import serialization
from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ObjectID


class ObjectStoreFullError(Exception):
    pass


_MADV_POPULATE_WRITE = 23  # linux 5.14+; not yet in the mmap module


def _populate_range(m: mmap.mmap, offset: int, size: int):
    """Kernel-side PTE population for [offset, offset+size): one syscall,
    then writes into the range run at memcpy speed instead of taking a
    minor fault per 4K page. Called just-in-time for large puts so idle
    mappings (short-lived workers) never pay a full-arena pass."""
    if not GlobalConfig.object_store_prealloc:
        return
    page = mmap.PAGESIZE
    start = (offset // page) * page
    length = offset + size - start
    try:
        m.madvise(_MADV_POPULATE_WRITE, start, length)
    except (ValueError, OSError, AttributeError):
        pass  # older kernel: first-touch minor faults still apply


class ObjectLostError(Exception):
    pass


# ---------------------------------------------------------------------------
# Same-process store registry: when a worker (usually the driver on the head
# node) lives in the SAME process as its raylet, store metadata ops dispatch
# as plain method calls instead of RPC round-trips. The reference pays a UDS
# round-trip per plasma create/seal even co-located (plasma/client.cc); here
# co-location is the common head-node case and a small put drops from ~300us
# (TCP round-trip through the shared poller) to ~10us.
# ---------------------------------------------------------------------------

_LOCAL_STORES: Dict[Tuple[str, int], "PlasmaStore"] = {}
_LOCAL_STORES_LOCK = threading.Lock()
_LOCAL_STORES_PID = os.getpid()


def register_local_store(address: Tuple[str, int], store: "PlasmaStore") -> None:
    with _LOCAL_STORES_LOCK:
        _LOCAL_STORES[tuple(address)] = store


def unregister_local_store(address: Tuple[str, int]) -> None:
    with _LOCAL_STORES_LOCK:
        _LOCAL_STORES.pop(tuple(address), None)


def local_store_for(address: Tuple[str, int]) -> Optional["PlasmaStore"]:
    """The PlasmaStore served at ``address``, iff it lives in THIS process.
    Guarded by pid so a fork never inherits a parent's registry entries
    (the child would call into closed mmaps)."""
    if os.getpid() != _LOCAL_STORES_PID:
        return None
    with _LOCAL_STORES_LOCK:
        return _LOCAL_STORES.get(tuple(address))


def _local_store_call(store: "PlasmaStore", method: str, payload=None):
    """In-process mirror of the raylet's store_* RPC handlers
    (raylet.py rpc_store_*): same methods, same payload shapes, no wire."""
    if method == "store_put":
        object_id, data = payload
        store.put_bytes(object_id, data)
        return True
    if method == "store_get":
        object_ids, timeout = payload
        return store.get_locations(object_ids, timeout)
    if method == "store_create":
        object_id, size = payload
        return store.create(object_id, size)
    if method == "store_seal":
        store.seal(payload)
        return True
    if method == "store_contains":
        return store.contains(payload)
    if method == "store_release":
        store.release(payload)
        return True
    if method == "store_delete":
        store.delete(payload)
        return True
    if method == "store_delete_batch":
        for oid in payload:
            store.delete(oid)
        return True
    if method == "store_abort":
        store.abort(payload)
        return True
    if method == "store_stats":
        return store.stats()
    if method == "store_list":
        return store.list_objects()
    raise KeyError(f"no local store dispatch for {method!r}")


# ---------------------------------------------------------------------------
# In-process memory store (inline results, small puts)
# ---------------------------------------------------------------------------


class MemoryStore:
    """Per-process store for inline objects; supports blocking gets."""

    def __init__(self):
        self._objects: Dict[ObjectID, bytes] = {}
        self._cv = threading.Condition()
        self._version = 0  # bumped on every put: lets wait() block on change
        # oid -> callbacks fired (on the putting thread; must be quick) the
        # moment a value lands — the async serve ingress awaits completions
        # this way instead of parking a thread per in-flight request
        self._waiters: Dict[ObjectID, List] = {}

    def put(self, object_id: ObjectID, data: bytes):
        # re-wrap: over the co-located fast path the caller's instance would
        # otherwise be retained as the dict key, pinning the worker-side
        # weakref finalizer forever and defeating reference gc
        object_id = ObjectID(object_id.binary())
        with self._cv:
            self._objects[object_id] = data
            self._version += 1
            self._cv.notify_all()
            callbacks = self._waiters.pop(object_id, None)
        if callbacks:
            for cb in callbacks:
                try:
                    cb()
                except Exception:
                    pass

    def add_waiter(self, object_id: ObjectID, callback) -> None:
        """Invoke ``callback()`` once a value for object_id lands (or
        immediately if it already has). The callback runs on the putting
        thread: schedule real work elsewhere (e.g. call_soon_threadsafe)."""
        with self._cv:
            if object_id not in self._objects:
                self._waiters.setdefault(object_id, []).append(callback)
                return
        callback()

    def remove_waiter(self, object_id: ObjectID, callback) -> None:
        """Drop a registered waiter (e.g. the awaiting side timed out)."""
        with self._cv:
            cbs = self._waiters.get(object_id)
            if not cbs:
                return
            try:
                cbs.remove(callback)
            except ValueError:
                pass
            if not cbs:
                del self._waiters[object_id]

    @property
    def version(self) -> int:
        with self._cv:
            return self._version

    def wait_change(self, version: int, timeout: float) -> int:
        """Block until a put lands after ``version`` (or timeout); returns
        the current version. Task completions (inline results and plasma
        markers) all arrive via put, so callers can sleep instead of
        polling (replaces the 2 ms spin the round-1 review flagged)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._version == version:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            return self._version

    def contains(self, object_id: ObjectID) -> bool:
        with self._cv:
            return object_id in self._objects

    def get(self, object_id: ObjectID, timeout: Optional[float] = None) -> Optional[bytes]:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while object_id not in self._objects:
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                self._cv.wait(remaining if remaining is not None else 1.0)
            return self._objects[object_id]

    def delete(self, object_id: ObjectID):
        with self._cv:
            self._objects.pop(object_id, None)


# ---------------------------------------------------------------------------
# Arena allocators
# ---------------------------------------------------------------------------


class _PyArena:
    """First-fit free-list allocator (fallback when native lib not built)."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        # sorted list of (offset, size) free ranges
        self._free: List[Tuple[int, int]] = [(0, capacity)]
        self._allocated: Dict[int, int] = {}

    def allocate(self, size: int) -> int:
        size = max(64, (size + 63) & ~63)
        for i, (off, sz) in enumerate(self._free):
            if sz >= size:
                if sz == size:
                    self._free.pop(i)
                else:
                    self._free[i] = (off + size, sz - size)
                self._allocated[off] = size
                return off
        return -1

    def free(self, offset: int):
        size = self._allocated.pop(offset, None)
        if size is None:
            return
        self._free.append((offset, size))
        self._free.sort()
        merged: List[Tuple[int, int]] = []
        for off, sz in self._free:
            if merged and merged[-1][0] + merged[-1][1] == off:
                merged[-1] = (merged[-1][0], merged[-1][1] + sz)
            else:
                merged.append((off, sz))
        self._free = merged

    def allocated_bytes(self) -> int:
        return sum(self._allocated.values())


def _make_arena(capacity: int):
    if GlobalConfig.object_store_native:
        try:
            from ray_tpu.native import native_store

            return native_store.NativeArena(capacity)
        except Exception:
            pass
    return _PyArena(capacity)


# ---------------------------------------------------------------------------
# Plasma-style node store (server side; embedded in the raylet)
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = (
        "offset", "size", "sealed", "pin_count", "last_used",
        "creating_worker", "spill_path", "spill_data", "delete_pending",
    )

    def __init__(self, offset: int, size: int, creating_worker=None):
        self.offset = offset
        self.size = size
        self.sealed = False
        self.pin_count = 0
        self.delete_pending = False
        self.last_used = time.monotonic()
        self.creating_worker = creating_worker
        # spilled state: bytes held in memory until the background flusher
        # persists them (spill_data), then a file path (spill_path)
        self.spill_path: Optional[str] = None
        self.spill_data: Optional[bytes] = None

    @property
    def resident(self) -> bool:
        return self.offset >= 0


class PlasmaStore:
    """Node-wide shm object store, metadata side. Lives in the raylet process.

    Data plane: a single file in /dev/shm mapped by every process on the node.
    This class owns allocation, seal notification, pinning, and LRU eviction
    (reference: src/ray/object_manager/plasma/object_lifecycle_manager.cc,
    eviction_policy.cc).

    ``chaos_identity`` (set by the owning raylet) attributes this store to
    its logical node for slow_store_reads fault rules — in-process test
    clusters host several stores per process.
    """

    def __init__(self, session_dir: str, capacity: Optional[int] = None, name: str = "store"):
        self.chaos_identity = None
        self.capacity = capacity or GlobalConfig.object_store_memory_bytes
        shm_dir = "/dev/shm" if os.path.isdir("/dev/shm") else session_dir
        self.path = os.path.join(
            shm_dir, f"raytpu_{os.path.basename(session_dir)}_{name}_{os.getpid()}"
        )
        self._fd = os.open(self.path, os.O_CREAT | os.O_RDWR | os.O_EXCL, 0o600)
        os.ftruncate(self._fd, self.capacity)
        if GlobalConfig.object_store_prealloc:
            # allocate tmpfs pages up front (~0.1s/GiB): first-touch writes
            # then take minor faults (~1.5 GiB/s) instead of allocate+zero
            # faults (~0.3 GiB/s). Bounded to half the free shm space so
            # multi-raylet in-process clusters (tests/bench run 4+ stores on
            # one host) don't commit N×capacity of RAM while idle — the
            # remainder stays allocate-on-use (ADVICE r3).
            prealloc = self.capacity
            try:
                st = os.statvfs(shm_dir)
                prealloc = min(prealloc, (st.f_bavail * st.f_frsize) // 2)
            except OSError:
                pass
            if prealloc > 0:
                try:
                    os.posix_fallocate(self._fd, 0, prealloc)
                except OSError:
                    pass
            self._prefault_bytes = prealloc
        self._map = mmap.mmap(self._fd, self.capacity)
        self._view = memoryview(self._map)
        self._arena = _make_arena(self.capacity)
        self._entries: Dict[ObjectID, _Entry] = {}
        self._cv = threading.Condition()
        # disk spilling (reference: raylet/local_object_manager.h +
        # python/ray/_private/external_storage.py:246 FileSystemStorage):
        # under memory pressure, unpinned sealed objects move to files and
        # restore transparently on the next get.
        self._spill_enabled = GlobalConfig.object_spilling_enabled
        self._spill_dir = GlobalConfig.object_spilling_dir or os.path.join(
            session_dir, f"spill_{name}"
        )
        self._closed = False
        self._flush_queue: List[ObjectID] = []
        self._spill_pending_bytes = 0  # un-flushed spill_data held in heap
        self._spilled_bytes_total = 0  # lifetime spill volume (stats)
        # background page population: fallocate reserves blocks but the
        # first WRITE to each page still takes a minor fault (~1.5 GB/s
        # effective vs ~7.5 GB/s on populated pages, measured on this host).
        # Populate the arena once off the hot path; pages stay resident
        # after arena frees, so steady-state puts run at warm-memcpy speed.
        if GlobalConfig.object_store_prealloc and getattr(self, "_prefault_bytes", 0) > 0:
            threading.Thread(
                target=self._prefault_loop,
                args=(self._prefault_bytes,),
                name=f"{name}-prefault",
                daemon=True,
            ).start()
        if self._spill_enabled:
            # disk writes happen off the store lock: _spill_locked only
            # copies bytes out of the arena; this thread persists them
            self._flusher = threading.Thread(
                target=self._flush_loop, name=f"{name}-spill-flush", daemon=True
            )
            self._flusher.start()

    def _prefault_loop(self, total: int, step: int = 32 * 1024 * 1024):
        for start in range(0, total, step):
            if self._closed:
                return
            length = min(step, total - start)
            t0 = time.monotonic()
            try:
                self._map.madvise(_MADV_POPULATE_WRITE, start, length)
            except (ValueError, OSError, AttributeError):
                return  # kernel without MADV_POPULATE_WRITE: faults apply
            # self-pacing at ~50% duty: finish a 2 GiB arena in a few
            # seconds without monopolizing a small host's core — too gentle
            # and the contention window stretches across the caller's whole
            # early workload, which costs more than the pacing saves
            time.sleep(max(0.01, time.monotonic() - t0))

    # -- server-side API (called via raylet RPC handlers or locally) --

    def create(self, object_id: ObjectID, size: int, creating_worker=None) -> int:
        # fresh key: never retain the caller's instance (the co-located
        # dispatch path passes it by reference; holding it would pin the
        # owner's weakref finalizer and break reference gc)
        object_id = ObjectID(object_id.binary())
        with self._cv:
            if object_id in self._entries:
                raise ValueError(f"object {object_id.hex()} already exists")
            offset = self._arena.allocate(size)
            if offset < 0:
                self._evict_locked(size)
                offset = self._arena.allocate(size)
            if offset < 0:
                raise ObjectStoreFullError(
                    f"cannot allocate {size} bytes (capacity {self.capacity})"
                )
            self._entries[object_id] = _Entry(offset, size, creating_worker)
            internal_metrics.inc(
                "ray_tpu_object_store_bytes_written_total", float(size)
            )
            return offset

    def put_bytes(self, object_id: ObjectID, data: bytes, creating_worker=None):
        """create+write+seal in one step (single-RPC path for small puts).

        Duplicate-tolerant: a put of an already-sealed object is a no-op
        success, so the RPC is retry-safe (a dropped/duplicated store_put
        frame must not fail the task — object ids name one task attempt's
        immutable result, so the bytes are the same)."""
        with self._cv:
            existing = self._entries.get(object_id)
            if existing is not None and existing.sealed:
                return
        offset = self.create(object_id, len(data), creating_worker)
        self._view[offset : offset + len(data)] = data
        self.seal(object_id)

    def seal(self, object_id: ObjectID):
        with self._cv:
            entry = self._entries.get(object_id)
            if entry is None:
                raise KeyError(f"seal of unknown object {object_id.hex()}")
            entry.sealed = True
            entry.last_used = time.monotonic()
            self._cv.notify_all()

    def abort(self, object_id: ObjectID):
        with self._cv:
            entry = self._entries.pop(object_id, None)
            if entry is not None and not entry.sealed:
                self._arena.free(entry.offset)

    def get_locations(
        self, object_ids: List[ObjectID], timeout: Optional[float], pin: bool = True
    ) -> Optional[Dict[ObjectID, Tuple[int, int]]]:
        """Block until all objects are sealed; returns {oid: (offset, size)}."""
        self._chaos_stall()  # local read path (shm readers resolve via here)
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                if all(
                    (e := self._entries.get(o)) is not None and e.sealed for o in object_ids
                ):
                    # restore + pin in one pass: a pinned entry cannot be
                    # re-spilled by a later restore's eviction in this loop
                    pinned = []
                    ok = True
                    for o in object_ids:
                        entry = self._entries[o]
                        if not entry.resident and not self._restore_locked(o, entry):
                            ok = False  # arena too full even after spilling
                            break
                        entry.last_used = time.monotonic()
                        entry.pin_count += 1
                        pinned.append(entry)
                    if ok:
                        result = {}
                        for o in object_ids:
                            entry = self._entries[o]
                            if not pin:
                                entry.pin_count -= 1
                            result[o] = (entry.offset, entry.size)
                        return result
                    for entry in pinned:  # partial restore: undo and wait
                        entry.pin_count -= 1
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                self._cv.wait(min(remaining, 1.0) if remaining is not None else 1.0)

    def contains(self, object_id: ObjectID) -> bool:
        with self._cv:
            e = self._entries.get(object_id)
            return e is not None and e.sealed

    def release(self, object_id: ObjectID):
        with self._cv:
            e = self._entries.get(object_id)
            if e is not None and e.pin_count > 0:
                e.pin_count -= 1
                if e.pin_count == 0 and e.delete_pending:
                    # a delete arrived while a reader held the buffer: the
                    # last release completes it (otherwise the entry would
                    # strand — the owner's ref gc only issues delete once)
                    self._delete_locked(object_id, e)

    def delete(self, object_id: ObjectID):
        with self._cv:
            e = self._entries.get(object_id)
            if e is None:
                return
            if e.pin_count > 0:
                e.delete_pending = True  # completed by the last release()
                return
            self._delete_locked(object_id, e)

    def _delete_locked(self, object_id: ObjectID, e: _Entry):
        self._entries.pop(object_id)
        if e.resident:
            self._arena.free(e.offset)
        else:
            if e.spill_data is not None:
                self._spill_pending_bytes -= e.size
                e.spill_data = None
            if e.spill_path is not None:
                try:
                    os.unlink(e.spill_path)
                except OSError:
                    pass

    def _evict_locked(self, needed: int):
        """Free ``needed`` bytes: spill unpinned sealed objects to disk when
        enabled (no data loss), otherwise LRU-drop them."""
        candidates = sorted(
            (
                o
                for o, e in self._entries.items()
                if e.sealed and e.pin_count == 0 and e.resident
            ),
            key=lambda o: self._entries[o].last_used,
        )
        freed = 0
        for o in candidates:
            e = self._entries[o]
            if self._spill_enabled:
                self._spill_locked(o, e)
            else:
                self._entries.pop(o)
                self._arena.free(e.offset)
            freed += e.size
            if freed >= needed:
                break

    def _spill_locked(self, object_id: ObjectID, e: _Entry):
        """Move the object out of the arena. Fast path: memcpy into heap +
        async flush. Backpressure: once un-flushed bytes exceed half the
        arena, write synchronously (bounded memory beats bounded latency
        when producers outrun the disk)."""
        self._spilled_bytes_total += e.size
        internal_metrics.inc("ray_tpu_object_store_spills_total")
        internal_metrics.inc(
            "ray_tpu_object_store_spilled_bytes_total", float(e.size)
        )
        if self._spill_pending_bytes > self.capacity // 2:
            os.makedirs(self._spill_dir, exist_ok=True)
            path = os.path.join(self._spill_dir, object_id.hex())
            with open(path, "wb") as f:
                f.write(self._view[e.offset : e.offset + e.size])
            e.spill_path = path
        else:
            e.spill_data = bytes(self._view[e.offset : e.offset + e.size])
            self._spill_pending_bytes += e.size
            self._flush_queue.append(object_id)
        self._arena.free(e.offset)
        e.offset = -1
        self._cv.notify_all()

    def _flush_loop(self):
        while not self._closed:
            with self._cv:
                while not self._flush_queue and not self._closed:
                    self._cv.wait(0.5)
                if self._closed:
                    return
                oid = self._flush_queue.pop(0)
                e = self._entries.get(oid)
                data = e.spill_data if e is not None else None
                if data is None:
                    continue  # restored or deleted before the flush
            os.makedirs(self._spill_dir, exist_ok=True)
            path = os.path.join(self._spill_dir, oid.hex())
            # a name of this thread's own: while it writes, with no lock
            # held, the object may be restored and spilled again, and the
            # synchronous spill writes ``path`` itself. Unlinking ``path``
            # on the way out then deleted the only copy of the object
            # (a reader's restore raised FileNotFoundError for good).
            tmp = path + ".flush"
            with open(tmp, "wb") as f:
                f.write(data)
            with self._cv:
                cur = self._entries.get(oid)
                if cur is e and e.spill_data is data and not e.resident:
                    os.replace(tmp, path)
                    e.spill_path = path
                    e.spill_data = None
                    self._spill_pending_bytes -= e.size
                else:
                    # restored or deleted while we were writing
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass

    def _restore_locked(self, object_id: ObjectID, e: _Entry) -> bool:
        """Bring a spilled object back into the arena (may spill others)."""
        offset = self._arena.allocate(e.size)
        if offset < 0:
            self._evict_locked(e.size)
            offset = self._arena.allocate(e.size)
        if offset < 0:
            return False
        if e.spill_data is not None:
            self._view[offset : offset + e.size] = e.spill_data
            self._spill_pending_bytes -= e.size
        else:
            # cold path: the object was flushed to disk. The read happens
            # under the lock — bounded by the object's size; the common
            # (recently-spilled) case is the memcpy branch above. readinto
            # lands file bytes straight in the arena (no intermediate bytes).
            with open(e.spill_path, "rb") as f:
                f.readinto(self._view[offset : offset + e.size])
            try:
                os.unlink(e.spill_path)
            except OSError:
                pass
        e.spill_path = None
        e.spill_data = None
        e.offset = offset
        e.last_used = time.monotonic()
        return True

    def _chaos_stall(self):
        """slow_store_reads fault hook: one attribute read when disarmed."""
        from ray_tpu._private import fault_injection

        if fault_injection._armed is not None:
            delay = fault_injection.store_read_delay(self.chaos_identity)
            if delay > 0:
                time.sleep(delay)

    def read(self, object_id: ObjectID, offset: int, length: int) -> Optional[bytes]:
        """Copy out a chunk of a sealed object (node-to-node transfer plane,
        reference: src/ray/object_manager/object_buffer_pool.cc)."""
        self._chaos_stall()
        with self._cv:
            e = self._entries.get(object_id)
            if e is None or not e.sealed:
                return None
            length = min(length, e.size - offset)
            if not e.resident:
                if e.spill_data is not None:  # not yet flushed to disk
                    return e.spill_data[offset : offset + length]
                with open(e.spill_path, "rb") as f:
                    f.seek(offset)
                    return f.read(length)
            base = e.offset
            # copy while holding the lock: an unpinned entry could otherwise
            # be spilled/evicted between lock release and the copy
            return bytes(self._view[base + offset : base + offset + length])

    def read_view(
        self, object_id: ObjectID, offset: int, length: int
    ) -> Optional[memoryview]:
        """Zero-copy chunk view for the transfer plane. The zero-copy path
        is served ONLY when the entry is actually pinned (the puller pins
        via store_get for the whole pull) — the invariant is enforced here,
        not assumed: a peer that lost its pin (bug, retry after release,
        protocol drift) gets a copy instead of a live view that eviction
        could concurrently reuse (ADVICE r4). Spilled entries use the
        copying read too."""
        self._chaos_stall()
        with self._cv:
            e = self._entries.get(object_id)
            if e is None or not e.sealed:
                return None
            if e.resident and e.pin_count > 0:
                length = min(length, e.size - offset)
                base = e.offset
                return self._view[base + offset : base + offset + length]
        data = self.read(object_id, offset, length)
        return None if data is None else memoryview(data)

    def stats(self) -> Dict[str, int]:
        with self._cv:
            return {
                "capacity": self.capacity,
                "num_objects": len(self._entries),
                "allocated_bytes": sum(e.size for e in self._entries.values()),
                "spilled_bytes_total": self._spilled_bytes_total,
            }

    def list_objects(self) -> List[Dict[str, object]]:
        """Per-object metadata for the state API (`ray list objects`
        equivalent; reference: node_manager.proto:415 GetObjectsInfo)."""
        with self._cv:
            return [
                {
                    "object_id": o.hex(),
                    "size": e.size,
                    "sealed": e.sealed,
                    "pin_count": e.pin_count,
                    "spilled": not e.resident,
                }
                for o, e in self._entries.items()
            ]

    # -- local data-plane access (for the raylet process itself) --

    def view(self, offset: int, size: int) -> memoryview:
        return self._view[offset : offset + size]

    def close(self):
        self._closed = True
        try:
            self._view.release()
            self._map.close()
            os.close(self._fd)
            os.unlink(self.path)
        except OSError:
            pass


class PlasmaClient:
    """Worker-side client: RPC for metadata, direct mmap for data.

    ``rpc_call(method, payload)`` is provided by the worker's raylet
    connection; methods are ``store_create/store_seal/...``.
    """

    #: client-side PTE-population granularity. PTEs are per-mapping: the
    #: raylet's background prefault does not warm THIS process's mapping,
    #: and the per-put madvise costs ~5 ms per 64 MB even on populated
    #: pages (measured) — ~35% of a 64 MB put. Track populated chunks so
    #: each region of the arena pays the syscall once per client lifetime.
    _POP_STEP = 32 * 1024 * 1024

    def __init__(self, store_path: str, capacity: int, rpc_call, local_store=None):
        if local_store is not None:
            # co-located raylet: metadata ops are method calls, not RPCs
            import functools

            self._rpc = functools.partial(_local_store_call, local_store)
        else:
            self._rpc = rpc_call
        fd = os.open(store_path, os.O_RDWR)
        try:
            self._map = mmap.mmap(fd, capacity)
        finally:
            os.close(fd)
        self._view = memoryview(self._map)
        self._capacity = capacity
        self._pop_chunks: set = set()
        self._pop_lock = threading.Lock()
        self._pop_closed = False
        if local_store is not None and GlobalConfig.object_store_prealloc:
            # background PTE warm-up for this mapping, bounded to pages the
            # store itself has committed (its prealloc bound): by the time
            # the first large puts land, writes run at warm-memcpy speed
            # instead of paying ~5 ms of on-demand madvise per 64 MB region
            warm = min(capacity, getattr(local_store, "_prefault_bytes", 0))
            if warm > 0:
                threading.Thread(
                    target=self._warm_loop, args=(warm,),
                    name="plasma-client-warm", daemon=True,
                ).start()

    def _warm_loop(self, total: int) -> None:
        # let the store's own prefault run first: populating after it means
        # this pass only builds PTEs (~2.5 ms/32 MiB) instead of doing the
        # tmpfs allocate+zero itself, and the caller's first puts aren't
        # competing with two madvise loops for a small host's core
        time.sleep(1.0)
        step = self._POP_STEP
        for start in range(0, total, step):
            if self._pop_closed:
                return
            t0 = time.monotonic()
            try:
                self._ensure_populated(start, min(step, total - start))
            except Exception:
                return
            # ~25% duty: never monopolize a small host's core at startup
            time.sleep(max(0.002, 3 * (time.monotonic() - t0)))

    def _ensure_populated(self, offset: int, size: int) -> None:
        """Populate the page tables under [offset, offset+size) once: puts
        into already-populated chunks skip the madvise entirely."""
        if not GlobalConfig.object_store_prealloc:
            return
        step = self._POP_STEP
        first, last = offset // step, (offset + size - 1) // step
        with self._pop_lock:
            missing = [
                c for c in range(first, last + 1) if c not in self._pop_chunks
            ]
            self._pop_chunks.update(missing)
        # merge adjacent chunks into runs: one syscall per contiguous gap
        run_start = None
        prev = None
        for c in missing + [None]:
            if run_start is not None and c != prev + 1:
                start = run_start * step
                length = min((prev + 1) * step, self._capacity) - start
                if length > 0:
                    _populate_range(self._map, start, length)
                run_start = None
            if c is not None and run_start is None:
                run_start = c
            prev = c

    def put_serialized(self, object_id: ObjectID, sobj: serialization.SerializedObject):
        """Reserve → serialize-in-place → seal. Large objects are written
        directly into the mapped arena at the offset the store hands back
        (no intermediate full-payload bytes); small objects (≤256 KiB) ride
        a single store_put RPC instead of the create/seal round-trips."""
        size = sobj.total_size()
        deadline = time.monotonic() + GlobalConfig.object_store_full_retry_s
        small = size <= 256 * 1024
        while True:
            try:
                if small:
                    # one RPC carrying the bytes instead of create+seal
                    self._rpc("store_put", (object_id, sobj.to_bytes()))
                    return
                offset = self._rpc("store_create", (object_id, size))
                break
            except ValueError:
                # object already exists (e.g. a retried task re-creating the
                # result its first attempt already sealed): nothing to do
                return
            except ObjectStoreFullError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)
        if size > 1024 * 1024:
            self._ensure_populated(offset, size)
        try:
            sobj.write_to(self._view[offset : offset + size])
        except BaseException:
            # never leave an unsealed entry behind (a failed deferred
            # device→host transfer would otherwise wedge readers forever)
            try:
                self._rpc("store_abort", object_id)
            except Exception:
                pass
            raise
        self._rpc("store_seal", object_id)
        serialization.note_inplace_write(size)
        internal_metrics.inc("ray_tpu_object_store_inplace_writes_total")

    def put_wire_bytes(self, object_id: ObjectID, data) -> bool:
        """Store an already-serialized wire payload (e.g. an owner-inline
        object being promoted to plasma). Returns False when the object
        already exists (a concurrent writer won the race)."""
        size = len(data)
        deadline = time.monotonic() + GlobalConfig.object_store_full_retry_s
        while True:
            try:
                if size <= 256 * 1024:
                    self._rpc("store_put", (object_id, data))
                    return True
                offset = self._rpc("store_create", (object_id, size))
                break
            except ValueError:
                return False
            except ObjectStoreFullError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)
        if size > 1024 * 1024:
            self._ensure_populated(offset, size)
        self._view[offset : offset + size] = data
        self._rpc("store_seal", object_id)
        return True

    def get_views(
        self, object_ids: List[ObjectID], timeout: Optional[float] = None
    ) -> Optional[Dict[ObjectID, memoryview]]:
        locs = self._rpc("store_get", (object_ids, timeout))
        if locs is None:
            return None
        return {o: self._view[off : off + size] for o, (off, size) in locs.items()}

    def contains(self, object_id: ObjectID) -> bool:
        return self._rpc("store_contains", object_id)

    def release(self, object_id: ObjectID):
        self._rpc("store_release", object_id)

    def delete(self, object_id: ObjectID):
        self._rpc("store_delete", object_id)

    def delete_batch(self, object_ids: List[ObjectID]):
        """One RPC frees many objects (the ref-gc thread coalesces)."""
        if object_ids:
            self._rpc("store_delete_batch", list(object_ids))

    def close(self):
        self._pop_closed = True
        try:
            self._view.release()
            self._map.close()
        except (OSError, BufferError):
            pass
