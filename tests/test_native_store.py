"""Native C++ arena allocator: semantics, parity with the Python fallback,
and PlasmaStore integration (reference analogue: plasma_allocator.cc +
dlmalloc.cc unit behavior)."""

import numpy as np
import pytest

from ray_tpu._private.object_store import _PyArena
from ray_tpu.native.native_store import NativeArena


def test_basic_alloc_free_coalesce():
    a = NativeArena(1 << 20)
    o1 = a.allocate(1000)
    o2 = a.allocate(1000)
    o3 = a.allocate(1000)
    assert 0 <= o1 < o2 < o3
    assert a.num_blocks() == 3
    # free the middle, then neighbors: the hole must coalesce back
    a.free(o2)
    a.free(o1)
    a.free(o3)
    assert a.num_blocks() == 0
    assert a.allocated_bytes() == 0
    assert a.largest_free() == 1 << 20


def test_full_and_best_fit():
    a = NativeArena(4096)
    o1 = a.allocate(2048)
    o2 = a.allocate(2048)
    assert o1 >= 0 and o2 >= 0
    assert a.allocate(64) == -1  # full
    a.free(o1)
    # best-fit: a 1 KiB request reuses the 2 KiB hole
    o3 = a.allocate(1024)
    assert o3 == o1
    assert a.free(12345) == -1 or True  # unknown offset: no crash


def test_double_free_is_safe():
    a = NativeArena(4096)
    o = a.allocate(128)
    a.free(o)
    a.free(o)  # second free is a no-op, must not corrupt
    assert a.allocated_bytes() == 0
    assert a.allocate(4096) == 0


def test_random_stress_invariants():
    """Random alloc/free workload: no overlapping blocks, exact accounting,
    and full coalescing once everything is freed. (Best-fit placement can
    legitimately differ from the Python first-fit fallback under
    fragmentation, so invariants — not placement parity — are the check.)"""
    rng = np.random.default_rng(0)
    cap = 1 << 16
    a = NativeArena(cap)
    live = {}
    for step in range(2000):
        if live and (rng.random() < 0.45 or step > 1500):
            k = list(live)[int(rng.integers(len(live)))]
            a.free(k)
            live.pop(k)
        else:
            size = int(rng.integers(1, 2048))
            off = a.allocate(size)
            if off >= 0:
                live[off] = size
        aligned = lambda s: max(64, (s + 63) & ~63)  # noqa: E731
        assert a.allocated_bytes() == sum(aligned(s) for s in live.values())
        spans = sorted((o, o + aligned(s)) for o, s in live.items())
        for (s1, e1), (s2, _e2) in zip(spans, spans[1:]):
            assert e1 <= s2, spans
    for k in list(live):
        a.free(k)
    assert a.allocated_bytes() == 0
    assert a.largest_free() == cap


def test_plasma_store_uses_native_arena(tmp_path):
    from ray_tpu._private.config import GlobalConfig
    from ray_tpu._private.object_store import PlasmaStore
    from ray_tpu._private.ids import ObjectID

    assert GlobalConfig.object_store_native
    store = PlasmaStore(str(tmp_path), capacity=1 << 20, name="nat")
    assert isinstance(store._arena, NativeArena)
    # round-trip an object through the native-backed store
    oid = ObjectID.from_random()
    store.put_bytes(oid, b"x" * 1000)
    locs = store.get_locations([oid], timeout=5)
    off, size = locs[oid]
    assert bytes(store.view(off, size)) == b"x" * 1000
    store.release(oid)
    store.delete(oid)
    assert store._arena.allocated_bytes() == 0
    store.close()


def test_late_flush_does_not_unlink_a_newer_spill(tmp_path, monkeypatch):
    """The flusher writes a spilled object with no lock held. Meanwhile the
    object is restored and spilled again, this time synchronously (the
    backpressure path), to the file of its id. The flusher, finding its copy
    stale, must drop its own file and not that one: the object's only copy
    (``test_spill_workload_completes`` then waited for a restore that raised
    FileNotFoundError on every retry)."""
    import os
    import threading
    import time

    from ray_tpu._private import object_store
    from ray_tpu._private.ids import ObjectID

    in_flush, go_on = threading.Event(), threading.Event()
    makedirs = os.makedirs

    def held(*args, **kwargs):
        if threading.current_thread().name.endswith("spill-flush") and not go_on.is_set():
            in_flush.set()
            go_on.wait(10)
        return makedirs(*args, **kwargs)

    monkeypatch.setattr(object_store.os, "makedirs", held)
    store = object_store.PlasmaStore(str(tmp_path), capacity=1 << 20, name="race")
    try:
        oid, data = ObjectID.from_random(), bytes(range(256)) * 1024
        store.put_bytes(oid, data)
        with store._cv:
            store._spill_locked(oid, store._entries[oid])  # queued for the flusher
        assert in_flush.wait(10)  # the flusher holds its copy, nothing written yet
        assert store.get_locations([oid], timeout=5) is not None  # restored
        store.release(oid)
        with store._cv:
            pending = store._spill_pending_bytes
            store._spill_pending_bytes = store.capacity  # producers outrun the disk
            store._spill_locked(oid, store._entries[oid])  # written under the lock
            store._spill_pending_bytes = pending
        assert store._entries[oid].spill_path is not None
        go_on.set()
        # one flusher, one queue: once it has written a later object it has
        # passed its verdict on this one
        later = ObjectID.from_random()
        store.put_bytes(later, b"y" * 4096)
        with store._cv:
            store._spill_locked(later, store._entries[later])
        deadline = time.monotonic() + 10
        while store._entries[later].spill_path is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert store._entries[later].spill_path is not None
        off, size = store.get_locations([oid], timeout=5)[oid]
        assert bytes(store.view(off, size)) == data
        store.release(oid)
    finally:
        go_on.set()
        store.close()
