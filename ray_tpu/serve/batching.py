"""@serve.batch + @serve.continuous_batch: request batching in a replica.

Reference: serve/batching.py (@serve.batch decorator). Requests queue in
the replica; a flusher calls the wrapped fn with a list when either
``max_batch_size`` items are waiting or ``batch_wait_timeout_s`` elapses.

TPU twist (SURVEY.md §7.7): XLA recompiles per input shape, so
``bucket_sizes`` restricts flush sizes to a fixed set — a full *largest*
bucket flushes immediately; at timeout the largest bucket <= queue length
flushes (or the whole remainder when it is smaller than every bucket, in
which case the callable should pad internally). Intermediate buckets wait
for the timeout on purpose: flushing the moment any bucket fills would
defeat batching under steady low-concurrency load.

``@serve.continuous_batch`` is the iteration-level variant for decode-style
loops: the wrapped fn is a *step* function called repeatedly with the
current active set; new requests are admitted into the in-flight batch
between steps, and sequences leave the moment they call ``finish()`` —
no head-of-line blocking on the longest sequence. ``bucket_pad_size``
keeps the shape discipline: step fns pad the active set to the smallest
configured bucket so XLA never sees a new leading dim mid-burst.

Batchers are keyed by *weakref* to the bound instance (an ``id()`` key can
alias a dead instance's batcher after GC id-reuse) and are reaped — queue
drained, flusher thread stopped — when the instance is collected or
``shutdown_batchers()`` is called.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

from ray_tpu._private import accelerator, internal_metrics


def bucket_pad_size(n: int, bucket_sizes: Sequence[int]) -> int:
    """The smallest configured bucket >= ``n`` (or the largest bucket when
    ``n`` exceeds them all) — the leading dim a step fn should pad to so
    XLA only ever compiles the configured shapes."""
    buckets = sorted(bucket_sizes)
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


class _Pending:
    __slots__ = ("item", "event", "result", "error")

    def __init__(self, item):
        self.item = item
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


class _Batcher:
    """Static flusher: one call of ``fn`` per batch, results zip back."""

    mode = "static"

    def __init__(self, fn, max_batch_size, batch_wait_timeout_s, bucket_sizes,
                 name="fn"):
        self.fn = fn
        self.name = name
        self.max_batch_size = max_batch_size
        self.timeout = batch_wait_timeout_s
        self.buckets = sorted(bucket_sizes) if bucket_sizes else None
        if self.buckets:
            self.max_batch_size = self.buckets[-1]
        self.queue: List[_Pending] = []
        self.cv = threading.Condition()
        self._stop = False
        self.thread = threading.Thread(
            target=self._loop, daemon=True, name=f"serve-batch:{name}")
        self.thread.start()

    def submit(self, item):
        p = _Pending(item)
        with self.cv:
            if self._stop:
                raise RuntimeError(f"batcher for {self.name!r} is shut down")
            self.queue.append(p)
            self.cv.notify_all()
        p.event.wait()
        if p.error is not None:
            raise p.error
        return p.result

    def shutdown(self, drain: bool = True) -> None:
        """Stop the flusher. ``drain=True`` lets queued requests flush
        first; ``drain=False`` fails them immediately (used to reap a
        creation-race loser, whose queue is empty by construction)."""
        with self.cv:
            self._stop = True
            orphans: List[_Pending] = []
            if not drain:
                orphans, self.queue = self.queue, []
            self.cv.notify_all()
        for p in orphans:
            p.error = RuntimeError(f"batcher for {self.name!r} shut down")
            p.event.set()

    def _flush_size(self, n: int, timed_out: bool) -> int:
        if n >= self.max_batch_size:
            return self.max_batch_size
        if not timed_out:
            return 0
        if not self.buckets:
            return n
        fitting = [b for b in self.buckets if b <= n]
        return fitting[-1] if fitting else n

    def _loop(self):
        while True:
            with self.cv:
                while not self.queue and not self._stop:
                    self.cv.wait()
                if self._stop and not self.queue:
                    return
                start = time.monotonic()
                while (
                    not self._stop
                    and len(self.queue) < self.max_batch_size
                    and time.monotonic() - start < self.timeout
                ):
                    self.cv.wait(self.timeout / 4)
                take = self._flush_size(len(self.queue), timed_out=True)
                batch, self.queue = self.queue[:take], self.queue[take:]
            if not batch:
                continue
            try:
                results = self.fn([p.item for p in batch])
                if len(results) != len(batch):
                    raise ValueError(
                        f"@serve.batch fn returned {len(results)} results for "
                        f"a batch of {len(batch)}"
                    )
                for p, r in zip(batch, results):
                    p.result = r
                    p.event.set()
            except BaseException as e:  # noqa: BLE001
                for p in batch:
                    p.error = e
                    p.event.set()
            _record_step(self.name, self.mode, len(batch))


class _Sequence:
    """One caller's request inside a continuous batch.

    The step fn reads ``item``, keeps per-sequence scratch in ``state``
    (e.g. the decode cursor / generated tokens) and calls ``finish()``
    when the sequence is done — the slot frees for a queued request at
    the next step boundary.

    ``enqueued_at`` (monotonic) is stamped at submission so step fns can
    report queue wait / time-to-first-token. ``on_release`` is an optional
    zero-arg hook the scheduler invokes exactly once when the sequence
    leaves the batcher for ANY reason — finish, fail, step poison,
    cancellation, shutdown — the anchor for resources the step fn leased
    per sequence (KV-cache blocks) that must never leak on an abandoned
    request.
    """

    __slots__ = ("item", "state", "_result", "_error", "_done", "_event",
                 "enqueued_at", "cancelled", "on_release", "_released")

    def __init__(self, item):
        self.item = item
        self.state: Any = None
        self._result = None
        self._error: Optional[BaseException] = None
        self._done = False
        self._event = threading.Event()
        self.enqueued_at = time.monotonic()
        self.cancelled = False
        self.on_release: Optional[Callable[[], None]] = None
        self._released = False

    def finish(self, result) -> None:
        self._result = result
        self._done = True

    def fail(self, error: BaseException) -> None:
        self._error = error
        self._done = True

    @property
    def done(self) -> bool:
        return self._done

    def _release(self) -> None:
        if self._released:
            return
        self._released = True
        cb = self.on_release
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — release hooks must not poison
                pass


def _idle_span():
    """Waiting for requests, as a span on the profiler's clock — so a profile
    of a replica shows it as ``serve.batch_idle`` and not as a hole. Only in
    a process that already runs jax: the batcher imports it for no
    deployment."""
    return accelerator.quiet_span("serve.batch_idle")


def _caller_cancelled() -> bool:
    """True when the task running the current thread was cooperatively
    cancelled (``ray_tpu.cancel(force=False)`` — the async proxy's
    client-EOF path). Blocked batcher callers poll this: a plain
    ``Event.wait()`` would strand the replica thread (and any per-sequence
    leases) forever, since a cooperative cancel only sets a flag."""
    try:
        from ray_tpu import api as _api

        return _api.get_runtime_context().was_cancelled()
    except Exception:  # noqa: BLE001 — outside a task / before init
        return False


class _ContinuousBatcher:
    """Iteration-level scheduler: admits queued requests into the active
    set between calls of the step fn (decode-style continuous batching)."""

    mode = "continuous"

    #: how often a blocked caller re-checks for cooperative cancellation
    poll_interval_s = 0.02

    def __init__(self, step_fn, max_batch_size, batch_wait_timeout_s,
                 bucket_sizes, name="fn"):
        self.step_fn = step_fn
        self.name = name
        self.buckets = sorted(bucket_sizes) if bucket_sizes else None
        self.max_batch_size = (
            self.buckets[-1] if self.buckets else max_batch_size)
        self.timeout = batch_wait_timeout_s
        self.queue: List[_Sequence] = []
        self.cv = threading.Condition()
        self._stop = False
        self.thread = threading.Thread(
            target=self._loop, daemon=True, name=f"serve-cbatch:{name}")
        self.thread.start()

    def submit(self, item):
        seq = _Sequence(item)
        with self.cv:
            if self._stop:
                raise RuntimeError(f"batcher for {self.name!r} is shut down")
            self.queue.append(seq)
            self.cv.notify_all()
        try:
            while not seq._event.wait(self.poll_interval_s):
                if not seq.cancelled and _caller_cancelled():
                    from ray_tpu._private.core_worker import (
                        TaskCancelledError,
                    )

                    raise TaskCancelledError(self.name)
        except BaseException:
            # the caller is abandoning the sequence — cooperative cancel
            # noticed above, or a force-cancel injected into this thread:
            # flag it so the scheduler drops it and runs its release hook
            seq.cancelled = True
            with self.cv:
                self.cv.notify_all()
            raise
        if seq._error is not None:
            raise seq._error
        return seq._result

    def shutdown(self, drain: bool = True) -> None:
        with self.cv:
            self._stop = True
            orphans: List[_Sequence] = []
            if not drain:
                orphans, self.queue = self.queue, []
            self.cv.notify_all()
        for s in orphans:
            s._error = RuntimeError(f"batcher for {self.name!r} shut down")
            s._release()
            s._event.set()

    def _loop(self):
        active: List[_Sequence] = []
        while True:
            with self.cv:
                while not self.queue and not active and not self._stop:
                    with _idle_span():
                        self.cv.wait()
                if self._stop and not self.queue and not active:
                    return
                if not active and self.timeout > 0 and not self._stop:
                    # cold batch: give the queue one beat to fill toward a
                    # full bucket before the first step
                    start = time.monotonic()
                    while (
                        len(self.queue) < self.max_batch_size
                        and time.monotonic() - start < self.timeout
                        and not self._stop
                    ):
                        self.cv.wait(self.timeout / 4)
                # iteration-level admission: every free slot fills from
                # the queue at each step boundary (cancelled-while-queued
                # sequences release without ever entering a step)
                while self.queue and len(active) < self.max_batch_size:
                    s = self.queue.pop(0)
                    if s.cancelled:
                        s._release()
                        s._event.set()
                        continue
                    active.append(s)
            # cancelled mid-flight (client EOF / force-cancel): drop before
            # the step so the release hook (KV blocks etc.) fires now and
            # exactly once
            live: List[_Sequence] = []
            for s in active:
                if s.cancelled:
                    s._release()
                    s._event.set()
                else:
                    live.append(s)
            active = live
            if not active:
                continue
            step = list(active)
            try:
                self.step_fn(step)
            except BaseException as e:  # noqa: BLE001
                # a failed step poisons the whole in-flight batch: there is
                # no per-sequence result to salvage after a crashed forward
                for s in step:
                    s._error = e
                    s._release()
                    s._event.set()
                active = []
                continue
            _record_step(self.name, self.mode, len(step))
            active = []
            for s in step:
                if s._done:
                    s._release()
                    s._event.set()
                else:
                    active.append(s)


def _record_step(name: str, mode: str, n: int) -> None:
    tags = {"fn": name, "mode": mode}
    internal_metrics.inc("ray_tpu_serve_batch_steps_total", 1, tags)
    internal_metrics.inc("ray_tpu_serve_batch_items_total", n, tags)


# ---------------------------------------------------------------------------
# batcher registry: weakref-keyed, reaped on instance GC / explicit shutdown
# ---------------------------------------------------------------------------

# every decorator-closure holder that materialized a batcher in this
# process, keyed by id(holder) (dicts compare by value, so no `in` checks)
_HOLDERS: Dict[int, dict] = {}


def _reap(holder: dict, key) -> None:
    b = holder.pop(key, None)
    if b is not None:
        b.shutdown(drain=True)


def _bound_call(fn, owner):
    """``fn`` bound to ``owner`` through a weakref: the batcher (held by
    the registry) must not keep the instance alive, or the GC reap that
    stops its flusher thread can never fire."""
    if owner is None:
        return fn
    try:
        ref = weakref.ref(owner)
    except TypeError:
        return lambda items: fn(owner, items)  # non-weakrefable: legacy
    del owner

    def call(items):
        inst = ref()
        if inst is None:
            raise RuntimeError("batcher owner was garbage collected")
        return fn(inst, items)

    return call


def _batcher_for(holder: dict, owner, factory):
    """The batcher for ``owner`` in ``holder``, creating (and registering
    GC cleanup for) it on first use. Keyed by weakref so a recycled id()
    can never hand a new instance a dead instance's batcher."""
    if owner is None:
        key: Any = "__fn__"
    else:
        try:
            key = weakref.ref(owner)
        except TypeError:
            key = id(owner)  # non-weakrefable (e.g. __slots__): legacy keying
    b = holder.get(key)
    if b is not None:
        return b
    nb = factory()
    # dict.setdefault is atomic under the GIL: one batcher wins
    b = holder.setdefault(key, nb)
    if b is not nb:
        nb.shutdown(drain=False)  # lost the race: reap the idle flusher now
        return b
    _HOLDERS[id(holder)] = holder
    if isinstance(key, weakref.ref):
        # CPython runs weakref callbacks during dealloc, before the id can
        # be reused — the dead batcher is gone before any aliasing window
        weakref.finalize(owner, _reap, holder, key)
    return b


def shutdown_batchers(instance=None, drain: bool = True) -> int:
    """Shut down batchers materialized in this process — all of them, or
    only those bound to ``instance``. Returns the number stopped."""
    stopped = 0
    for holder in list(_HOLDERS.values()):
        for key, b in list(holder.items()):
            if instance is not None:
                bound_to = key() if isinstance(key, weakref.ref) else None
                if bound_to is not instance and key != id(instance):
                    continue
            if holder.pop(key, None) is not None:
                b.shutdown(drain=drain)
                stopped += 1
    return stopped


def batch(
    _fn: Optional[Callable] = None,
    *,
    max_batch_size: int = 8,
    batch_wait_timeout_s: float = 0.01,
    bucket_sizes: Optional[Sequence[int]] = None,
):
    """Decorator: ``fn(list_of_items) -> list_of_results`` becomes an
    item-at-a-time callable that batches concurrent callers."""

    def deco(fn):
        # no lock captured here: the decorated fn is pickled to replicas
        # and locks are unpicklable; the batcher materializes lazily in
        # the process that first calls it
        holder: dict = {}

        @functools.wraps(fn)
        def wrapper(*args):
            # support bound methods: the last positional arg is the item
            item = args[-1]
            bound = args[:-1]
            # one batcher per bound instance, not per decorated function:
            # two instances in one process must not flush each other's
            # requests against the wrong self
            owner = bound[0] if bound else None
            b = _batcher_for(
                holder,
                owner,
                lambda: _Batcher(
                    _bound_call(fn, owner),
                    max_batch_size,
                    batch_wait_timeout_s,
                    bucket_sizes,
                    name=getattr(fn, "__name__", "fn"),
                ),
            )
            return b.submit(item)

        return wrapper

    return deco if _fn is None else deco(_fn)


def continuous_batch(
    _fn: Optional[Callable] = None,
    *,
    max_batch_size: int = 8,
    batch_wait_timeout_s: float = 0.002,
    bucket_sizes: Optional[Sequence[int]] = None,
):
    """Decorator for iteration-level (continuous) batching.

    The wrapped fn is a *step* function ``fn(self, sequences)`` called
    repeatedly by the scheduler with the current active set — a list of
    sequence objects carrying ``.item`` (the caller's payload), ``.state``
    (mutable per-sequence scratch, starts as None) and ``.finish(result)``
    / ``.fail(exc)``. Callers invoke the wrapper with one item and block
    until their sequence finishes. Between steps, queued requests are
    admitted into free slots — a short sequence never waits for the
    longest one in its batch. With ``bucket_sizes``, pad the active set to
    ``bucket_pad_size(len(sequences), buckets)`` inside the step fn to
    keep XLA shapes static.
    """

    def deco(fn):
        holder: dict = {}

        @functools.wraps(fn)
        def wrapper(*args):
            item = args[-1]
            bound = args[:-1]
            owner = bound[0] if bound else None
            b = _batcher_for(
                holder,
                owner,
                lambda: _ContinuousBatcher(
                    _bound_call(fn, owner),
                    max_batch_size,
                    batch_wait_timeout_s,
                    bucket_sizes,
                    name=getattr(fn, "__name__", "fn"),
                ),
            )
            return b.submit(item)

        return wrapper

    return deco if _fn is None else deco(_fn)
