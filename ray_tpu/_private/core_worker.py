"""Core worker: the in-process runtime linked into every driver and worker.

Responsibilities (reference: src/ray/core_worker/core_worker.cc — SubmitTask
:1893, Get :1322, Put :1110, ExecuteTask :2553; task_manager.h ownership and
retries; transport/direct_task_transport.cc lease-based direct submission;
transport/direct_actor_task_submitter.cc per-handle actor ordering):

- owns objects created by its tasks/puts (inline results live in the
  in-process memory store; large results in the node's shm plasma store)
- submits normal tasks by leasing workers from the raylet and pushing the
  task directly to the leased worker (two-level scheduling)
- submits actor tasks directly to the actor's worker with per-handle
  sequence numbers
- executes tasks when running inside a worker process (the same class serves
  both roles, like the reference's CoreWorker)
- retries failed tasks (owner-side) and surfaces failures as exception
  objects that re-raise at ``get``
"""

from __future__ import annotations

import hashlib
import io
import itertools
import json
import logging
import os
import pickle
import queue
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import cloudpickle

from ray_tpu._private import fault_injection
from ray_tpu._private import internal_metrics
from ray_tpu._private import serialization
from ray_tpu._private import trace as _trace
from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu._private import object_store as object_store_mod
from ray_tpu._private.object_store import MemoryStore, ObjectLostError, PlasmaClient
from ray_tpu._private import rpc as rpc_mod
from ray_tpu._private.rpc import (
    ConnectionLost,
    RpcClient,
    RpcError,
    RpcServer,
    ServerConn,
)

logger = logging.getLogger(__name__)

PLASMA_MARKER = b"\x00__IN_PLASMA__"

#: the reply to a pushed task is its result, and a task runs as long as it runs
#: (a train worker's ``run`` lasts the whole job): its slot carries no deadline
#: (0 disables ``rpc_async_call_timeout_s``, which cut every task and actor call
#: off at 120 s). A worker that dies closes its connection, which fails the slot.
TASK_REPLY_TIMEOUT = 0


# ---------------------------------------------------------------------------
# public exception types
# ---------------------------------------------------------------------------


class RayTpuError(Exception):
    pass


class TaskError(RayTpuError):
    """Wraps an exception raised inside a task; re-raised at ``get``."""

    def __init__(self, cause: BaseException, task_desc: str = "", tb: str = ""):
        self.cause = cause
        self.task_desc = task_desc
        self.tb = tb
        super().__init__(f"task {task_desc} failed: {cause!r}\n{tb}")


class ActorDiedError(RayTpuError):
    pass


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class WorkerCrashedError(RayTpuError):
    pass


class TaskCancelledError(RayTpuError):
    """The task was cancelled via ``ray_tpu.cancel``; re-raised at ``get``
    on any of the task's return refs."""

    def __init__(self, task_desc: str = ""):
        self.task_desc = task_desc
        super().__init__(
            f"task {task_desc or '<unknown>'} was cancelled"
        )


# ---------------------------------------------------------------------------
# argument capture: collect nested ObjectRefs while serializing
# ---------------------------------------------------------------------------


class _RefCollectingPickler(cloudpickle.Pickler):
    """Serializes args while recording every nested ObjectID, so the owner can
    promote inline values to plasma before a borrower needs them (the
    reference tracks these as 'borrowed' refs, reference_count.h:67)."""

    def __init__(self, file):
        super().__init__(file, protocol=5)
        self.refs: List[ObjectID] = []

    def reducer_override(self, obj):
        if isinstance(obj, ObjectID):
            self.refs.append(obj)
            return (ObjectID, (obj.binary(),))
        r = serialization._maybe_reduce_device(obj)
        if r is not None:
            return r
        # cloudpickle implements function/class-by-value in its own
        # reducer_override — returning NotImplemented here would silently
        # fall back to by-reference pickling and break closures
        return super().reducer_override(obj)


def _serialize_with_refs(obj: Any) -> Tuple[bytes, List[ObjectID]]:
    buf = io.BytesIO()
    p = _RefCollectingPickler(buf)
    p.dump(obj)
    return buf.getvalue(), p.refs


# ---------------------------------------------------------------------------


class CoreWorker:
    def __init__(
        self,
        *,
        mode: str,  # "driver" | "worker"
        job_id: JobID,
        gcs_address: Tuple[str, int],
        raylet_address: Tuple[str, int],
        worker_id: Optional[WorkerID] = None,
        session_dir: str = "",
    ):
        self.mode = mode
        self.job_id = job_id
        self.worker_id = worker_id or WorkerID.from_random()
        self.session_dir = session_dir
        self.memory_store = MemoryStore()
        self._task_counter = 0
        self._put_counter = 0
        self._counter_lock = threading.Lock()
        self._current_task_id = TaskID.for_driver_task(job_id)
        self._task_ctx = threading.local()
        _trace.init_from_config()

        # chaos attribution: this worker belongs to its raylet's node, so
        # partition rules naming that node also cover its workers/driver
        self._chaos_node_identity = fault_injection.identity_for(
            None, tuple(raylet_address)
        )
        self.gcs = RpcClient(
            gcs_address, on_notify=self._on_gcs_notify, prefer_local=True
        )
        self.gcs.chaos_identity = self._chaos_node_identity
        if mode == "driver":
            # proactive actor-cache updates are a driver-side optimization;
            # at N workers the wholesale subscription turns every actor
            # event into N pubsub frames (quadratic at envelope scale).
            # Workers resolve actors on demand (wait_for_actor) and
            # invalidate their caches on ConnectionLost.
            self.gcs.call("subscribe", "actors")  # actor address/state
        # node events are rare (node count, not op count) and every worker
        # needs them: the pull failure path leaves stale locations in place
        # and relies on node-removed to mark objects lost for lineage
        # recovery (_on_gcs_notify "nodes")
        self.gcs.call("subscribe", "nodes")
        try:
            self.gcs.call("subscribe", "chaos", timeout=5.0)
            blob = self.gcs.call("kv_get", ("chaos", "schedule"), timeout=5.0)
            if blob:
                # a schedule armed before this worker/driver joined
                fault_injection.arm(
                    json.loads(blob),
                    local_addresses=[tuple(raylet_address)],
                )
        except Exception:
            pass  # older GCS without a chaos plane
        self.captured_logs: "deque" = deque(maxlen=1000)
        if mode == "driver" and GlobalConfig.log_to_driver:
            # worker stdout/stderr streamed back via the log monitors
            # (reference: log_monitor.py -> gcs pubsub -> driver)
            self.gcs.call("subscribe", "logs")
        self.raylet = RpcClient(raylet_address, prefer_local=True)
        self.raylet.chaos_identity = self._chaos_node_identity
        reg = self.raylet.call(
            "register_worker",
            {
                "worker_id": self.worker_id,
                "address": ("", 0),  # drivers don't serve tasks
                "pid": os.getpid(),
                "is_driver": True,
            },
        ) if mode == "driver" else None
        self.node_id: Optional[NodeID] = reg["node_id"] if reg else None
        self._store_info = (
            (reg["store_path"], reg["store_capacity"]) if reg else None
        )
        self.plasma: Optional[PlasmaClient] = None
        # set once plasma is attached: a worker's task server starts before
        # late_register returns, and a pushed task must not observe
        # plasma=None (the lease can land between registration and attach)
        self.runtime_ready = threading.Event()
        if self._store_info:
            self.plasma = PlasmaClient(
                self._store_info[0],
                self._store_info[1],
                self.raylet.call,
                local_store=object_store_mod.local_store_for(tuple(raylet_address)),
            )
            self.runtime_ready.set()

        # function/class import cache
        import weakref as _weakref

        self._fn_cache: Dict[bytes, Any] = {}
        self._fn_exported: set = set()
        self._fn_export_ids: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()
        # direct connections to other workers / actors
        self._worker_clients: Dict[Tuple[str, int], RpcClient] = {}
        self._worker_clients_lock = threading.Lock()
        # actor bookkeeping (submitter side). Ordered (max_concurrency==1)
        # actors get caller-side FIFO submission: one in-flight call per
        # (caller, actor), drained in seq order — this keeps ordering simple
        # and correct across actor restarts (the reference instead pipelines
        # with worker-side seq queues, direct_actor_task_submitter.cc).
        self._actor_info: Dict[ActorID, Dict[str, Any]] = {}
        self._actor_seq: Dict[ActorID, int] = {}
        self._actor_pending: Dict[ActorID, List] = {}
        self._actor_inflight: Dict[ActorID, int] = {}
        self._actor_next_send: Dict[ActorID, int] = {}
        # per-actor outbox drained by at most one submitter thread at a
        # time: sends hit the actor's connection in seq order without any
        # cross-thread gate (the round-2 wire-order gate could starve the
        # submitter pool when racing pumps inverted queue order — all
        # threads blocked waiting for a seq whose send action had no free
        # thread, wedging pipelined calls for worker_lease_timeout_s*4)
        self._actor_outbox: Dict[ActorID, Any] = {}
        self._actor_draining: Dict[ActorID, bool] = {}
        self._actor_lock = threading.Lock()
        # pending normal tasks owned by this worker
        self._pending: Dict[TaskID, Dict[str, Any]] = {}
        self._pending_lock = threading.Lock()
        # ownership-side lineage fan-out for recursive cancellation: parent
        # task binary -> TaskIDs of still-pending children submitted by this
        # process while that parent was executing (TaskIDs hash the parent,
        # so parentage is not recoverable from an ID — this registry is the
        # explicit edge set). Entries are pruned as children complete.
        self._children: Dict[bytes, List[TaskID]] = {}
        # owner-based object directory: object -> raylet address of a node
        # whose plasma store holds it (reference:
        # object_manager/ownership_based_object_directory.cc — locations come
        # from owners/producers, not from a central service)
        self._locations: Dict[bytes, Tuple[str, int]] = {}
        self._locations_lock = threading.Lock()
        self._pulls_inflight: set = set()
        from concurrent.futures import ThreadPoolExecutor

        # 16 slots: enough that a few dead-peer pulls (each blocking up to
        # the transfer timeout) can't starve pulls of healthy objects
        self._pull_pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="obj-pull"
        )
        # lineage (reference: core_worker/object_recovery_manager.h:41 +
        # task_manager.h:203 ResubmitTask): plasma return oid -> the spec of
        # the task that created it, kept while local refs exist so the owner
        # can re-execute the task if every copy of the object is lost
        self._lineage: Dict[bytes, Dict[str, Any]] = {}
        self._lost_objects: set = set()  # binaries whose location died
        # lease cache (reference: direct_task_transport.cc OnWorkerIdle —
        # a leased worker runs queued same-shape tasks back to back instead
        # of a lease round-trip per task)
        self._idle_leases: Dict[Tuple, List] = {}
        # dynamic-returns: top-level return oid -> item oids whose lineage
        # pins live only as long as the generator ref does
        self._dynamic_children: Dict[bytes, List[bytes]] = {}
        self._lease_waiting: Dict[Tuple, Any] = {}  # sig -> deque[spec]
        self._lease_inflight: Dict[Tuple, int] = {}  # sig -> lease rpcs out
        self._active_pushes: Dict[Tuple, int] = {}  # sig -> pushes in flight
        self._lease_lock = threading.Lock()
        # raylet clients for spillback leasing on other nodes
        self._raylet_clients: Dict[Tuple[str, int], RpcClient] = {}
        self._node_addr_cache: Dict[NodeID, Tuple[str, int]] = {}
        # local reference counting: when the last local ObjectRef instance
        # handed out by this worker is GC'd, the owned object is freed
        # (a single-process slice of the reference's distributed
        # ReferenceCounter, reference_count.h:61)
        self._local_refs: Dict[bytes, int] = {}
        self._local_refs_lock = threading.Lock()
        # inline objects promoted to plasma for borrowers: their frees must
        # still issue a plasma delete even though a local value exists
        self._promoted: set = set()
        # async submission queue + submitter pool (lease-per-task with reuse)
        self._shutdown = threading.Event()
        # dropped-ref cleanup runs on this thread, never in the finalizer
        # (finalizers must not lock or RPC — see _on_ref_deleted)
        import collections as _collections

        self._gc_pending: "_collections.deque" = _collections.deque()
        self._gc_signaled = False  # edge trigger: armed while gc may sleep
        # finalizer->gc-thread wakeup rides a pipe: os.write is a plain
        # syscall, usable from a weakref finalizer with zero lock risk
        # (an Event would deadlock if GC ran a finalizer on the gc thread
        # inside Event.wait, which holds the Event's condition lock)
        self._gc_r, self._gc_w = os.pipe()
        os.set_blocking(self._gc_r, False)
        os.set_blocking(self._gc_w, False)
        self._gc_thread = threading.Thread(
            target=self._ref_gc_loop, name="ref-gc", daemon=True
        )
        self._gc_thread.start()
        # wire-spec templates: the static fields of a RemoteFunction's spec
        # (fn_id, resources, retry policy, ...) are registered once and
        # shipped to each worker connection once; per-task frames carry only
        # the varying fields (task_id, args, deps). This halves the pickle
        # work per task on both ends — the analogue of the reference caching
        # serialized TaskSpec protos per function in the submitter.
        self._tmpl_defs: Dict[bytes, Dict[str, Any]] = {}
        self._tmpl_by_key: Dict[Tuple, bytes] = {}
        self._tmpl_counter = itertools.count(1)
        # actor-call templates keyed by (actor, method, num_returns, ordered);
        # entries are dropped with the actor (_forget_actor)
        self._actor_tmpl_cache: Dict[Tuple, Tuple[bytes, Dict[str, Any]]] = {}
        # streamed batch-push bookkeeping: bid -> {"specs": [...], "acked": bytearray}
        self._batches: Dict[int, Dict[str, Any]] = {}
        self._batches_lock = threading.Lock()
        self._batch_ids = itertools.count(1)
        self._submit_queue: "queue.Queue[Optional[Dict[str, Any]]]" = queue.Queue()
        self._submitters = [
            threading.Thread(target=self._submit_loop, name=f"submitter-{i}", daemon=True)
            for i in range(8)
        ]
        for t in self._submitters:
            t.start()
        # task events → GCS
        self._events: "deque" = deque()
        self._events_thread = threading.Thread(target=self._event_loop, daemon=True)
        self._events_thread.start()

    def late_register(self, address: Tuple[str, int]):
        """Worker-mode registration once the task server port is known."""
        reg = self.raylet.call(
            "register_worker",
            {"worker_id": self.worker_id, "address": address, "pid": os.getpid()},
        )
        self.node_id = reg["node_id"]
        self._store_info = (reg["store_path"], reg["store_capacity"])
        self.plasma = PlasmaClient(
            self._store_info[0],
            self._store_info[1],
            self.raylet.call,
            local_store=object_store_mod.local_store_for(tuple(self.raylet.address)),
        )
        self.runtime_ready.set()

    # ------------------------------------------------------------------
    # id helpers
    # ------------------------------------------------------------------

    def _next_task_id(self, actor_id: Optional[ActorID] = None) -> TaskID:
        with self._counter_lock:
            self._task_counter += 1
            counter = self._task_counter
        # `or` (not getattr default): _run restores task_id to None after a
        # task, so code running outside a task on a pooled thread — e.g. an
        # actor constructor submitting to another actor — must still fall
        # back to the root task id
        parent = getattr(self._task_ctx, "task_id", None) or self._current_task_id
        if actor_id is not None:
            return TaskID.for_actor_task(self.job_id, parent, counter, actor_id)
        return TaskID.for_normal_task(self.job_id, parent, counter)

    def _record_child(self, spec: Dict[str, Any], task_id: TaskID):
        """Record the parent->child edge for recursive cancellation. TaskIDs
        hash the parent, so parentage is not recoverable from an ID — this
        registry is the explicit edge set, pruned as children complete."""
        parent = getattr(self._task_ctx, "task_id", None) or self._current_task_id
        parent_bin = parent.binary()
        spec["_parent_bin"] = parent_bin
        with self._pending_lock:
            self._children.setdefault(parent_bin, []).append(task_id)

    def _prune_child(self, spec: Dict[str, Any]):
        """Drop a completed task from its parent's child registry (called
        with the task terminally resolved; best-effort)."""
        parent_bin = spec.get("_parent_bin")
        if parent_bin is None:
            return
        with self._pending_lock:
            children = self._children.get(parent_bin)
            if children is None:
                return
            try:
                children.remove(spec["task_id"])
            except ValueError:
                pass
            if not children:
                self._children.pop(parent_bin, None)

    def _next_put_id(self) -> ObjectID:
        with self._counter_lock:
            self._put_counter += 1
            counter = self._put_counter
        parent = getattr(self._task_ctx, "task_id", None) or self._current_task_id
        return ObjectID.from_put(parent, counter)

    # ------------------------------------------------------------------
    # put / get / wait
    # ------------------------------------------------------------------

    def put(self, value: Any) -> ObjectID:
        span = _trace.start_span("object.put", kind="object") if _trace._active else None
        object_id = self._next_put_id()
        sobj = serialization.serialize(value)
        self.plasma.put_serialized(object_id, sobj)
        self._register_ref(object_id)
        self.register_locations({object_id.binary(): self.raylet.address})
        if span is not None:
            _trace.end_span(span, attrs={"object_id": object_id.hex()[:16]})
        return object_id

    # -- object directory ------------------------------------------------

    def register_locations(self, locations: Dict[bytes, Tuple[str, int]]):
        if not locations:
            return
        with self._locations_lock:
            for binary, addr in locations.items():
                self._locations[binary] = tuple(addr)

    def _location_of(self, oid: ObjectID) -> Optional[Tuple[str, int]]:
        with self._locations_lock:
            return self._locations.get(oid.binary())

    def _pull_if_remote(self, oid: ObjectID, timeout: Optional[float] = None) -> None:
        """Ensure a remotely-located object is present in the local store.
        Deduplicates concurrent pulls of the same object."""
        if self.plasma is None or self.plasma.contains(oid):
            return
        loc = self._location_of(oid)
        if loc is None or loc == tuple(self.raylet.address):
            return
        binary = oid.binary()
        with self._locations_lock:
            if binary in self._pulls_inflight:
                return  # another caller is pulling; plasma get provides the wait
            self._pulls_inflight.add(binary)
        try:
            ok = self.raylet.call("store_pull", (oid, loc), timeout=timeout or 120.0)
            if not ok:
                # the local raylet contacted the peer and the peer cannot
                # serve the object (dead or dropped it): the location is
                # genuinely gone — mark lost so get() can try lineage recovery
                logger.warning(
                    "pull of %s failed: %s no longer holds it; marking lost",
                    oid.hex()[:12], loc,
                )
                with self._locations_lock:
                    if self._locations.get(binary) == loc:
                        self._locations.pop(binary, None)
                    self._lost_objects.add(binary)
        except Exception as e:  # noqa: BLE001
            # an RPC error/timeout here proves nothing about the peer (it may
            # just be a short caller deadline on a big transfer): keep the
            # location so a later get can retry; node death is detected
            # separately via the GCS node-removed notification
            logger.warning(
                "pull of %s from %s did not complete (%s: %s); will retry",
                oid.hex()[:12], loc, type(e).__name__, e,
            )
        finally:
            with self._locations_lock:
                self._pulls_inflight.discard(binary)

    def _start_pulls(self, object_ids: Sequence[ObjectID], timeout: Optional[float]):
        """Kick off background pulls for known-remote objects; the blocking
        plasma get (which waits on the local seal) provides completion.
        Pulls run on a small bounded pool — a thread per pulled object
        would mean thousands of threads at the reference's envelope scale
        (release/benchmarks/README.md); the raylet-side transfer is the
        actual bandwidth limiter, so a few concurrent pulls saturate it."""
        own = tuple(self.raylet.address)
        for oid in object_ids:
            loc = self._location_of(oid)
            if loc is None or loc == own:
                continue
            with self._locations_lock:
                if oid.binary() in self._pulls_inflight:
                    continue
            self._pull_pool.submit(self._pull_if_remote, oid, timeout)

    def _register_ref(self, ref: ObjectID):
        import weakref

        binary = ref.binary()
        with self._local_refs_lock:
            self._local_refs[binary] = self._local_refs.get(binary, 0) + 1
        weakref.finalize(ref, self._on_ref_deleted, binary)

    def _on_ref_deleted(self, binary: bytes):
        """Weakref-finalizer callback. MUST stay lock-free and non-blocking:
        finalizers run at arbitrary allocation points — including inside
        another frame that holds an executor/RPC lock — so taking any lock
        or making an RPC here can deadlock the whole process (observed: GC
        fired inside ThreadPoolExecutor.submit on the rpc server pool, and
        the plasma-delete RPC it then issued could never be dispatched).
        deque.append is atomic; the pipe write is a raw syscall (EAGAIN
        when full is fine — the gc thread is already awake then); the
        ref-gc thread does the real work. Edge-triggered: the write (and
        the context switch it causes) is skipped while the gc thread is
        known-awake — at tens of thousands of dropped refs/s on a small
        host the wakeup churn otherwise costs more than the bookkeeping.
        A lost race only delays the wakeup to the loop's next drain pass,
        never loses the ref (the deque is re-checked after re-arming)."""
        self._gc_pending.append(binary)
        if not self._gc_signaled:
            self._gc_signaled = True
            try:
                os.write(self._gc_w, b"x")
            except (BlockingIOError, OSError):
                pass

    def _ref_gc_loop(self):
        # event-driven, not polled: hundreds of idle workers each waking
        # 20x/s to check an empty deque measurably loads a small host.
        # selectors (epoll/poll), never the select() syscall wrapper: that
        # one is capped at FD_SETSIZE (1024) and a worker that opened >1024
        # fds before init (sockets, datasets) gets a pipe fd past the cap —
        # it then raises "filedescriptor out of range" forever and ref gc
        # dies.
        import selectors as _selectors

        sel = _selectors.DefaultSelector()
        try:
            sel.register(self._gc_r, _selectors.EVENT_READ)
        except (ValueError, OSError):
            return  # shutdown closed the pipe before the thread started
        try:
            while not self._shutdown.is_set():
                try:
                    binary = self._gc_pending.popleft()
                except IndexError:
                    # re-arm the edge trigger, then re-check: an append that
                    # raced the empty popleft (and skipped its write because
                    # the flag was still set) is picked up here
                    self._gc_signaled = False
                    if self._gc_pending:
                        continue
                    try:
                        if sel.select(5.0):
                            os.read(self._gc_r, 4096)  # drain wakeup bytes
                    except OSError:
                        pass
                    continue
                try:
                    to_free = self._process_ref_deleted(binary)
                except Exception:
                    logger.exception("ref gc failed for %s", binary.hex()[:16])
                    continue
                if to_free:
                    batch = [to_free]
                    # coalesce: one delete RPC frees every queued plasma object
                    while len(batch) < 256:
                        try:
                            nxt = self._gc_pending.popleft()
                        except IndexError:
                            break
                        try:
                            extra = self._process_ref_deleted(nxt)
                        except Exception:
                            logger.exception(
                                "ref gc failed for %s", nxt.hex()[:16]
                            )
                            continue
                        if extra:
                            batch.append(extra)
                    try:
                        if self.plasma is not None:
                            self.plasma.delete_batch(batch)
                    except Exception:
                        pass
        finally:
            sel.close()

    def _process_ref_deleted(self, binary: bytes):
        """Local bookkeeping for one dropped ref. Returns the ObjectID when
        the caller must issue a plasma delete (plasma-resident or promoted
        objects); inline-only results free with zero RPCs — the dominant
        case in tight submit/get loops."""
        with self._local_refs_lock:
            n = self._local_refs.get(binary, 0) - 1
            if n > 0:
                self._local_refs[binary] = n
                return None
            self._local_refs.pop(binary, None)
        if self._shutdown.is_set():
            return None
        oid = ObjectID(binary)
        data = self.memory_store.get(oid, timeout=0)
        inline_only = (
            data is not None
            and data != PLASMA_MARKER
            and binary not in self._promoted
        )
        self._promoted.discard(binary)
        self.memory_store.delete(oid)
        with self._pending_lock:
            self._lineage.pop(binary, None)
            # dropping a dynamic task's generator ref releases the lineage
            # pinned for item refs the user does NOT hold; held item refs
            # were adopted in get() and release via their own finalizers
            children = self._dynamic_children.pop(binary, ())
        if children:
            with self._local_refs_lock:
                held = {c for c in children if self._local_refs.get(c, 0) > 0}
            with self._pending_lock:
                for child in children:
                    if child not in held:
                        self._lineage.pop(child, None)
        return None if inline_only or self.plasma is None else oid

    def put_exception(self, object_id: ObjectID, exc: BaseException):
        sobj = serialization.serialize(exc, is_exception=True)
        self.plasma.put_serialized(object_id, sobj)

    def _promote_to_plasma(self, object_id: ObjectID):
        """Copy an owner-inline object into plasma so borrowers can read it."""
        data = self.memory_store.get(object_id, timeout=0)
        if data is None or data == PLASMA_MARKER:
            return
        if self.plasma.contains(object_id):
            return
        # put_wire_bytes takes the co-located local-store fast path (method
        # calls, not raylet RPCs) and the single-RPC small path — the old
        # direct store_create/store_seal calls paid two RPC round-trips
        # even when the store lives in this process
        if not self.plasma.put_wire_bytes(object_id, data):
            return  # another thread promoted it concurrently
        binary = object_id.binary()
        self._promoted.add(binary)
        # Close the seal->mark window (ADVICE r3): if the final local ref
        # dropped while we were sealing, _process_ref_deleted classified the
        # object inline-only (mark not yet visible) and skipped the plasma
        # delete — detect that here and free the copy ourselves. Marking
        # BEFORE create would be worse: the deleter may then free the
        # UNSEALED entry while this thread is still memcpying into it.
        with self._local_refs_lock:
            gone = self._local_refs.get(binary, 0) <= 0
        if gone:
            self._promoted.discard(binary)
            try:
                self.plasma.delete(object_id)
            except Exception:
                pass

    def get(self, object_ids: Sequence[ObjectID], timeout: Optional[float] = None) -> List[Any]:
        if _trace._active:
            span = _trace.start_span("object.get", kind="object")
            if span is not None:
                try:
                    result = self._get_inner(object_ids, timeout)
                except Exception:
                    _trace.end_span(span, status="error",
                                    attrs={"n": len(object_ids)})
                    raise
                _trace.end_span(span, attrs={"n": len(object_ids)})
                return result
        return self._get_inner(object_ids, timeout)

    def _get_inner(self, object_ids: Sequence[ObjectID], timeout: Optional[float] = None) -> List[Any]:
        deadline = None if timeout is None else time.monotonic() + timeout
        results: Dict[ObjectID, Any] = {}
        plasma_ids: List[ObjectID] = []
        for oid in object_ids:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            # ownership BEFORE the store read: completion stores the result
            # and THEN pops the task from _pending, so reading in the other
            # order can classify an in-flight inline reply as
            # plasma-resident and wait on a store it will never reach
            owned = self._owns(oid)
            data = self.memory_store.get(oid, timeout=0)
            if data is None and owned:
                # owned but still pending: wait for the reply
                data = self.memory_store.get(oid, timeout=remaining)
                if data is None:
                    raise GetTimeoutError(f"timed out waiting for {oid.hex()[:16]}")
            if data is None or data == PLASMA_MARKER:
                plasma_ids.append(oid)
            else:
                results[oid] = self._deserialize(memoryview(data))
        if plasma_ids:
            views = self._plasma_get_with_recovery(plasma_ids, deadline)
            for oid, view in views.items():
                try:
                    value = self._deserialize(view)
                except BaseException:
                    self._release_plasma(oid.binary())
                    raise
                self._schedule_release(oid, view, value)
                results[oid] = value
        for value in results.values():
            self._adopt_dynamic_refs(value)
        return [results[oid] for oid in object_ids]

    def _adopt_dynamic_refs(self, value: Any):
        """Register the item refs inside a fetched ObjectRefGenerator so
        their lineage pins live as long as the user holds them — not just as
        long as the generator's top-level ref (the common `get(t.remote())`
        pattern drops that temporary immediately)."""
        from ray_tpu._private.ids import ObjectRefGenerator

        if isinstance(value, ObjectRefGenerator):
            for ref in value:
                self._register_ref(ref)

    def _plasma_get_with_recovery(
        self, plasma_ids: List[ObjectID], deadline: Optional[float]
    ) -> Dict[ObjectID, memoryview]:
        """Blocking plasma get that notices lost objects between waits and
        re-executes their creating tasks from lineage (reference:
        object_recovery_manager.h:90 RecoverObject)."""
        while True:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            slice_t = 2.0 if remaining is None else min(2.0, remaining)
            self._start_pulls(plasma_ids, remaining)
            views = self.plasma.get_views(plasma_ids, timeout=slice_t)
            if views is not None:
                return views
            for oid in plasma_ids:
                if self.plasma.contains(oid):
                    continue
                # a reply that raced the ownership check lands inline in the
                # memory store, which this loop cannot see — promote it so
                # the next get_views pass picks it up (no-op otherwise)
                self._promote_to_plasma(oid)
                binary = oid.binary()
                with self._locations_lock:
                    lost = binary in self._lost_objects and binary not in self._pulls_inflight
                if lost and not self._try_recover(oid):
                    raise ObjectLostError(
                        f"object {oid.hex()[:16]} is lost: the node holding it "
                        f"died and no lineage is available to re-create it "
                        f"(ray.put objects and exhausted resubmit budgets are "
                        f"not recoverable)"
                    )
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError(
                    f"timed out waiting for {[o.hex()[:16] for o in plasma_ids]}"
                )

    def _try_recover(self, oid: ObjectID) -> bool:
        """Resubmit the creating task of a lost object. Returns False when no
        lineage exists or the resubmit budget is exhausted."""
        binary = oid.binary()
        with self._pending_lock:
            spec = self._lineage.get(binary)
            if spec is None:
                return False
            task_id = spec["task_id"]
            if task_id in self._pending:
                return True  # resubmit already in flight
            if spec.get("resubmits_left", GlobalConfig.lineage_max_resubmits) <= 0:
                return False
            spec["resubmits_left"] = (
                spec.get("resubmits_left", GlobalConfig.lineage_max_resubmits) - 1
            )
            # the resubmitted attempt keeps the task's own retry budget
            spec["retries_left"] = spec.get(
                "max_retries_initial", GlobalConfig.task_max_retries_default
            )
            spec["attempt"] = spec.get("attempt", 0) + 1
            spec.pop("locations", None)
            spec.pop("_finalized", None)
            spec.pop("_cancelled", None)
            spec.pop("_worker_addr", None)
            self._pending[task_id] = spec
            internal_metrics.inc("ray_tpu_lineage_reconstructions_total")
        with self._locations_lock:
            self._locations.pop(binary, None)
            self._lost_objects.discard(binary)
        logger.warning(
            "recovering lost object %s: resubmitting task %r (%d resubmits left)",
            oid.hex()[:12], spec["name"], spec["resubmits_left"],
        )
        self._emit_event(task_id, "PENDING_ARGS_AVAIL", spec["name"], spec.get("trace"))
        self._submit_queue.put(spec)
        return True

    def _schedule_release(self, oid: ObjectID, view: memoryview, value: Any):
        """Unpin a plasma object once the deserialized value can no longer
        reference its shared-memory buffers."""
        import weakref

        try:
            nbuf = serialization.num_buffers(view)
        except Exception:
            nbuf = 1
        if nbuf == 0:
            # no out-of-band buffers: the value is a full copy
            self._release_plasma(oid.binary())
            return
        try:
            weakref.finalize(value, self._release_plasma, oid.binary())
        except TypeError:
            # not weakref-able (e.g. a dict of arrays): stays pinned for the
            # process lifetime — safe, but unevictable
            pass

    def _release_plasma(self, binary: bytes):
        if self._shutdown.is_set() or self.plasma is None:
            return
        try:
            self.plasma.release(ObjectID(binary))
        except Exception:
            pass

    def _deserialize(self, view: memoryview) -> Any:
        return serialization.deserialize_from(view)

    def _owns(self, oid: ObjectID) -> bool:
        with self._pending_lock:
            return oid.task_id() in self._pending

    def ready(self, oid: ObjectID) -> bool:
        data = self.memory_store.get(oid, timeout=0)
        if data is not None:
            return True
        return self.plasma.contains(oid)

    def wait(
        self,
        object_ids: Sequence[ObjectID],
        num_returns: int,
        timeout: Optional[float],
        fetch_local: bool = True,
    ) -> Tuple[List[ObjectID], List[ObjectID]]:
        deadline = None if timeout is None else time.monotonic() + timeout
        if fetch_local:
            # kick off pulls for known-remote objects so wait() makes progress
            self._start_pulls(object_ids, timeout)
        version = self.memory_store.version
        while True:
            ready = [o for o in object_ids if self.ready(o)]
            if len(ready) >= num_returns:
                ready = ready[:num_returns]
                not_ready = [o for o in object_ids if o not in ready]
                return ready, not_ready
            if deadline is not None and time.monotonic() >= deadline:
                not_ready = [o for o in object_ids if o not in ready]
                return ready, not_ready
            # event-driven: task completions land in the memory store (inline
            # data or plasma markers) and bump its version; the 50 ms cap
            # covers plasma-only arrivals (remote pulls into the local store)
            version = self.memory_store.wait_change(version, 0.05)

    # ------------------------------------------------------------------
    # function export/import (GCS KV is the function table)
    # ------------------------------------------------------------------

    def export_function(self, fn: Any) -> bytes:
        # identity cache: pickling dominates submit cost otherwise. Matches
        # the reference's export-once semantics (function_manager.py): later
        # mutation of a function's globals/closure does not re-export.
        try:
            cached = self._fn_export_ids.get(fn)
        except TypeError:
            cached = None
        if cached is not None:
            return cached
        data = cloudpickle.dumps(fn)
        fn_id = hashlib.sha1(data).digest()
        if fn_id not in self._fn_exported:
            self.gcs.call("kv_put", ("fn", fn_id.hex(), data, True))
            self._fn_exported.add(fn_id)
        self._fn_cache.setdefault(fn_id, fn)
        try:
            self._fn_export_ids[fn] = fn_id
        except TypeError:
            pass  # unweakrefable callable: pickle each time
        return fn_id

    def import_function(self, fn_id: bytes) -> Any:
        fn = self._fn_cache.get(fn_id)
        if fn is None:
            data = self.gcs.call("kv_get", ("fn", fn_id.hex()))
            if data is None:
                raise RayTpuError(f"function {fn_id.hex()[:12]} not found in GCS")
            fn = cloudpickle.loads(data)
            self._fn_cache[fn_id] = fn
        return fn

    # ------------------------------------------------------------------
    # argument marshalling
    # ------------------------------------------------------------------

    _EMPTY_ARGS_PAYLOAD = pickle.dumps(((), {}), protocol=5)

    def _serialize_args(self, args, kwargs) -> Tuple[bytes, List[ObjectID], List[ObjectID]]:
        """Returns (payload, top_level_deps, nested_refs).

        Top-level ObjectRef args are replaced by ("ref", oid) descriptors and
        resolved by the executing worker; nested refs are promoted to plasma.
        """
        if not args and not kwargs:
            # zero-arg calls (pollers, pings, microtask floods) skip the
            # descriptor walk and the ref-collecting pickler entirely
            return self._EMPTY_ARGS_PAYLOAD, [], []
        desc_args = []
        deps: List[ObjectID] = []
        for a in args:
            if isinstance(a, ObjectID):
                desc_args.append(("ref", a))
                deps.append(a)
            else:
                desc_args.append(("val", a))
        desc_kwargs = {}
        for k, v in kwargs.items():
            if isinstance(v, ObjectID):
                desc_kwargs[k] = ("ref", v)
                deps.append(v)
            else:
                desc_kwargs[k] = ("val", v)
        if self.plasma is not None:
            # large value args ride the object plane, not the control RPC
            # (reference: put_arg_in_object_store for args >100KB,
            # _private/ray_option_utils.py) — for jax/numpy values this is
            # also what keeps the device plane zero-copy end to end
            for i, (kind, v) in enumerate(desc_args):
                if kind == "val" and self._est_large(v):
                    oid = self.put(v)
                    desc_args[i] = ("ref", oid)
                    deps.append(oid)
            for k, (kind, v) in list(desc_kwargs.items()):
                if kind == "val" and self._est_large(v):
                    oid = self.put(v)
                    desc_kwargs[k] = ("ref", oid)
                    deps.append(oid)
        payload, nested = _serialize_with_refs((desc_args, desc_kwargs))
        nested = [r for r in nested if r not in deps]
        return payload, deps, nested

    @staticmethod
    def _est_large(v: Any) -> bool:
        """Cheap size probe for the arg-promotion path: covers ndarray-like
        leaves and shallow containers of them without serializing."""
        limit = GlobalConfig.object_store_inline_max_bytes
        nbytes = getattr(v, "nbytes", None)
        if isinstance(nbytes, int):
            return nbytes > limit
        if isinstance(v, (list, tuple)):
            items = v
        elif isinstance(v, dict):
            items = v.values()
        else:
            return sys.getsizeof(v) > limit
        total = 0
        for item in items:
            n = getattr(item, "nbytes", None)
            total += n if isinstance(n, int) else sys.getsizeof(item)
            if total > limit:
                return True
        return False

    def _resolve_deps(self, deps: List[ObjectID], nested: List[ObjectID]):
        """Owner-side dependency resolution: make every dep readable by the
        executing worker. Inline values get promoted to plasma."""
        for oid in list(deps) + list(nested):
            owned = self._owns(oid)  # before the store read (see get())
            data = self.memory_store.get(oid, timeout=0)
            if data is None and owned:
                # still in flight: wait for the reply, then re-read
                data = self.memory_store.get(oid, timeout=None)
            if data is not None and data != PLASMA_MARKER:
                self._promote_to_plasma(oid)
                self.register_locations({oid.binary(): self.raylet.address})
            # refs in plasma (markers, puts, other owners): the executing
            # worker's blocking plasma get provides the wait.

    def _dep_locations(
        self, deps: List[ObjectID], nested: List[ObjectID]
    ) -> Dict[bytes, Tuple[str, int]]:
        """Location hints shipped with the task spec so a worker on another
        node can pull the arguments (the reference resolves these through the
        owner's object directory; here the hints ride the spec)."""
        locs: Dict[bytes, Tuple[str, int]] = {}
        own = tuple(self.raylet.address)
        for oid in list(deps) + list(nested):
            binary = oid.binary()
            known = self._location_of(oid)
            if known is not None:
                locs[binary] = known
            elif self.plasma is not None and self.plasma.contains(oid):
                locs[binary] = own
        return locs

    # ------------------------------------------------------------------
    # normal task submission
    # ------------------------------------------------------------------

    def new_template(self, fields: Dict[str, Any]) -> bytes:
        """Register a wire-spec template (the static fields shared by every
        invocation of one RemoteFunction+options). Content-keyed: the loop
        pattern ``f.options(name=...).remote()`` creates a fresh
        RemoteFunction per call, and each must dedupe onto one template
        instead of growing ``_tmpl_defs`` (and every worker's mirror)
        forever. Returns the template id."""
        try:
            key = tuple(
                (k, v if not isinstance(v, dict) else tuple(sorted(v.items())))
                for k, v in sorted(fields.items(), key=lambda kv: kv[0])
            )
            existing = self._tmpl_by_key.get(key)
            if existing is not None:
                return existing
        except TypeError:
            key = None  # unhashable field (nested runtime_env): no dedupe
        tmpl_id = self.worker_id.binary()[:6] + next(self._tmpl_counter).to_bytes(4, "big")
        self._tmpl_defs[tmpl_id] = dict(fields)
        if key is not None:
            self._tmpl_by_key[key] = tmpl_id
        return tmpl_id

    def build_template(
        self,
        fn: Callable,
        *,
        num_returns: int = 1,
        resources: Optional[Dict[str, float]] = None,
        max_retries: Optional[int] = None,
        name: str = "",
        scheduling_node: Optional[NodeID] = None,
        scheduling_soft: bool = False,
        runtime_env: Optional[Dict[str, Any]] = None,
    ) -> Tuple[bytes, Dict[str, Any]]:
        """Build + register the static spec fields for a remote function."""
        retries = (
            max_retries if max_retries is not None else GlobalConfig.task_max_retries_default
        )
        fields = {
            "job_id": self.job_id,
            "name": name or getattr(fn, "__name__", "task"),
            "fn_id": self.export_function(fn),
            "num_returns": num_returns,
            "resources": resources or {"CPU": 1.0},
            "max_retries_initial": retries,
            "caller_id": self.worker_id,
            "scheduling_node": scheduling_node,
            "scheduling_soft": scheduling_soft,
            "runtime_env": runtime_env,
        }
        # "name" stays OUT of the wire template: per-task display names
        # (``f.options(name=f"work-{i}")``) would otherwise mint a template
        # per call and grow every registry O(N calls); the name rides the
        # per-task diff instead (~15 bytes)
        wire_fields = {k: v for k, v in fields.items() if k != "name"}
        return self.new_template(wire_fields), fields

    def submit_task(
        self,
        fn: Callable,
        args: tuple,
        kwargs: dict,
        *,
        num_returns: int = 1,
        resources: Optional[Dict[str, float]] = None,
        max_retries: Optional[int] = None,
        name: str = "",
        scheduling_node: Optional[NodeID] = None,
        scheduling_soft: bool = False,
        runtime_env: Optional[Dict[str, Any]] = None,
        template: Optional[Tuple[bytes, Dict[str, Any]]] = None,
    ) -> List[ObjectID]:
        submit_t0 = time.perf_counter()
        task_id = self._next_task_id()
        payload, deps, nested = self._serialize_args(args, kwargs)
        # num_returns="dynamic": one top-level return holding an
        # ObjectRefGenerator; the executing worker creates the per-item
        # returns at indices >= 2 (reference: ray_option_utils.py:157-159)
        n_static = 1 if num_returns == "dynamic" else num_returns
        return_ids = [ObjectID.for_task_return(task_id, i + 1) for i in range(n_static)]
        if template is not None:
            tmpl_id, tmpl_fields = template
            spec = dict(tmpl_fields)
            spec["_tmpl"] = tmpl_id
        else:
            # one-off submission (no cached plan): full spec, no template
            retries = (
                max_retries
                if max_retries is not None
                else GlobalConfig.task_max_retries_default
            )
            spec = {
                "job_id": self.job_id,
                "name": name or getattr(fn, "__name__", "task"),
                "fn_id": self.export_function(fn),
                "num_returns": num_returns,
                "resources": resources or {"CPU": 1.0},
                "max_retries_initial": retries,
                "caller_id": self.worker_id,
                "scheduling_node": scheduling_node,
                "scheduling_soft": scheduling_soft,
                "runtime_env": runtime_env,
            }
        spec.update(
            task_id=task_id,
            args=payload,
            deps=deps,
            nested=nested,
            retries_left=spec["max_retries_initial"],
            resubmits_left=GlobalConfig.lineage_max_resubmits,
            attempt=0,
            trace=self._trace_ctx(),
        )
        with self._pending_lock:
            self._pending[task_id] = spec
        self._record_child(spec, task_id)
        for r in return_ids:
            self._register_ref(r)
        self._emit_event(task_id, "PENDING_ARGS_AVAIL", spec["name"], spec.get("trace"))
        # Fast path: a dependency-free task with an idle cached lease pushes
        # straight from the calling thread (call_async never blocks) —
        # skipping the submit-queue hop saves two context switches per
        # task, which dominates round-trip latency on small hosts
        # (reference analogue: OnWorkerIdle running submissions inline,
        # direct_task_transport.cc:191).
        if not deps and not nested and scheduling_node is None:
            sig = self._lease_sig(spec)
            if sig is not None:
                lease_entry = None
                with self._lease_lock:
                    stack = self._idle_leases.get(sig)
                    if stack and not self._lease_waiting.get(sig):
                        lease_entry = stack.pop()
                if lease_entry is not None:
                    lease, lease_raylet, client, _ts = lease_entry
                    spec["locations"] = {}
                    with self._lease_lock:
                        lease["_out"] = lease.get("_out", 0) + 1
                    self._push_batch([spec], sig, lease, lease_raylet, client)
                    internal_metrics.inc("ray_tpu_tasks_submitted_total")
                    internal_metrics.observe(
                        "ray_tpu_task_submit_latency_seconds",
                        time.perf_counter() - submit_t0,
                    )
                    return return_ids
        self._submit_queue.put(spec)
        internal_metrics.inc("ray_tpu_tasks_submitted_total")
        internal_metrics.observe(
            "ray_tpu_task_submit_latency_seconds", time.perf_counter() - submit_t0
        )
        return return_ids

    # -- lease caching / scheduling keys --------------------------------

    def _lease_sig(self, spec: Dict[str, Any]) -> Optional[Tuple]:
        if spec.get("scheduling_node") is not None:
            return None  # affinity-constrained: never reuse generic leases
        from ray_tpu._private.runtime_env_packaging import runtime_env_key

        env = spec.get("runtime_env") or {}
        env_sig = runtime_env_key(env)
        return (tuple(sorted((spec.get("resources") or {}).items())), env_sig)

    def _maybe_push_from_cache(self, sig: Tuple):
        """Marry waiting specs with idle cached leases (no raylet RPC)."""
        while True:
            with self._lease_lock:
                stack = self._idle_leases.get(sig)
                waiting = self._lease_waiting.get(sig)
                if not stack or not waiting:
                    return
                lease, lease_raylet, client, _ts = stack.pop()
            self._on_worker_idle(sig, lease, lease_raylet, client)

    def _pop_waiting_batch_locked(self, sig: Tuple) -> List[Dict[str, Any]]:
        """Pop a fair share of the waiting backlog (lease lock held). Backlog
        beyond one task rides a single batched push — the 1-frame-per-task
        round trip is what capped async throughput at 0.16x baseline
        (reference analogue: backlog-driven pipelined grants,
        direct_task_transport.cc:346). The share divides the backlog by the
        number of workers currently running pushes so one idle worker never
        swallows work that other (about-to-be-idle) workers should get —
        batching must not serialize long tasks onto one process."""
        waiting = self._lease_waiting.get(sig)
        # every source that can absorb queued work counts against this
        # batch's share: workers mid-push, lease RPCs in flight (incl.
        # spillback grants on OTHER nodes), and cached idle leases — a
        # batch that swallowed the whole queue would serialize work the
        # cluster could run in parallel (and defeat spillback balancing)
        slots = (
            self._active_pushes.get(sig, 0)
            + self._lease_inflight.get(sig, 0)
            + len(self._idle_leases.get(sig) or ())
        )
        cap = min(
            GlobalConfig.task_push_batch,
            max(1, len(waiting) // (slots + 1)),
        )
        out = [waiting.popleft()]
        # only dependency-free tasks ride shared batches: a task with deps
        # executes strictly behind its batchmates on one worker thread, so
        # any wait on a not-yet-satisfied ref inside the batch would wedge
        # the whole batch (ADVICE r4) — dep-carrying specs push alone
        if out[0].get("deps") or out[0].get("nested"):
            return out
        while waiting and len(out) < cap:
            head = waiting[0]
            if head.get("deps") or head.get("nested"):
                break
            out.append(waiting.popleft())
        return out

    def _ensure_lease_requests(self, sig: Tuple):
        """Keep enough lease requests in flight to cover the waiting queue
        (minus idle leases), capped; the raylet queues excess requests."""
        with self._lease_lock:
            waiting = len(self._lease_waiting.get(sig) or ())
            idle = len(self._idle_leases.get(sig) or ())
            inflight = self._lease_inflight.get(sig, 0)
            # an in-flight request guarantees exactly ONE worker — its
            # grant-ahead extras are opportunistic (only already-idle
            # workers), so discount inflight at face value and divide only
            # the REMAINING deficit by the window. Discounting the full
            # window per request starves the raylet's parked-request queue,
            # which is the autoscaler's demand signal (and spillback's
            # chance to parallelize a saturated shape).
            window = max(1, int(GlobalConfig.lease_grant_window))
            deficit = waiting - idle - inflight
            need = min(-(-deficit // window), 32 - inflight)
            if need <= 0:
                return
            self._lease_inflight[sig] = inflight + need
        for _ in range(need):
            self._submit_queue.put({"__action__": "lease", "sig": sig})

    def _acquire_lease(self, sig: Tuple):
        """Run the lease dance for one worker of shape ``sig`` (submitter
        thread), then hand it to a waiting spec."""
        res_sig, env_sig = sig
        resources = dict(res_sig)
        lease_raylet = self.raylet
        hops = 0
        try:
            while not self._shutdown.is_set():
                with self._lease_lock:
                    waiting = self._lease_waiting.get(sig)
                    if not waiting:
                        return  # queue drained (cached leases served it)
                    # every spec with this sig carries an equivalent env;
                    # reading it here (not from a side map) can't race with
                    # any cache eviction
                    runtime_env = waiting[0].get("runtime_env") or None
                    # grant-ahead window: one round-trip may bring back up
                    # to lease_grant_window already-idle workers when the
                    # backlog warrants more than one
                    count = min(
                        max(1, int(GlobalConfig.lease_grant_window)),
                        max(1, len(waiting) // max(1, GlobalConfig.task_push_batch)),
                    )
                try:
                    # short raylet-side wait: a request whose demand has
                    # since drained must not pin a submitter thread (nor
                    # block raylet grants) for the full lease timeout
                    lease = lease_raylet.call(
                        "request_worker_lease",
                        {
                            "resources": resources,
                            "job_id": self.job_id,
                            "runtime_env": runtime_env,
                            "allow_spill": hops == 0,
                            "timeout": 1.0,
                            "count": count,
                        },
                        timeout=GlobalConfig.worker_lease_timeout_s * 2,
                    )
                except (ConnectionLost, TimeoutError, OSError):
                    if lease_raylet is self.raylet:
                        raise  # our own raylet is gone
                    self._node_addr_cache.clear()
                    lease_raylet, hops = self.raylet, 0
                    continue
                if lease is None:
                    lease_raylet, hops = self.raylet, 0
                    continue
                if "retry_at" in lease:
                    lease_raylet = self._get_raylet_client(tuple(lease["retry_at"]))
                    hops += 1
                    continue
                extra = lease.pop("extra", None) or ()
                try:
                    client = self._get_worker_client(tuple(lease["address"]))
                except (ConnectionLost, OSError):
                    self._return_lease(lease, lease_raylet)
                    client = None
                if client is not None:
                    self._on_worker_idle(
                        sig, lease, lease_raylet, client, stash_ok=False
                    )
                # grant-ahead extras: feed the backlog, park surplus in the
                # idle-lease cache (stash_ok) or return it to the raylet
                for g in extra:
                    try:
                        c = self._get_worker_client(tuple(g["address"]))
                    except (ConnectionLost, OSError):
                        self._return_lease(g, lease_raylet)
                        continue
                    self._on_worker_idle(sig, g, lease_raylet, c, stash_ok=True)
                if client is None:
                    continue
                return
        except Exception as e:  # noqa: BLE001 - fail one waiting spec
            with self._lease_lock:
                waiting = self._lease_waiting.get(sig)
                spec = waiting.popleft() if waiting else None
            if spec is not None:
                self._fail_task(spec, e)
        finally:
            with self._lease_lock:
                self._lease_inflight[sig] = max(
                    0, self._lease_inflight.get(sig, 1) - 1
                )
            self._ensure_lease_requests(sig)

    def _on_worker_idle(self, sig, lease, lease_raylet, client, stash_ok=True):
        """A leased worker can take work: feed it from the backlog, keeping
        up to TWO batches in flight per lease. Double-buffering matters on a
        small host: with one batch in flight the worker idles for the whole
        time this owner pickles and sends the next batch (~40% of wall time
        measured at batch 25-64); with two, encode of batch N+1 overlaps
        execution of batch N. With no backlog the lease is cached briefly
        (``stash_ok``) or returned to the raylet."""
        while True:
            with self._lease_lock:
                if lease.get("_dead"):
                    break
                out = lease.get("_out", 0)
                waiting = self._lease_waiting.get(sig)
                if out >= 2 or not waiting:
                    if out > 0:
                        return  # in-flight batch will re-enter on completion
                    if stash_ok:
                        stack = self._idle_leases.setdefault(sig, [])
                        if len(stack) < 16:
                            stack.append(
                                (lease, lease_raylet, client, time.monotonic())
                            )
                            return
                    break  # retire outside the lock
                specs = self._pop_waiting_batch_locked(sig)
                lease["_out"] = out + 1
            self._push_batch(specs, sig, lease, lease_raylet, client)
        self._maybe_retire_lease(lease, lease_raylet)

    def _maybe_retire_lease(self, lease, lease_raylet):
        """Return a lease to its raylet exactly once, and only when no push
        is still in flight on it (two streamed batches can fail
        concurrently; both completions funnel here)."""
        with self._lease_lock:
            if lease.get("_out", 0) > 0 or lease.get("_returned"):
                return
            lease["_returned"] = True
        self._return_lease(lease, lease_raylet)

    def _push_active_inc(self, sig):
        if sig is not None:
            with self._lease_lock:
                self._active_pushes[sig] = self._active_pushes.get(sig, 0) + 1

    def _push_active_dec(self, sig):
        if sig is not None:
            with self._lease_lock:
                n = self._active_pushes.get(sig, 1) - 1
                if n > 0:
                    self._active_pushes[sig] = n
                else:
                    self._active_pushes.pop(sig, None)

    def _wire_task(self, client, spec, tmpl_out: Dict[bytes, Dict[str, Any]]):
        """Encode one spec for the wire: ``(tmpl_id, varying-fields)`` when
        the spec came from a registered template (the template definition
        itself is attached the first time this connection sees it), else
        ``(None, full-spec)``."""
        tid = spec.get("_tmpl")
        if tid is None:
            return (None, spec)
        tmpl = self._tmpl_defs.get(tid)
        if tmpl is None:
            # template evicted (actor died) while this spec was in flight:
            # ship the full spec instead
            full = dict(spec)
            full.pop("_tmpl", None)
            return (None, full)
        sent = client.__dict__.setdefault("_sent_tmpls", set())
        if tid not in sent:
            tmpl_out[tid] = tmpl
            sent.add(tid)
        diff = {"task_id": spec["task_id"], "args": spec["args"]}
        # these ride the diff only when the template doesn't pin them
        # (normal tasks decrement retries across pushes and carry per-task
        # names; actor templates pin retries_left=0/name and ship seq_no)
        for k in ("retries_left", "resubmits_left", "seq_no", "name", "attempt"):
            if k in spec and k not in tmpl:
                diff[k] = spec[k]
        for k in ("deps", "nested", "locations", "trace"):
            v = spec.get(k)
            if v:
                diff[k] = v
        return (tid, diff)

    def _on_worker_notify(self, method: str, payload):
        """Streamed per-task replies from a batch push. Runs INLINE on the
        rpc poller thread so every streamed item is fully handled before
        the batch's terminal response callback can fire; must not block."""
        if method != "batch_item":
            return
        bid, idx, reply = payload
        with self._batches_lock:
            entry = self._batches.get(bid)
            if entry is None or entry["acked"][idx]:
                return
            entry["acked"][idx] = 1
            spec = entry["specs"][idx]
        try:
            if isinstance(reply, BaseException):
                self._fail_task(spec, reply)
            else:
                self._handle_reply(spec, reply)
        except Exception:
            logger.exception("streamed batch reply handling failed")

    def _push_batch(self, specs, sig, lease, lease_raylet, client, cacheable=True):
        """Push a batch (possibly of one) to a leased worker in one frame.

        The worker streams each task's reply as an inline NOTIFY the moment
        the task completes — dependents unblock without waiting for
        batchmates, and completed work is acked immediately so a later
        worker death never burns its retries or loses its results (ADVICE
        r4 medium) — then sends a terminal response. On worker death only
        the UNACKED members retry. Callers must have incremented
        ``lease["_out"]`` (or own the lease exclusively, affinity path)."""
        self._push_active_inc(sig)
        bid = next(self._batch_ids)
        entry = {"specs": specs, "acked": bytearray(len(specs))}
        with self._batches_lock:
            self._batches[bid] = entry

        def on_done(kind, reply, specs=specs):
            with self._batches_lock:
                self._batches.pop(bid, None)
            acked = entry["acked"]
            self._push_active_dec(sig)
            lost = kind != rpc_mod.RESPONSE and isinstance(
                reply, (ConnectionLost, OSError)
            )
            with self._lease_lock:
                lease["_out"] = max(0, lease.get("_out", 1) - 1)
                if lost:
                    lease["_dead"] = True
            if kind == rpc_mod.RESPONSE:
                if cacheable:
                    self._on_worker_idle(sig, lease, lease_raylet, client)
                else:
                    self._maybe_retire_lease(lease, lease_raylet)
                replies = reply.get("replies") or ()
                for i, spec in enumerate(specs):
                    if acked[i]:
                        continue
                    r = replies[i] if i < len(replies) else None
                    if r is None:
                        self._fail_task(
                            spec, RpcError(f"batch item {i} reply lost")
                        )
                    elif isinstance(r, BaseException):
                        self._fail_task(spec, r)
                    else:
                        self._handle_reply(spec, r)
            elif lost:
                self._maybe_retire_lease(lease, lease_raylet)
                # worker died mid-batch: owner-side retry of the unacked
                # members only (task_manager.h:277)
                for i, spec in enumerate(specs):
                    if acked[i]:
                        continue
                    if spec.get("_cancelled"):
                        continue  # ref already resolved cancelled; no retry
                    if spec["retries_left"] > 0:
                        spec["retries_left"] -= 1
                        spec["attempt"] = spec.get("attempt", 0) + 1
                        logger.warning(
                            "task %s lost worker, retrying (%d left)",
                            spec["name"],
                            spec["retries_left"],
                        )
                        self._submit_queue.put(spec)
                    else:
                        self._fail_task(
                            spec,
                            WorkerCrashedError(
                                f"worker died running {spec['name']}: {reply}"
                            ),
                        )
            else:
                if cacheable:
                    self._on_worker_idle(sig, lease, lease_raylet, client)
                else:
                    self._maybe_retire_lease(lease, lease_raylet)
                for i, spec in enumerate(specs):
                    if not acked[i]:
                        self._fail_task(spec, reply)

        # record the push target so a later cancel() can reach the
        # executing worker directly (no GCS lookup on the common path)
        for s in specs:
            s["_worker_addr"] = tuple(client.address)
        # encode + send under the client's template lock: the frame carrying
        # a template definition must hit the socket before any frame that
        # references it without one
        with client._tmpl_lock:
            tmpls: Dict[bytes, Dict[str, Any]] = {}
            tasks = [self._wire_task(client, s, tmpls) for s in specs]
            client.call_async(
                "push_task_batch",
                {"bid": bid, "tmpls": tmpls or None, "tasks": tasks},
                on_done, timeout=TASK_REPLY_TIMEOUT,
            )

    def _sweep_idle_leases(self, max_age: float = 1.0):
        """Return leases that sat unused past max_age (runs on the event
        loop tick); prevents hoarding when the queue drains elsewhere."""
        to_return = []
        now = time.monotonic()
        with self._lease_lock:
            for sig, stack in self._idle_leases.items():
                keep = []
                for item in stack:
                    (keep if now - item[3] <= max_age else to_return).append(item)
                self._idle_leases[sig] = keep
        for lease, lease_raylet, _client, _ts in to_return:
            self._return_lease(lease, lease_raylet)

    def _submit_loop(self):
        while not self._shutdown.is_set():
            try:
                spec = self._submit_queue.get(timeout=5.0)
            except queue.Empty:
                continue
            if spec is None:
                return
            try:
                if spec.get("__action__") == "drain_actor":
                    self._drain_actor(spec["actor_id"])
                elif spec.get("__action__") == "lease":
                    self._acquire_lease(spec["sig"])
                elif self._park_until_deps(spec):
                    pass  # back in this queue when the argument lands
                elif spec.get("actor_id") is not None and spec.get("method") is not None:
                    if spec.get("ordered", True):
                        self._enqueue_actor_task(spec)
                    else:
                        self._send_actor_task(spec)
                else:
                    self._submit_one(spec)
            except Exception as e:  # noqa: BLE001
                self._fail_task(spec.get("spec", spec), e)

    def _park_until_deps(self, spec: Dict[str, Any]) -> bool:
        """True if ``spec`` takes a result this process still has in flight:
        the spec then re-enters the queue when that result lands, instead of
        a submitter waiting for it in ``_resolve_deps``. The pool is small
        and the lease actions share its queue: a burst of calls that each
        take the one before (``f.remote(f.remote(...))``) could park every
        submitter behind the lease action of the first, for good."""
        if spec.get("_cancelled"):
            return False  # its ref is resolved; the usual path drops it
        for oid in (*(spec.get("deps") or ()), *(spec.get("nested") or ())):
            # ownership before the store read, as in _resolve_deps
            if self._owns(oid) and not self.memory_store.contains(oid):
                self.memory_store.add_waiter(
                    oid, lambda: self._submit_queue.put(spec)
                )
                return True
        return False

    def _submit_one(self, spec: Dict[str, Any]):
        """Lease a worker and push the task asynchronously. The submitter
        thread is released as soon as the push is on the wire; completion
        (reply handling, lease return, retries) runs on the rpc callback
        executor, so in-flight task count is bounded by leases, not by the
        submitter pool size."""
        if spec.get("_cancelled"):
            return  # cancelled while queued: ref already resolved
        self._resolve_deps(spec["deps"], spec["nested"])
        spec["locations"] = self._dep_locations(spec["deps"], spec["nested"])
        sig = self._lease_sig(spec)
        if sig is not None:
            # scheduling-key path (reference: direct_task_transport.cc —
            # tasks queue per resource shape; granted/idle leased workers
            # pop from the queue and run tasks back to back)
            import collections

            with self._lease_lock:
                self._lease_waiting.setdefault(sig, collections.deque()).append(spec)
            self._maybe_push_from_cache(sig)
            self._ensure_lease_requests(sig)
            return
        lease_raylet = self.raylet
        hops = 0
        if spec.get("scheduling_node") is not None:
            # NodeAffinity: lease directly from the target node's raylet
            addr = self._node_address(spec["scheduling_node"])
            if addr is not None:
                lease_raylet, hops = self._get_raylet_client(addr), 1
            elif not spec.get("scheduling_soft"):
                raise RayTpuError(
                    f"node {spec['scheduling_node'].hex()[:8]} is not alive "
                    f"(NodeAffinity hard)"
                )
        while not self._shutdown.is_set():
            try:
                lease = lease_raylet.call(
                    "request_worker_lease",
                    {
                        "resources": spec["resources"],
                        "job_id": spec["job_id"],
                        "runtime_env": spec.get("runtime_env"),
                        # a redirected request must not bounce again (avoids
                        # spillback ping-pong between two saturated nodes)
                        "allow_spill": hops == 0,
                    },
                    timeout=GlobalConfig.worker_lease_timeout_s * 2,
                )
            except (ConnectionLost, TimeoutError, OSError) as e:
                if lease_raylet is self.raylet:
                    raise  # our own raylet is gone: nothing to fall back to
                self._node_addr_cache.clear()  # the peer died; addresses stale
                if spec.get("scheduling_node") is not None and not spec.get(
                    "scheduling_soft"
                ):
                    raise RayTpuError(
                        f"node {spec['scheduling_node'].hex()[:8]} died "
                        f"(NodeAffinity hard): {e}"
                    ) from e
                lease_raylet, hops = self.raylet, 0
                continue
            if lease is None:
                if spec.get("scheduling_node") is not None and not spec.get(
                    "scheduling_soft"
                ):
                    continue  # hard affinity: keep waiting on the target node
                lease_raylet, hops = self.raylet, 0  # restart from our node
                continue
            if "retry_at" in lease:
                lease_raylet = self._get_raylet_client(tuple(lease["retry_at"]))
                hops += 1
                continue
            try:
                client = self._get_worker_client(tuple(lease["address"]))
            except (ConnectionLost, OSError):
                self._return_lease(lease, lease_raylet)
                continue

            self._push_with_lease(spec, sig, lease, lease_raylet, client)
            return

    def _push_with_lease(self, spec, sig, lease, lease_raylet, client):
        """Affinity-path push (sig is None): one lease per task, returned on
        completion — constrained leases are never cached."""
        lease["_out"] = 1  # fresh lease owned exclusively by this push
        self._push_batch([spec], sig, lease, lease_raylet, client, cacheable=False)

    def _return_lease(self, lease, lease_raylet=None):
        try:
            (lease_raylet or self.raylet).call(
                "return_worker", {"worker_id": lease["worker_id"]}
            )
        except Exception:
            pass

    def _node_address(self, node_id: NodeID) -> Optional[Tuple[str, int]]:
        cached = self._node_addr_cache.get(node_id)
        if cached is not None:
            return cached
        try:
            for n in self.gcs.call("get_nodes", timeout=10.0):
                if n["alive"]:
                    self._node_addr_cache[n["node_id"]] = tuple(n["address"])
        except Exception:
            pass
        return self._node_addr_cache.get(node_id)

    def _get_raylet_client(self, addr: Tuple[str, int]) -> RpcClient:
        if tuple(addr) == tuple(self.raylet.address):
            return self.raylet
        with self._worker_clients_lock:
            client = self._raylet_clients.get(tuple(addr))
            if client is not None and not client.closed:
                return client
            client = RpcClient(tuple(addr), prefer_local=True)
            self._raylet_clients[tuple(addr)] = client
            return client

    def _get_worker_client(self, addr: Tuple[str, int]) -> RpcClient:
        with self._worker_clients_lock:
            client = self._worker_clients.get(addr)
            if client is not None and not client.closed:
                return client
            # inline notify: streamed batch-item replies must be handled in
            # frame order ahead of their batch's terminal response
            client = RpcClient(
                addr,
                on_notify=self._on_worker_notify,
                inline_notify=True,
                prefer_local=True,
            )
            # serializes mark-template-sent with the frame write so a racing
            # push can never reference a template whose defining frame lost
            # the socket-write race
            client._tmpl_lock = threading.Lock()
            client.chaos_identity = self._chaos_node_identity
            self._worker_clients[addr] = client
            return client

    def _handle_reply(self, spec: Dict[str, Any], reply: Dict[str, Any]):
        task_id = spec["task_id"]
        if spec.get("_cancelled"):
            # the ref already resolved to TaskCancelledError owner-side; a
            # late worker reply must not overwrite it (or re-pin lineage)
            with self._pending_lock:
                self._pending.pop(task_id, None)
            self._prune_child(spec)
            return
        if reply["status"] == "retry":  # application asked for retry (unused yet)
            raise RayTpuError("unexpected retry status")
        producer_node = reply.get("node")
        self.register_locations(reply.get("ref_locations") or {})
        for oid, kind, data in reply["results"]:
            with self._local_refs_lock:
                wanted = oid.binary() in self._local_refs
            if not wanted:
                continue  # every local ref was dropped before completion
            if kind == "inline":
                self.memory_store.put(oid, data)
            else:
                if producer_node is not None:
                    self.register_locations({oid.binary(): tuple(producer_node)})
                self.memory_store.put(oid, PLASMA_MARKER)
                if reply["status"] == "ok" and spec.get("max_retries_initial", 0) > 0:
                    # pin lineage: this spec can recreate the object if the
                    # node holding it dies (object_recovery_manager.h:90).
                    # max_retries=0 declares the task non-idempotent, which
                    # makes its objects non-reconstructable (reference
                    # semantics: task_manager.h retryable check)
                    with self._pending_lock:
                        self._lineage[oid.binary()] = spec
            with self._locations_lock:
                self._lost_objects.discard(oid.binary())
        if (
            spec.get("num_returns") == "dynamic"
            and reply["status"] == "ok"
            and spec.get("max_retries_initial", 0) > 0
        ):
            # dynamic items (indices >= 2) arrive only as location hints;
            # pin the creating spec so they reconstruct on node loss too.
            # The pins release with the generator's top-level ref
            # (_on_ref_deleted) instead of leaking for the process lifetime.
            # Fire-and-forget guard: if the caller already dropped the
            # top-level ref, pinning now would never be released.
            tid_bin = task_id.binary()
            top_bin = ObjectID.for_task_return(task_id, 1).binary()
            with self._local_refs_lock:
                top_held = self._local_refs.get(top_bin, 0) > 0
            if top_held:
                with self._pending_lock:
                    children = self._dynamic_children.setdefault(top_bin, [])
                    for oid_bin in reply.get("ref_locations") or {}:
                        if oid_bin.startswith(tid_bin):
                            self._lineage[oid_bin] = spec
                            children.append(oid_bin)
                # close the drop-during-pin race: if the top ref died while
                # we pinned, its finalizer saw an empty children list
                with self._local_refs_lock:
                    still_held = self._local_refs.get(top_bin, 0) > 0
                if not still_held:
                    with self._pending_lock:
                        for child in self._dynamic_children.pop(top_bin, ()):
                            self._lineage.pop(child, None)
        with self._pending_lock:
            self._pending.pop(task_id, None)
        self._prune_child(spec)
        internal_metrics.inc(
            "ray_tpu_tasks_finished_total"
            if reply["status"] == "ok"
            else "ray_tpu_tasks_failed_total"
        )
        self._emit_event(task_id, "FINISHED" if reply["status"] == "ok" else "FAILED", spec["name"], spec.get("trace"))

    def _fail_task(self, spec: Dict[str, Any], exc: BaseException):
        # finalize-once: a cancelled task can see a second failure (its
        # push erroring after the owner already resolved the ref) — the
        # first resolution wins. _try_recover clears the flag on resubmit.
        if spec.get("_finalized"):
            return
        spec["_finalized"] = True
        task_id = spec["task_id"]
        err = serialization.serialize(
            exc if isinstance(exc, RayTpuError) else TaskError(exc, spec["name"]),
            is_exception=True,
        ).to_bytes()
        n = spec["num_returns"]
        for i in range(1 if n == "dynamic" else n):
            self.memory_store.put(ObjectID.for_task_return(task_id, i + 1), err)
        with self._pending_lock:
            self._pending.pop(task_id, None)
        self._prune_child(spec)
        cancelled = isinstance(exc, TaskCancelledError)
        if not cancelled:
            internal_metrics.inc("ray_tpu_tasks_failed_total")
        self._emit_event(
            task_id,
            "CANCELLED" if cancelled else "FAILED",
            spec["name"],
            spec.get("trace"),
        )

    # ------------------------------------------------------------------
    # actor submission
    # ------------------------------------------------------------------

    def create_actor(
        self,
        cls: type,
        args: tuple,
        kwargs: dict,
        options: Dict[str, Any],
    ) -> ActorID:
        actor_id = ActorID.of(self.job_id)
        class_id = self.export_function(cls)
        payload, deps, nested = self._serialize_args(args, kwargs)
        self._resolve_deps(deps, nested)
        spec = {
            "actor_id": actor_id,
            "job_id": self.job_id,
            "class_id": class_id,
            "class_name": getattr(cls, "__name__", "Actor"),
            "args": payload,
            "deps": deps,
            "locations": self._dep_locations(deps, nested),
            "options": options,
        }
        self.gcs.call("register_actor", (actor_id, spec))
        with self._actor_lock:
            self._actor_info[actor_id] = {"address": None, "state": "PENDING"}
            self._actor_seq[actor_id] = 0
        return actor_id

    def _resolve_actor(self, actor_id: ActorID, timeout: Optional[float] = None) -> Tuple[str, int]:
        with self._actor_lock:
            info = self._actor_info.get(actor_id)
            if info and info.get("address") and info.get("state") == "ALIVE":
                return info["address"]
        view = self.gcs.call(
            "wait_for_actor", (actor_id, timeout or GlobalConfig.worker_lease_timeout_s * 4)
        )
        if view is None:
            raise GetTimeoutError(f"actor {actor_id.hex()[:8]} not ready")
        if view["state"] == "DEAD":
            raise ActorDiedError(
                f"actor {actor_id.hex()[:8]} is dead: {view.get('death_cause')}"
            )
        with self._actor_lock:
            self._actor_info[actor_id] = {"address": tuple(view["address"]), "state": "ALIVE"}
        return tuple(view["address"])

    def submit_actor_task(
        self,
        actor_id: ActorID,
        method_name: str,
        args: tuple,
        kwargs: dict,
        *,
        num_returns: int = 1,
        ordered: bool = True,
    ) -> List[ObjectID]:
        task_id = self._next_task_id(actor_id)
        payload, deps, nested = self._serialize_args(args, kwargs)
        if ordered:
            with self._actor_lock:
                seq = self._actor_seq.get(actor_id, 0)
                self._actor_seq[actor_id] = seq + 1
        else:
            # unordered calls are out-of-band: they must not consume a seq
            # from the ordered stream, or _pump_actor waits forever for a
            # seq that will never enter its heap
            seq = -1
        # "dynamic" has one static return: the ObjectRefGenerator (same
        # contract as normal tasks — reference: _raylet.pyx generators)
        n_static = 1 if num_returns == "dynamic" else num_returns
        return_ids = [ObjectID.for_task_return(task_id, i + 1) for i in range(n_static)]
        tkey = (actor_id, method_name, num_returns, ordered)
        entry = self._actor_tmpl_cache.get(tkey)
        if entry is None:
            fields = {
                "job_id": self.job_id,
                "actor_id": actor_id,
                "method": method_name,
                "name": method_name,
                "num_returns": num_returns,
                "ordered": ordered,
                "caller_id": self.worker_id,
                "retries_left": 0,
            }
            entry = (self.new_template(fields), fields)
            self._actor_tmpl_cache[tkey] = entry
        tmpl_id, fields = entry
        spec = dict(fields)
        spec.update(
            _tmpl=tmpl_id,
            task_id=task_id,
            args=payload,
            deps=deps,
            nested=nested,
            seq_no=seq,
            trace=self._trace_ctx(),
        )
        with self._pending_lock:
            self._pending[task_id] = spec
        self._record_child(spec, task_id)
        for r in return_ids:
            self._register_ref(r)
        self._submit_queue.put(spec)
        return return_ids

    def _enqueue_actor_task(self, spec: Dict[str, Any]):
        import heapq

        actor_id = spec["actor_id"]
        with self._actor_lock:
            heapq.heappush(
                self._actor_pending.setdefault(actor_id, []), (spec["seq_no"], id(spec), spec)
            )
        self._pump_actor(actor_id)

    def _pump_actor(self, actor_id: ActorID):
        """Move every in-order queued call up to the in-flight window into
        the actor's outbox and ensure one drainer is running (pipelining:
        the reference keeps many calls in flight per handle and the
        worker-side queue orders execution —
        direct_actor_task_submitter.cc). May run on a submitter thread or
        the rpc callback executor; the outbox append happens under the
        actor lock so outbox order always equals seq order."""
        import collections
        import heapq

        start_drain = False
        with self._actor_lock:
            heap = self._actor_pending.get(actor_id) or []
            nxt = self._actor_next_send.get(actor_id, 0)
            inflight = self._actor_inflight.get(actor_id, 0)
            cap = GlobalConfig.actor_max_inflight
            outbox = self._actor_outbox.setdefault(actor_id, collections.deque())
            while heap and heap[0][0] == nxt and inflight < cap:
                _, _, spec = heapq.heappop(heap)
                outbox.append(spec)
                nxt += 1
                inflight += 1
            self._actor_next_send[actor_id] = nxt
            self._actor_inflight[actor_id] = inflight
            if outbox and not self._actor_draining.get(actor_id):
                self._actor_draining[actor_id] = True
                start_drain = True
        if start_drain:
            # hop to a submitter thread: address resolution can block
            self._submit_queue.put({"__action__": "drain_actor", "actor_id": actor_id})

    def _drain_actor(self, actor_id: ActorID):
        """Send the actor's outbox in order. Exactly one drainer runs per
        actor at a time (the _actor_draining flag), so pushes hit the
        actor's connection in seq order with no cross-thread coordination;
        only this actor's pipeline stalls if resolution blocks."""
        while not self._shutdown.is_set():
            with self._actor_lock:
                outbox = self._actor_outbox.get(actor_id)
                if not outbox:
                    self._actor_draining[actor_id] = False
                    return
                spec = outbox.popleft()
            self._send_actor_task(spec)
        with self._actor_lock:
            self._actor_draining[actor_id] = False

    def _actor_task_done(self, spec: Dict[str, Any]):
        if not spec.get("ordered", True):
            return
        actor_id = spec["actor_id"]
        with self._actor_lock:
            self._actor_inflight[actor_id] = max(
                0, self._actor_inflight.get(actor_id, 1) - 1
            )
        self._pump_actor(actor_id)

    def _send_actor_task(self, spec: Dict[str, Any]):
        """Resolve the actor address (blocking, on the actor's single
        drainer for ordered calls) and push asynchronously; completion runs
        on the callback executor. Any unexpected failure must still release
        the in-flight window, or the actor wedges."""
        if spec.get("_cancelled"):
            # purged queued actor call: skip the wire send but keep the
            # seq/window accounting intact (removing it from the seq heap
            # instead would stall _pump_actor forever on the missing seq)
            self._actor_task_done(spec)
            return
        try:
            self._send_actor_task_inner(spec)
        except Exception as e:  # noqa: BLE001
            self._fail_task(spec, e)
            self._actor_task_done(spec)

    def _send_actor_task_inner(self, spec: Dict[str, Any]):
        self._resolve_deps(spec["deps"], spec["nested"])
        spec["locations"] = self._dep_locations(spec["deps"], spec["nested"])
        actor_id = spec["actor_id"]
        attempts = 0
        while not self._shutdown.is_set():
            attempts += 1
            try:
                addr = self._resolve_actor(actor_id)
            except ActorDiedError as e:
                self._fail_task(spec, e)
                self._actor_task_done(spec)
                return
            except GetTimeoutError as e:
                self._fail_task(spec, e)
                self._actor_task_done(spec)
                return
            try:
                client = self._get_worker_client(addr)
                spec["_worker_addr"] = tuple(addr)
            except (ConnectionLost, OSError):
                # couldn't even connect: address stale (restart in flight)
                with self._actor_lock:
                    self._actor_info.pop(actor_id, None)
                if attempts > 50:
                    self._fail_task(
                        spec, ActorDiedError(f"actor {actor_id.hex()[:8]} unreachable")
                    )
                    self._actor_task_done(spec)
                    return
                time.sleep(0.1)
                continue

            def on_done(kind, payload, spec=spec, actor_id=actor_id):
                if kind == rpc_mod.RESPONSE:
                    self._handle_reply(spec, payload)
                elif isinstance(payload, (ConnectionLost, OSError)):
                    # The call may have executed before the worker died, so
                    # the default is at-most-once: fail rather than resend
                    # (the reference's actor tasks also fail here unless
                    # max_task_retries is set).
                    with self._actor_lock:
                        self._actor_info.pop(actor_id, None)
                    self._fail_task(
                        spec,
                        ActorDiedError(
                            f"actor {actor_id.hex()[:8]} died while running "
                            f"{spec['name']}: {payload}"
                        ),
                    )
                else:
                    self._fail_task(spec, payload)
                self._actor_task_done(spec)

            if spec.get("_tmpl") is not None:
                with client._tmpl_lock:
                    tmpls: Dict[bytes, Dict[str, Any]] = {}
                    wire = self._wire_task(client, spec, tmpls)
                    client.call_async(
                        "push_task", {"t": wire, "tmpls": tmpls or None}, on_done,
                        timeout=TASK_REPLY_TIMEOUT,
                    )
            else:
                client.call_async("push_task", spec, on_done, timeout=TASK_REPLY_TIMEOUT)
            return

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        self.gcs.call("kill_actor", (actor_id, no_restart))

    # ------------------------------------------------------------------
    # cancellation
    # ------------------------------------------------------------------

    def cancel(self, object_ref: ObjectID, *, force: bool = False,
               recursive: bool = True) -> bool:
        """Cancel the task that produces ``object_ref``. Pending tasks are
        dequeued before lease grant; running tasks get their cooperative
        cancel flag set on the executing worker (``force=True`` escalates to
        a thread interrupt); the ref resolves to TaskCancelledError. Returns
        True when this owner still had the task pending."""
        return self.cancel_task_id(
            object_ref.task_id(), force=force, recursive=recursive
        )

    def cancel_task_id(self, task_id: TaskID, *, force: bool = False,
                       recursive: bool = True) -> bool:
        with self._pending_lock:
            spec = self._pending.get(task_id)
        owned = spec is not None
        first = owned and not spec.get("_cancelled")
        if first:
            spec["_cancelled"] = True
            # dequeue a not-yet-pushed normal task before any lease grant
            if spec.get("actor_id") is None:
                sig = self._lease_sig(spec)
                if sig is not None:
                    with self._lease_lock:
                        waiting = self._lease_waiting.get(sig)
                        if waiting is not None:
                            try:
                                waiting.remove(spec)
                            except ValueError:
                                pass  # already popped for a push (or queued)
            mode = "force" if force else "cooperative"
            internal_metrics.inc(
                "ray_tpu_tasks_cancelled_total", tags={"mode": mode}
            )
            # resolve the ref NOW: cancellation must not wait on a worker
            # round-trip (a task sleeping in C code can't ack cooperatively)
            self._fail_task(spec, TaskCancelledError(spec.get("name", "")))
        # reach the executing worker — idempotent RPC, delivered off-thread
        # (and retried by the rpc layer across drops while chaos is armed)
        if first or not owned:
            self._send_cancel_rpc(task_id, spec, force, recursive)
        if recursive:
            with self._pending_lock:
                children = list(self._children.get(task_id.binary(), ()))
            for child in children:
                try:
                    self.cancel_task_id(child, force=force, recursive=True)
                except Exception:
                    pass
        return owned

    def cancel_descendants(self, task_id: TaskID, *, force: bool = False):
        """Cancel every still-pending child this process submitted while
        ``task_id`` was executing (the worker-side leg of recursive
        cancellation: each child cancel fans out to ITS executing worker)."""
        with self._pending_lock:
            children = list(self._children.get(task_id.binary(), ()))
        for child in children:
            try:
                self.cancel_task_id(child, force=force, recursive=True)
            except Exception:
                pass

    def _send_cancel_rpc(self, task_id: TaskID, spec, force: bool,
                         recursive: bool):
        payload = {
            "task_id": task_id.binary(),
            "force": bool(force),
            "recursive": bool(recursive),
        }
        addr = tuple(spec.get("_worker_addr") or ()) if spec else ()
        name = spec.get("name", "") if spec else ""
        trace_id = ((spec.get("trace") or {}).get("trace_id")
                    if spec else None)

        def _deliver():
            if addr:
                try:
                    self._get_worker_client(addr).call(
                        "cancel_task", payload, timeout=3.0
                    )
                    self._report_cancel_event(task_id, name, trace_id)
                    return
                except Exception:
                    pass  # push target gone/stale: fall back to GCS lookup
            try:
                loc = self.gcs.call(
                    "locate_worker", {"task_id": task_id.hex()}, timeout=10.0
                )
                if not loc or not loc.get("node_id"):
                    if spec is not None:
                        self._report_cancel_event(task_id, name, trace_id)
                    return
                node_addr = self._node_address(NodeID.from_hex(loc["node_id"]))
                if node_addr is None:
                    return
                self._get_raylet_client(node_addr).call(
                    "cancel_task",
                    {**payload, "worker_id": bytes.fromhex(loc["worker_id"])},
                    timeout=3.0,
                )
                self._report_cancel_event(task_id, name, trace_id)
            except Exception:
                pass  # best-effort: the owner-side resolution already stands

        threading.Thread(target=_deliver, name="cancel-rpc", daemon=True).start()

    def _report_cancel_event(self, task_id: TaskID, name: str,
                             trace_id: Optional[str] = None):
        try:
            ev = {
                "type": "TASK_CANCELLED",
                "severity": "INFO",
                "message": f"task {name or task_id.hex()[:12]} cancelled",
                "task_id": task_id.hex(),
            }
            if trace_id:
                ev["trace_id"] = trace_id
            self.gcs.call("report_cluster_event", ev, timeout=5.0)
        except Exception:
            pass

    # ------------------------------------------------------------------
    # task events + tracing
    # ------------------------------------------------------------------

    def _trace_ctx(self) -> Optional[Dict[str, Any]]:
        """Span context for a task submitted from the current frame, or
        None while the distributed tracing plane (_private/trace.py,
        RAYTPU_TRACE_SAMPLE) is off. The context rides inside the task spec
        so nested submits form one trace: the task's span id is allocated
        here, at submit, so the executor closes exactly that span and the
        assembled tree links parent spans across processes."""
        if not _trace._active:
            return None
        parent = getattr(self._task_ctx, "task_id", None) or self._current_task_id
        ctx = _trace.current()
        if ctx is None:
            # trace root: a submit with no inherited context starts a
            # new trace (sampling drawn here, once per trace). Multi-
            # submit workloads share one trace by opening a root span
            # via ray_tpu.trace.start(), which installs the context.
            ctx = _trace.mint()
        return {
            "trace_id": ctx.trace_id,
            "parent_id": parent.hex() if parent is not None else None,
            "span_id": _trace.new_span_id(),
            "parent_span_id": ctx.span_id,
            "sampled": ctx.sampled,
        }

    def _emit_event(self, task_id: TaskID, state: str, name: str,
                    trace: Optional[Dict[str, Any]] = None):
        """Hot path (2-3 calls per task): record a raw tuple; the flush
        thread does the hex/dict shaping once a second off the task path."""
        if not GlobalConfig.task_events_enabled:
            return
        # deque.append is atomic under the GIL and the flusher drains with
        # popleft (never swaps the container), so no lock and no lost-event
        # window on the emit side
        self._events.append((task_id, state, name, time.time(), trace))

    def _event_loop(self):
        wid = self.worker_id.hex()
        events = self._events
        while not self._shutdown.wait(1.0):
            self._sweep_idle_leases()
            batch = []
            while True:
                try:
                    batch.append(events.popleft())
                except IndexError:
                    break
            if batch:
                # node identity attached at flush time (node_id may register
                # after the thread starts): timeline() buckets pid lanes by
                # node and tid rows by worker
                nid = self.node_id.hex() if self.node_id is not None else ""
                out = []
                for task_id, state, name, ts, trace in batch:
                    ev = {
                        "task_id": task_id.hex(),
                        "state": state,
                        "name": name,
                        "ts": ts,
                        "worker_id": wid,
                        "node_id": nid,
                    }
                    if trace:
                        ev["trace_id"] = trace.get("trace_id")
                        ev["parent_id"] = trace.get("parent_id")
                    out.append(ev)
                try:
                    self.gcs.call("add_task_events", out, timeout=5.0)
                except Exception:
                    pass

    def _on_gcs_notify(self, channel: str, message: Any):
        if channel == "chaos":
            if message.get("event") == "cleared":
                fault_injection.disarm()
            else:
                schedule = message.get("schedule")
                if schedule:
                    fault_injection.arm(
                        schedule,
                        local_node_id=(
                            self.node_id.hex() if self.node_id else None
                        ),
                        local_addresses=[self.raylet.address],
                    )
            return
        if channel == "logs":
            prefix = f"({message.get('node', '')} worker={message.get('worker', '')[:8]})"
            for line in message.get("lines", ()):
                self.captured_logs.append((prefix, line))
                print(f"{prefix} {line}", file=sys.stderr)
            return
        if channel == "nodes":
            if message.get("event") == "removed":
                node = message["node"]
                self._node_addr_cache.pop(node["node_id"], None)
                # invalidate the object directory for that node: objects
                # located only there are lost and become recovery candidates
                # — EXCEPT objects a graceful drain re-replicated to a peer
                # (the migration map rides the removal notification), which
                # just get their location updated: zero reconstructions.
                migrated = message.get("migrated") or {}
                addr = tuple(node.get("address") or ())
                if addr:
                    with self._locations_lock:
                        stale = [
                            b for b, a in self._locations.items() if tuple(a) == addr
                        ]
                        for b in stale:
                            new_loc = migrated.get(b)
                            if new_loc:
                                self._locations[b] = tuple(new_loc)
                            else:
                                self._locations.pop(b, None)
                                self._lost_objects.add(b)
            return
        if channel == "actors" or channel.startswith("actor:"):
            actor_id = message["actor_id"]
            with self._actor_lock:
                if message["state"] == "ALIVE":
                    self._actor_info[actor_id] = {
                        "address": tuple(message["address"]),
                        "state": "ALIVE",
                    }
                else:
                    self._actor_info.pop(actor_id, None)
                    if message["state"] == "DEAD":
                        # call templates die with the actor (leak guard)
                        for k in [
                            k for k in self._actor_tmpl_cache if k[0] == actor_id
                        ]:
                            tid, _ = self._actor_tmpl_cache.pop(k)
                            self._tmpl_defs.pop(tid, None)

    # ------------------------------------------------------------------

    def shutdown(self):
        self._shutdown.set()
        self._sweep_idle_leases(max_age=0.0)  # return every cached lease
        for _ in self._submitters:
            self._submit_queue.put(None)
        self._pull_pool.shutdown(wait=False)
        # release the gc pipe (fd audit: init/shutdown cycles in one process
        # — tests, notebooks — previously leaked both ends every cycle).
        # Invalidate the fd fields BEFORE closing: a late weakref finalizer
        # writing to a recycled fd number would corrupt an unrelated file.
        try:
            os.write(self._gc_w, b"x")  # wake the gc thread so it exits
        except OSError:
            pass
        self._gc_thread.join(timeout=2.0)
        gc_r, gc_w = self._gc_r, self._gc_w
        self._gc_r = self._gc_w = -1
        for fd in (gc_r, gc_w):
            try:
                os.close(fd)
            except OSError:
                pass
        with self._worker_clients_lock:
            for c in self._worker_clients.values():
                c.close()
            for c in self._raylet_clients.values():
                c.close()
        if self.plasma is not None:
            self.plasma.close()
        self.gcs.close()
        self.raylet.close()
