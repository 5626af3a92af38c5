"""Worker-side task execution: the server half of the direct task transport.

Handles push_task / create_actor on a worker's RPC server (reference:
src/ray/core_worker/core_worker.cc:2553 ExecuteTask and the scheduling queues
in transport/actor_scheduling_queue.cc — in-order per caller via sequence
numbers; concurrency capped per actor by max_concurrency,
transport/concurrency_group_manager.h:37).
"""

from __future__ import annotations

import logging
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import accelerator, internal_metrics
from ray_tpu._private import serialization
from ray_tpu._private import trace as _trace
from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.core_worker import (
    CoreWorker,
    PLASMA_MARKER,
    TaskCancelledError,
    TaskError,
)
from ray_tpu._private.ids import ActorID, ObjectID, TaskID, WorkerID
from ray_tpu._private.rpc import Deferred, RpcServer, ServerConn

logger = logging.getLogger(__name__)

#: the process's TaskExecutor (workers only) — lets the public
#: ``get_runtime_context().was_cancelled()`` reach the cancel registry
#: without threading the executor through every call site
_current_executor: Optional["TaskExecutor"] = None


class _NullGate:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_GATE = _NullGate()

# per-kind bound metric handles, resolved on first task of each kind:
# the per-task path is lock + add, not tag-dict build + merge + sort
_task_metric_handles: Dict[str, Tuple[Any, Any]] = {}


def _task_metrics(kind: str) -> Tuple[Any, Any]:
    h = _task_metric_handles.get(kind)
    if h is None:
        h = (
            internal_metrics.bound_counter(
                "ray_tpu_tasks_executed_total", {"kind": kind}
            ),
            internal_metrics.bound_histogram(
                "ray_tpu_task_exec_latency_seconds", {"kind": kind}
            ),
        )
        _task_metric_handles[kind] = h
    return h


class _ActorState:
    """Hosts one actor instance plus its in-order execution queue.

    Ordered (max_concurrency==1) calls run on a dedicated thread consuming
    the queue in arrival order — arrival order equals the caller's send
    order because push_task is an inline rpc handler (enqueued on the
    connection read loop) and each caller pushes on one TCP connection in
    sequence order. This is the pipelined equivalent of the reference's
    ActorSchedulingQueue (transport/actor_scheduling_queue.cc): many calls
    in flight, execution strictly serialized and ordered."""

    def __init__(self, instance: Any, max_concurrency: int):
        self.instance = instance
        self.max_concurrency = max_concurrency
        self.sem = threading.Semaphore(max_concurrency)
        import collections

        self.queue: "collections.deque" = collections.deque()
        self.cv = threading.Condition()
        self.thread: Optional[threading.Thread] = None
        # lazily created per-actor asyncio loop for async methods (the
        # boost::fibers analogue — core_worker/fiber.h:17; here a real
        # event loop thread so `async def` methods interleave)
        self._loop = None
        self._loop_lock = threading.Lock()

    def ensure_loop(self):
        import asyncio

        with self._loop_lock:
            if self._loop is None:
                self._loop = asyncio.new_event_loop()
                t = threading.Thread(
                    target=self._loop.run_forever, name="actor-asyncio",
                    daemon=True,
                )
                t.start()
            return self._loop

    def enqueue(self, item):
        with self.cv:
            self.queue.append(item)
            self.cv.notify()


class TaskExecutor:
    # push_task runs inline on the connection read loop so ordered actor
    # calls enqueue in arrival order; the actual execution happens on the
    # actor's thread (ordered) or the server pool (normal/unordered).
    RPC_INLINE = ("push_task", "push_task_batch")

    def __init__(self, core: CoreWorker, server: RpcServer):
        self.core = core
        self.server = server
        self._actors: Dict[ActorID, _ActorState] = {}
        self._actors_lock = threading.Lock()
        # wire-spec templates registered by owners (bounded by the number of
        # distinct RemoteFunction+options objects across connected drivers)
        self._tmpls: Dict[bytes, Dict[str, Any]] = {}
        # cancellation plane: task binary -> {"cancelled", "thread"} while a
        # task executes; cancel RPCs that beat the task's arrival park in
        # _precancelled (bounded — cancel is best-effort once evicted)
        self._cancel_lock = threading.Lock()
        self._cancel_running: Dict[bytes, Dict[str, Any]] = {}
        import collections

        self._precancelled: "collections.OrderedDict" = collections.OrderedDict()
        global _current_executor
        _current_executor = self
        server.register("push_task", self.rpc_push_task, inline=True)
        server.register("push_task_batch", self.rpc_push_task_batch, inline=True)
        server.register("create_actor", self.rpc_create_actor)
        server.register("cancel_task", self.rpc_cancel_task)
        server.register("kill_self", self.rpc_kill_self)
        server.register("health", lambda conn, p: "ok")
        server.register("profile", self.rpc_profile)
        server.register("trace_spans", lambda conn, p: _trace.snapshot())

    # ------------------------------------------------------------------

    def _deserialize_args(self, spec: Dict[str, Any]) -> Tuple[list, dict]:
        import pickle

        # a pushed task can beat late_register's plasma attach by one hop
        if not self.core.runtime_ready.wait(timeout=30):
            raise RuntimeError("worker runtime not ready (plasma unattached)")
        # location hints let core.get pull cross-node deps into local plasma
        self.core.register_locations(spec.get("locations") or {})
        desc_args, desc_kwargs = pickle.loads(spec["args"])
        args = []
        ref_ids = [d[1] for d in desc_args if d[0] == "ref"]
        ref_ids += [d[1] for d in desc_kwargs.values() if d[0] == "ref"]
        resolved: Dict[ObjectID, Any] = {}
        if ref_ids:
            values = self.core.get(ref_ids)
            resolved = dict(zip(ref_ids, values))
        for kind, v in desc_args:
            args.append(resolved[v] if kind == "ref" else v)
        kwargs = {
            k: (resolved[v] if kind == "ref" else v) for k, (kind, v) in desc_kwargs.items()
        }
        return args, kwargs

    def _package_results(self, task_id, num_returns: int, value: Any, is_exception: bool):
        """Returns (results, ref_locations, is_exception): per-return
        (oid, kind, data) triples plus location hints for any ObjectRefs
        nested in the values, so a cross-node caller can pull them
        (ownership-based directory). The returned is_exception may be True
        even when the input flag was False: a dynamic-return generator can
        raise mid-iteration, after the task function itself returned."""
        if num_returns == "dynamic":
            if is_exception:
                return self._package_results(task_id, 1, value, True)
            return self._package_dynamic_results(task_id, value)
        if is_exception:
            values = [value] * num_returns
        elif num_returns == 1:
            values = [value]
        else:
            values = list(value)
            if len(values) != num_returns:
                err = TaskError(
                    ValueError(
                        f"task declared num_returns={num_returns} but returned "
                        f"{len(values)} values"
                    )
                )
                return self._package_results(task_id, num_returns, err, True)
        out = []
        ref_locations: Dict[bytes, Tuple[str, int]] = {}
        inline_max = GlobalConfig.object_store_inline_max_bytes
        for i, v in enumerate(values):
            oid = ObjectID.for_task_return(task_id, i + 1)
            sobj, refs = serialization.serialize_and_collect_refs(
                v, is_exception=is_exception
            )
            if refs:
                # returned ObjectRefs: the caller will resolve them from
                # plasma, so promote this worker's inline results first
                try:
                    self.core._resolve_deps([], refs)
                except Exception:
                    logger.exception("failed to promote returned refs")
                ref_locations.update(self.core._dep_locations([], refs))
            if sobj.total_size() <= inline_max:
                out.append((oid, "inline", sobj.to_bytes()))
            else:
                self.core.plasma.put_serialized(oid, sobj)
                out.append((oid, "plasma", None))
        return out, ref_locations, is_exception

    def _package_dynamic_results(self, task_id, value):
        """num_returns="dynamic": store each yielded item as its own return
        object (indices >= 2, local plasma) and package an
        ObjectRefGenerator over them as the task's single static return.
        The caller learns the item locations through the reply's
        ref_locations, exactly like any other ObjectRef nested in a return
        value (ownership-based directory). Items stream to plasma one at a
        time — the worker never holds more than one yielded value."""
        from ray_tpu._private.ids import ObjectRefGenerator

        node = tuple(self.core.raylet.address)
        refs: List[ObjectID] = []
        item_locations: Dict[bytes, Tuple[str, int]] = {}
        try:
            for j, item in enumerate(value):  # drives the generator
                oid = ObjectID.for_task_return(task_id, j + 2)
                # same nested-ref promotion as the static-return path: refs
                # inside a yielded value must reach plasma + ship locations
                sobj, nested = serialization.serialize_and_collect_refs(item)
                if nested:
                    try:
                        self.core._resolve_deps([], nested)
                    except Exception:
                        logger.exception("failed to promote refs in dynamic item")
                    item_locations.update(self.core._dep_locations([], nested))
                self.core.plasma.put_serialized(oid, sobj)
                refs.append(oid)
        except Exception as e:  # noqa: BLE001 — user generator code raised
            # items stored before the failure would be orphans (no owner
            # ref will ever exist for them): free them now
            for oid in refs:
                try:
                    self.core.plasma.delete(oid)
                except Exception:
                    pass
            return self._package_results(
                task_id, 1,
                TaskError(e, "dynamic-return generator", traceback.format_exc()),
                True,
            )
        out, ref_locations, _ = self._package_results(
            task_id, 1, ObjectRefGenerator(refs), False
        )
        ref_locations.update(item_locations)
        for oid in refs:
            ref_locations.setdefault(oid.binary(), node)
        return out, ref_locations, False

    def _reply(self, packed) -> Dict[str, Any]:
        results, ref_locations, is_exc = packed
        return {
            "status": "ok" if not is_exc else "error",
            "results": results,
            "node": tuple(self.core.raylet.address),
            "ref_locations": ref_locations,
        }

    def _run(self, fn, args, kwargs, task_id, name: str, loop=None, trace=None,
             attempt: int = 0):
        import asyncio
        import inspect

        token_tid = getattr(self.core._task_ctx, "task_id", None)
        token_name = getattr(self.core._task_ctx, "task_name", None)
        self.core._task_ctx.task_id = task_id
        self.core._task_ctx.task_name = name
        # distributed tracing plane: the submit site pre-allocated this
        # task's span id — install the context (so nested submits / RPCs /
        # object ops become children) and close exactly that span on exit
        t_ctx = t_token = None
        t_status = "ok"
        if _trace._active and trace and trace.get("span_id"):
            t_ctx = _trace.TraceContext(
                trace["trace_id"], trace["span_id"],
                bool(trace.get("sampled", True)),
            )
            t_token = _trace.set_current(t_ctx)
        t_start = time.time()
        t_perf = time.perf_counter()
        # structured boundary markers in the worker log: get_log(task_id=...)
        # slices the lines between this pair; the raylet log monitor strips
        # them from the driver's stdout mirror (name goes last — it may
        # contain spaces)
        marker = f"task_id={task_id.hex()} attempt={attempt} name={name}"
        print(f"::task_begin {marker}", flush=True)
        tbin = task_id.binary()
        with self._cancel_lock:
            precancelled = self._precancelled.pop(tbin, None) is not None
            if not precancelled:
                self._cancel_running[tbin] = {
                    "cancelled": False,
                    "thread": threading.get_ident(),
                }
        try:
            if precancelled:
                t_status = "cancelled"
                return TaskCancelledError(name), True
            result = fn(*args, **kwargs)
            if inspect.iscoroutine(result):
                if loop is not None:
                    # async actor method: all coroutines of this actor share
                    # one event loop so concurrent calls interleave (the
                    # asyncio equivalent of the reference's fiber actors)
                    result = asyncio.run_coroutine_threadsafe(result, loop).result()
                else:
                    result = asyncio.run(result)  # async normal task
            return result, False
        except TaskCancelledError:
            # raised by the task itself or injected by a force-cancel: reply
            # with the typed error unwrapped so the owner resolves the ref
            # to TaskCancelledError (not a generic TaskError)
            t_status = "cancelled"
            return TaskCancelledError(name), True
        except Exception as e:  # noqa: BLE001
            t_status = "error"
            return TaskError(e, name, traceback.format_exc()), True
        finally:
            with self._cancel_lock:
                self._cancel_running.pop(tbin, None)
            print(f"::task_end {marker}", flush=True)
            self.core._task_ctx.task_id = token_tid
            self.core._task_ctx.task_name = token_name
            if t_ctx is not None:
                _trace.record_span(
                    t_ctx.trace_id, t_ctx.span_id,
                    trace.get("parent_span_id"),
                    f"task:{name}", "task", t_start,
                    time.perf_counter() - t_perf, status=t_status,
                    attrs={
                        "task_id": task_id.hex(),
                        "node_id": self.core.node_id.hex()
                        if self.core.node_id is not None else "",
                        "worker_id": self.core.worker_id.hex(),
                        "attempt": attempt,
                    },
                    sampled=t_ctx.sampled,
                )
                _trace.set_current(t_token)

    # ------------------------------------------------------------------

    def rpc_push_task(self, conn: ServerConn, spec: Dict[str, Any]):
        """Inline handler: must not block. Routes to the actor's ordered
        queue or the dispatch pool and returns a Deferred reply."""
        if "task_id" not in spec:  # template-diff form: {"t": ..., "tmpls": ...}
            tmpls = spec.get("tmpls")
            if tmpls:
                self._tmpls.update(tmpls)
            spec = self._expand_spec(spec["t"])
        d = Deferred()
        if spec.get("actor_id") is not None and spec.get("method") is not None:
            with self._actors_lock:
                state = self._actors.get(spec["actor_id"])
            if state is None:
                raise RuntimeError(
                    f"actor {spec['actor_id'].hex()[:8]} not hosted on this worker"
                )
            control = spec.get("method") in getattr(
                type(state.instance), "__ray_control_methods__", ()
            )
            if control:
                # control-plane probes jump BOTH queues: a wedged ordered
                # actor (or saturated concurrency gate) must still answer
                self.server._pool.submit(
                    self._resolve_with, d, self._execute_actor_task, spec
                )
            elif spec.get("ordered", True) and state.max_concurrency == 1:
                if state.thread is None:
                    state.thread = threading.Thread(
                        target=self._actor_exec_loop,
                        args=(state,),
                        name=f"actor-{spec['actor_id'].hex()[:8]}",
                        daemon=True,
                    )
                    state.thread.start()
                state.enqueue((spec, d))
            else:
                self.server._pool.submit(
                    self._resolve_with, d, self._execute_actor_task, spec
                )
        else:
            self.server._pool.submit(
                self._resolve_with, d, self._execute_normal_task, spec
            )
        return d

    #: defaults for spec fields a template-diff frame may omit when empty
    _SPEC_DEFAULTS = {
        "deps": (),
        "nested": (),
        "locations": None,
        "trace": None,
        "retries_left": 0,
        "resubmits_left": 0,
    }

    def rpc_push_task_batch(self, conn: ServerConn, payload):
        """Inline handler: a pipelined batch of NORMAL tasks from one owner.
        Executed sequentially on one pool thread — the point is amortizing
        per-task wire/dispatch overhead (one frame, one pickle header, one
        callback each way per batch instead of per task), the single-core
        analogue of the reference's pipelined task pushes
        (direct_task_transport.cc:234 PushNormalTask back-to-back).

        Payload: ``{"bid", "tmpls": {id: static-fields}|None, "tasks":
        [(tmpl_id|None, diff-or-full-spec), ...]}``. Template definitions
        arrive on the connection that first uses them; registration here on
        the read loop (inline) guarantees a template always lands before
        any frame referencing it is dispatched."""
        tmpls = payload.get("tmpls")
        if tmpls:
            self._tmpls.update(tmpls)
        d = Deferred()
        self.server._pool.submit(
            self._run_batch, d, conn, payload["bid"], payload["tasks"]
        )
        return d

    def _expand_spec(self, task):
        tmpl_id, diff = task
        if tmpl_id is None:
            return diff
        spec = dict(self._SPEC_DEFAULTS)
        spec.update(self._tmpls[tmpl_id])
        spec.update(diff)
        return spec

    def _run_batch(self, d: Deferred, conn: ServerConn, bid: int, tasks):
        from ray_tpu._private.rpc import _wire_safe_exc

        # Batches that run long stream each reply the moment its task
        # finishes (NOTIFY rides the same socket, so item frames always
        # precede the terminal response): dependents unblock early and
        # completed work is acked before a potential worker death (ADVICE
        # r4 medium). Sub-threshold batches (microtask floods, where the
        # terminal reply is imminent anyway) skip the per-item frames —
        # streaming every noop costs ~25us/task on a 1-core host. The
        # terminal reply carries results only for unstreamed items.
        replies = []
        stream = False
        t0 = time.monotonic() if len(tasks) > 1 else None
        for i, task in enumerate(tasks):
            try:
                reply = self._execute_normal_task(self._expand_spec(task))
            except Exception as e:  # noqa: BLE001
                # these ride inside a RESPONSE frame, which skips the
                # server-side ERROR downcast: apply it here or one bad
                # exception tears down the owner's whole connection
                reply = _wire_safe_exc(e)
            if not stream and t0 is not None and time.monotonic() - t0 > 0.005:
                stream = True
            if stream:
                try:
                    conn.notify("batch_item", (bid, i, reply))
                    replies.append(None)
                    continue
                except Exception:  # conn dying: terminal path reports it
                    pass
            replies.append(reply)
        d.resolve({"bid": bid, "replies": replies})

    def _resolve_with(self, d: Deferred, fn, spec):
        try:
            d.resolve(fn(spec))
        except Exception as e:  # noqa: BLE001
            d.resolve(e, is_error=True)

    def _actor_exec_loop(self, state: _ActorState):
        while True:
            with state.cv:
                while not state.queue:
                    state.cv.wait()
                spec, d = state.queue.popleft()
            try:
                d.resolve(self._execute_actor_task(spec))
            except BaseException as e:  # noqa: BLE001 - incl. SystemExit:
                # the loop thread must survive (its death would strand every
                # queued Deferred); sys.exit() from a method surfaces as an
                # error reply, matching exit-from-task semantics
                d.resolve(e if isinstance(e, Exception) else RuntimeError(repr(e)), is_error=True)

    def _execute_normal_task(self, spec) -> Dict[str, Any]:
        task_id = spec["task_id"]
        self.core._emit_event(task_id, "RUNNING", spec["name"], spec.get("trace"))
        try:
            fn = self.core.import_function(spec["fn_id"])
            args, kwargs = self._deserialize_args(spec)
        except Exception as e:  # noqa: BLE001
            value, is_exc = TaskError(e, spec["name"], traceback.format_exc()), True
        else:
            exec_t0 = time.perf_counter()
            value, is_exc = self._run(
                fn, args, kwargs, task_id, spec["name"], trace=spec.get("trace"),
                attempt=spec.get("attempt", 0),
            )
            executed, latency = _task_metrics("normal")
            executed.inc()
            latency.observe(time.perf_counter() - exec_t0)
        return self._reply(
            self._package_results(task_id, spec["num_returns"], value, is_exc)
        )

    def _execute_actor_task(self, spec) -> Dict[str, Any]:
        # Per-caller ordering is guaranteed by the caller-side FIFO drain
        # (core_worker._enqueue_actor_task); here we only bound concurrency.
        task_id = spec["task_id"]
        actor_id = spec["actor_id"]
        with self._actors_lock:
            state = self._actors.get(actor_id)
        if state is None:
            raise RuntimeError(f"actor {actor_id.hex()[:8]} not hosted on this worker")
        if spec["method"] == "__ray_terminate__":
            self.rpc_kill_self(None, None)
            return self._reply(
                self._package_results(task_id, spec["num_returns"], None, False)
            )
        # control-plane methods bypass the concurrency cap so health/metrics
        # probes can't starve behind saturated user calls (the reference's
        # separate control concurrency group —
        # transport/concurrency_group_manager.h:37)
        control = spec["method"] in getattr(
            type(state.instance), "__ray_control_methods__", ()
        )
        gate = state.sem if not control else _NULL_GATE
        with gate:
            self.core._emit_event(task_id, "RUNNING", spec["name"], spec.get("trace"))
            try:
                method = getattr(state.instance, spec["method"])
                args, kwargs = self._deserialize_args(spec)
            except Exception as e:  # noqa: BLE001
                value, is_exc = TaskError(e, spec["name"], traceback.format_exc()), True
            else:
                import inspect

                loop = (
                    state.ensure_loop()
                    if inspect.iscoroutinefunction(getattr(method, "__func__", method))
                    else None
                )
                exec_t0 = time.perf_counter()
                value, is_exc = self._run(
                    method, args, kwargs, task_id, spec["name"], loop=loop,
                    trace=spec.get("trace"), attempt=spec.get("attempt", 0),
                )
                executed, latency = _task_metrics("actor")
                executed.inc()
                latency.observe(time.perf_counter() - exec_t0)
        return self._reply(
            self._package_results(task_id, spec["num_returns"], value, is_exc)
        )

    def rpc_create_actor(self, conn: ServerConn, payload) -> bool:
        spec = payload["spec"]
        actor_id = payload["actor_id"]
        cls = self.core.import_function(spec["class_id"])
        args, kwargs = self._deserialize_args(spec)
        options = spec["options"]
        creation_task = spec.get("creation_task_id") or actor_id
        instance = cls(*args, **kwargs)
        max_concurrency = int(options.get("max_concurrency", 1) or 1)
        with self._actors_lock:
            self._actors[actor_id] = _ActorState(instance, max_concurrency)
        logger.info("actor %s (%s) created", actor_id.hex()[:8], spec.get("class_name"))
        return True

    # ------------------------------------------------------------------
    # cancellation (idempotent: repeated calls for the same task converge
    # on the same state — the retry layer may deliver this twice)

    def rpc_cancel_task(self, conn: ServerConn, payload) -> Dict[str, Any]:
        payload = payload or {}
        tbin = payload.get("task_id")
        force = bool(payload.get("force"))
        recursive = bool(payload.get("recursive", True))
        status = "pending"
        with self._cancel_lock:
            entry = self._cancel_running.get(tbin)
            if entry is not None:
                already = entry["cancelled"]
                entry["cancelled"] = True
                status = "running"
            elif tbin not in self._precancelled:
                # task not here yet (or already finished): park the intent so
                # a late-arriving execution is rejected before user code runs
                self._precancelled[tbin] = True
                while len(self._precancelled) > 4096:
                    self._precancelled.popitem(last=False)
        if status == "running" and force and not already:
            # escalation: raise TaskCancelledError inside the executing
            # thread (takes effect at the next bytecode boundary — a task
            # blocked in C code is only reaped when it returns to Python)
            import ctypes

            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_long(entry["thread"]),
                ctypes.py_object(TaskCancelledError),
            )
        if recursive:
            try:
                self.core.cancel_descendants(
                    TaskID(tbin), force=force
                )
            except Exception:
                logger.exception("recursive cancel of descendants failed")
        return {"status": status}

    def is_cancelled(self, task_id) -> bool:
        """Cooperative check for the currently running task — surfaced as
        ``ray_tpu.get_runtime_context().was_cancelled()``."""
        with self._cancel_lock:
            entry = self._cancel_running.get(task_id.binary())
            return bool(entry and entry["cancelled"])

    def rpc_profile(self, conn: ServerConn, payload) -> Dict[str, Any]:
        """On-demand CPU profile: sample every thread's stack for
        ``duration_s`` at ``interval_s`` and return folded stacks (the
        flamegraph text format). The in-process stand-in for the
        reference's py-spy integration (dashboard/modules/reporter/
        profile_manager.py:10-25) — no subprocess, no ptrace, works on any
        live worker/actor."""
        import sys as _sys
        import time as _time

        payload = payload or {}
        duration = min(float(payload.get("duration_s", 2.0)), 30.0)
        interval = max(float(payload.get("interval_s", 0.01)), 0.001)
        folded: Dict[str, int] = {}
        samples = 0
        deadline = _time.monotonic() + duration
        my_thread = threading.get_ident()
        while _time.monotonic() < deadline:
            for tid, frame in _sys._current_frames().items():
                if tid == my_thread:
                    continue  # don't profile the profiler
                stack = ";".join(accelerator.fold_stack(frame))
                folded[stack] = folded.get(stack, 0) + 1
            samples += 1
            _time.sleep(interval)
        return {
            "pid": os.getpid(),
            "samples": samples,
            "duration_s": duration,
            "folded": folded,
        }

    def rpc_kill_self(self, conn: ServerConn, payload) -> bool:
        def _die():
            time.sleep(0.05)
            os._exit(0)

        threading.Thread(target=_die, daemon=True).start()
        return True
