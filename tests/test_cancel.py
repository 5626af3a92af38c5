"""Cooperative task cancellation: ``ray_tpu.cancel`` end to end.

Covers owner-side ref resolution (a cancel resolves to TaskCancelledError
within 1s, without waiting on the executing worker), the cooperative
per-task flag (``get_runtime_context().was_cancelled()``), ``force=True``
thread-interrupt escalation, pending-task dequeue before lease grant,
recursive cancellation of a 3-deep nested tree, actor-call cancellation
(queued seq purge + in-flight interrupt), and delivery of the idempotent
``cancel_task`` RPC through an injected chaos drop."""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu._private.config import GlobalConfig
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    fi.disarm()


@pytest.fixture(scope="module")
def cluster():
    saved = dict(GlobalConfig._values)
    GlobalConfig.initialize({"resource_broadcast_period_s": 0.2})
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    ray_tpu.init(address=c.address, log_level="ERROR")
    yield c
    try:
        ray_tpu.shutdown()
    except Exception:
        pass
    c.shutdown()
    with GlobalConfig._lock:
        GlobalConfig._values = saved


def _await(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def _await_started(started):
    """The task's first act is ``open(started, "w")``: a cancel sent before it
    runs takes a queued task, which no code of the task ever sees (under load a
    lease takes seconds, not the 0.8 s these tests used to sleep)."""
    _await(lambda: os.path.exists(started), 60, "the task to start")


def test_cancel_resolves_within_1s(cluster):
    """A running (sleeping) task cancels cooperatively: the ref resolves
    to TaskCancelledError immediately — no worker round-trip on the
    resolution path."""

    @ray_tpu.remote
    def sleeper():
        for _ in range(200):  # ~10s unless interrupted
            time.sleep(0.05)
        return "done"

    ref = sleeper.remote()
    time.sleep(0.8)  # let it reach RUNNING
    t0 = time.monotonic()
    assert ray_tpu.cancel(ref) is True
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=5)
    assert time.monotonic() - t0 < 1.0
    # cancelling again is a no-op (the task is no longer owned-pending)
    assert ray_tpu.cancel(ref) is False
    # escalate so the worker slot frees promptly for the next test
    ray_tpu.cancel(ref, force=True)


def test_was_cancelled_cooperative_exit(cluster, tmp_path):
    """A long-running task polls the runtime context and exits on its own
    terms when cancelled — the checkpoint-then-return pattern."""
    marker = str(tmp_path / "saw_cancel")

    @ray_tpu.remote
    def poller(path, started):
        open(started, "w").close()
        ctx = ray_tpu.get_runtime_context()
        for _ in range(400):
            if ctx.was_cancelled():
                with open(path, "w") as f:
                    f.write("cooperative")
                return "exited-early"
            time.sleep(0.05)
        return "never-cancelled"

    started = str(tmp_path / "started")
    ref = poller.remote(marker, started)
    _await_started(started)
    assert ray_tpu.cancel(ref) is True
    _await(lambda: os.path.exists(marker), 10, "cooperative exit marker")
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=5)


def test_force_cancel_interrupts_running_thread(cluster, tmp_path):
    """force=True raises TaskCancelledError inside the worker thread at
    the next bytecode boundary; user code observes it like any except."""
    marker = str(tmp_path / "interrupted")

    @ray_tpu.remote
    def stubborn(path, started):
        try:
            open(started, "w").close()
            for _ in range(400):  # never polls was_cancelled()
                time.sleep(0.05)
        except ray_tpu.TaskCancelledError:
            with open(path, "w") as f:
                f.write("interrupted")
            raise
        return "ran to completion"

    started = str(tmp_path / "started")
    ref = stubborn.remote(marker, started)
    _await_started(started)
    assert ray_tpu.cancel(ref, force=True) is True
    _await(lambda: os.path.exists(marker), 10, "force-interrupt marker")
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=5)


def test_cancel_pending_task_dequeues_before_lease(cluster, tmp_path):
    """A task cancelled while queued behind a resource hog never runs."""
    ran = str(tmp_path / "ran")

    @ray_tpu.remote(num_cpus=4)
    def hog():
        for _ in range(200):
            time.sleep(0.05)
        return "hog"

    @ray_tpu.remote(num_cpus=4)
    def pending(path):
        open(path, "w").close()
        return "ran"

    hog_ref = hog.remote()
    time.sleep(0.5)  # hog holds every CPU; the next submit must queue
    pend_ref = pending.remote(ran)
    time.sleep(0.3)
    assert ray_tpu.cancel(pend_ref) is True
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(pend_ref, timeout=5)
    ray_tpu.cancel(hog_ref, force=True)
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(hog_ref, timeout=10)
    time.sleep(1.0)  # would have started by now were it still queued
    assert not os.path.exists(ran), "cancelled pending task still ran"


def test_recursive_cancel_reaps_nested_tree(cluster, tmp_path):
    """cancel(recursive=True) walks the ownership registry: root -> mid
    -> leaf all observe cancellation, each hop fanning out from the
    worker that submitted the child."""
    d = str(tmp_path)

    @ray_tpu.remote
    def leaf(d):
        open(os.path.join(d, "leaf_started"), "w").close()
        try:
            for _ in range(400):
                time.sleep(0.05)
        except ray_tpu.TaskCancelledError:
            open(os.path.join(d, "leaf_cancelled"), "w").close()
            raise
        return "leaf"

    @ray_tpu.remote
    def mid(d):
        r = leaf.remote(d)
        open(os.path.join(d, "mid_started"), "w").close()
        try:
            return ray_tpu.get(r, timeout=30)
        except ray_tpu.TaskCancelledError:
            open(os.path.join(d, "mid_cancelled"), "w").close()
            raise

    @ray_tpu.remote
    def root(d):
        r = mid.remote(d)
        open(os.path.join(d, "root_started"), "w").close()
        try:
            return ray_tpu.get(r, timeout=30)
        except ray_tpu.TaskCancelledError:
            open(os.path.join(d, "root_cancelled"), "w").close()
            raise

    ref = root.remote(d)
    _await(
        lambda: os.path.exists(os.path.join(d, "leaf_started")),
        20,
        "the 3-deep tree to spin up",
    )
    assert ray_tpu.cancel(ref, force=True, recursive=True) is True
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=5)
    for name in ("root_cancelled", "mid_cancelled", "leaf_cancelled"):
        _await(
            lambda n=name: os.path.exists(os.path.join(d, n)), 10, name
        )


def test_cancel_after_finish_is_noop(cluster):
    @ray_tpu.remote
    def quick():
        return 7

    ref = quick.remote()
    assert ray_tpu.get(ref, timeout=20) == 7
    assert ray_tpu.cancel(ref) is False
    assert ray_tpu.get(ref, timeout=5) == 7  # the value survives


def test_cancel_rpc_retries_through_injected_drop(cluster, tmp_path):
    """The first cancel_task RPC is dropped by an armed chaos rule: the
    idempotency-classified retry still delivers the interrupt exactly
    once, and owner-side resolution never waited on it."""
    marker = str(tmp_path / "interrupted")

    @ray_tpu.remote
    def stubborn(path, started):
        try:
            open(started, "w").close()
            for _ in range(600):
                time.sleep(0.05)
        except ray_tpu.TaskCancelledError:
            open(path, "w").close()
            raise
        return "done"

    started = str(tmp_path / "started")
    ref = stubborn.remote(marker, started)
    _await_started(started)
    fi.arm(
        {
            "seed": 0,
            "rules": [{"action": "drop", "method": "cancel_task", "nth": 1}],
        }
    )
    assert ray_tpu.cancel(ref, force=True) is True
    # the ref resolves immediately regardless of the dropped delivery
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(ref, timeout=5)
    # the retried RPC reaches the worker (drop eats ~3s, retry lands)
    _await(
        lambda: os.path.exists(marker),
        20,
        "the retried cancel to reach the worker",
    )
    assert fi.local_report()["counts"].get("drop") == 1


def test_cancel_actor_call_in_flight_and_queued(cluster):
    """In-flight actor calls resolve to TaskCancelledError; queued seqs
    are purged from the per-actor outbox; the actor itself survives."""

    @ray_tpu.remote
    class Sleeper:
        def slow(self, s):
            time.sleep(s)
            return "slept"

        def ping(self):
            return "pong"

    a = Sleeper.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
    inflight = a.slow.remote(2.0)
    time.sleep(0.3)
    queued = a.slow.remote(2.0)
    assert ray_tpu.cancel(queued) is True
    assert ray_tpu.cancel(inflight) is True
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(inflight, timeout=5)
    with pytest.raises(ray_tpu.TaskCancelledError):
        ray_tpu.get(queued, timeout=5)
    # cancellation must not poison the actor
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"
