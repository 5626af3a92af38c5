"""Distributed tracing plane: propagation, chaos interplay, analysis.

Covers the hot-path contract (one attribute read when disabled, zero span
records), context propagation through RPC frames and task specs, the
retry/dedup invariant (a FaultSchedule-dropped-then-retried idempotent RPC
records exactly ONE span — the span wraps the logical call, not each
attempt), cancelled-task span status, cross-node parent/child linkage, and
the analysis layer (critical path + straggler flagging) on a synthetic
span set.
"""

import time

import pytest

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu._private import trace as _tr
from ray_tpu._private.rpc import RpcClient, RpcServer


@pytest.fixture(autouse=True)
def _reset_trace_plane():
    # ``_system_config={"trace_sample": 1.0}`` outlives ``shutdown``; the
    # conftest's ``_system_config_ends_with_the_test`` puts the config back
    yield
    fi.disarm()
    _tr.disable()
    _tr.clear()
    _tr.set_current(None)


# ---------------------------------------------------------------------------
# core plane semantics (no cluster)
# ---------------------------------------------------------------------------


def test_disabled_plane_records_nothing():
    _tr.clear()
    assert _tr.start_span("x") is None  # no context, nothing to trace
    _tr.enable(0.0)  # rate 0 == off
    assert _tr._active is False
    ctx = _tr.mint()
    assert ctx.sampled is False
    # unsampled + ok is dropped; unsampled + error is force-recorded
    _tr.record_span("t", "s", None, "n", "k", 0.0, 1.0, sampled=False)
    assert _tr.snapshot()["spans"] == []
    _tr.record_span("t", "s", None, "n", "k", 0.0, 1.0, status="error",
                    sampled=False)
    assert len(_tr.snapshot()["spans"]) == 1


def test_wire_roundtrip_and_unsampled_not_propagated():
    _tr.enable(1.0)
    _tr.set_current(_tr.child(_tr.mint(sampled=True)))
    wire = _tr.propagate()
    assert wire is not None
    ctx = _tr.adopt_wire(wire)
    assert ctx.trace_id == _tr.current().trace_id
    assert ctx.span_id == _tr.current().span_id
    # unsampled contexts stay off the wire entirely
    _tr.set_current(_tr.child(_tr.mint(sampled=False)))
    assert _tr.propagate() is None
    # malformed wire metadata must never raise
    assert _tr.adopt_wire(("only-two", "elems")) is None
    assert _tr.adopt_wire(None) is None


def test_ring_overwrite_reports_dropped():
    _tr.enable(1.0)
    _tr.clear()
    n = _tr._RING_SIZE + 7
    for i in range(n):
        _tr.record_span("t", f"s{i}", None, "n", "k", 0.0, 0.0)
    snap = _tr.snapshot()
    assert snap["dropped"] == 7
    assert len(snap["spans"]) == _tr._RING_SIZE


# ---------------------------------------------------------------------------
# chaos interplay: drop-then-retry yields exactly one span (raw rpc layer)
# ---------------------------------------------------------------------------


@pytest.fixture
def echo_server():
    srv = RpcServer(name="trace-test")
    state = {"kv": {"k": 42}, "calls": 0}

    def kv_get(conn, payload):
        state["calls"] += 1
        return state["kv"].get(payload)

    srv.register("kv_get", kv_get)
    client = RpcClient(srv.address)
    yield srv, client, state
    client.close()
    srv.stop()


def test_dropped_then_retried_idempotent_rpc_records_one_span(echo_server):
    srv, client, state = echo_server
    _tr.enable(1.0)
    _tr.set_current(_tr.child(_tr.mint(sampled=True)))
    _tr.clear()
    fi.arm(
        {
            "seed": 0,
            "rules": [{"action": "drop", "method": "kv_get", "nth": 1}],
        }
    )
    # first send swallowed -> injected timeout -> retried (idempotent)
    assert client.call("kv_get", "k", timeout=1.0) == 42
    assert fi.local_report()["counts"].get("drop") == 1
    spans = [
        s for s in _tr.snapshot()["spans"] if s["name"] == "rpc.kv_get"
    ]
    # the span wraps the LOGICAL call: one span, status ok, covering both
    # attempts — not one per attempt
    assert len(spans) == 1
    assert spans[0]["status"] == "ok"
    assert spans[0]["dur_s"] >= 0.9  # it really contains the retry wait
    assert spans[0]["parent_span_id"] == _tr.current().span_id


def test_failed_rpc_span_closes_with_error(echo_server):
    srv, client, state = echo_server
    _tr.enable(1.0)
    _tr.set_current(_tr.child(_tr.mint(sampled=True)))
    _tr.clear()

    def boom(conn, payload):
        raise RuntimeError("nope")

    srv.register("boom", boom)
    with pytest.raises(Exception):
        client.call("boom", None, timeout=5.0)
    spans = [s for s in _tr.snapshot()["spans"] if s["name"] == "rpc.boom"]
    assert len(spans) == 1
    assert spans[0]["status"] == "error"


# ---------------------------------------------------------------------------
# cluster propagation
# ---------------------------------------------------------------------------


def test_cancelled_task_span_closes_with_status_cancelled():
    ray_tpu.init(
        num_cpus=2,
        log_level="WARNING",
        _system_config={"trace_sample": 1.0},
    )
    try:

        @ray_tpu.remote
        def stubborn():
            for _ in range(400):  # never returns on its own
                time.sleep(0.05)

        with ray_tpu.trace.start("cancel-run") as root:
            ref = stubborn.remote()
            time.sleep(1.0)  # let it reach RUNNING
            assert ray_tpu.cancel(ref, force=True) is True
            with pytest.raises(ray_tpu.TaskCancelledError):
                ray_tpu.get(ref, timeout=10)

        deadline = time.monotonic() + 15
        span = None
        while time.monotonic() < deadline and span is None:
            t = ray_tpu.trace.get(root.trace_id)
            for s in t["spans"]:
                if s["name"] == "task:stubborn":
                    span = s
                    break
            time.sleep(0.3)
        assert span is not None, "task span never harvested"
        assert span["status"] == "cancelled"
    finally:
        ray_tpu.shutdown()


def test_cross_node_actor_call_parent_child_linkage(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2, resources={"B": 2.0})
    ray_tpu.init(
        address=cluster.address,
        log_level="WARNING",
        _system_config={"trace_sample": 1.0},
    )

    @ray_tpu.remote(resources={"B": 0.001})
    class Doubler:
        def ping(self, x):
            return x * 2

    a = Doubler.remote()
    assert ray_tpu.get(a.ping.remote(1), timeout=60) == 2  # warm up

    with ray_tpu.trace.start("xnode") as root:
        assert ray_tpu.get(a.ping.remote(21), timeout=60) == 42

    t = ray_tpu.trace.get(root.trace_id)
    roots = t["roots"]
    assert [r["name"] for r in roots] == ["trace:xnode"]
    pings = [
        s for s in t["spans"]
        if s["kind"] == "task" and s["name"].endswith("ping")
    ]
    assert len(pings) == 1
    ping = pings[0]
    # direct parent/child linkage: the actor call's pre-allocated span
    # parents on the driver's root span, across the node boundary
    assert ping["parent_span_id"] == roots[0]["span_id"]
    assert ping["status"] == "ok"
    # attribution: the span carries the EXECUTING node/worker, which is
    # the B node, not the head the driver sits on
    head_nid = cluster.head_node.raylet.node_id.hex()
    assert ping["attrs"]["node_id"]
    assert ping["attrs"]["node_id"] != head_nid
    # and the driver-side object.get that waited on it is in the tree too
    kinds = {s["kind"] for s in t["spans"]}
    assert "object" in kinds


# ---------------------------------------------------------------------------
# analysis layer (pure functions, synthetic spans)
# ---------------------------------------------------------------------------


def _span(span_id, parent, name, start, dur, **attrs):
    return {
        "trace_id": "t1",
        "span_id": span_id,
        "parent_span_id": parent,
        "name": name,
        "kind": "task",
        "start_ts": start,
        "dur_s": dur,
        "status": "ok",
        "attrs": attrs or None,
        "node_id": "",
        "process": "test",
    }


def test_critical_path_telescopes_to_root_duration():
    spans = [
        _span("r", None, "trace:step", 0.0, 10.0),
        _span("a", "r", "task:mid", 1.0, 8.0),
        _span("b", "a", "task:leaf", 2.0, 6.0),
        _span("c", "a", "task:leaf", 2.0, 1.0),
    ]
    trace = {"trace_id": "t1", "spans": spans,
             "roots": ray_tpu.trace._assemble(spans)}
    path = ray_tpu.trace.critical_path(trace)
    assert [h["span_id"] for h in path] == ["r", "a", "b"]
    assert sum(h["self_s"] for h in path) == pytest.approx(10.0)


def test_straggler_flagging_needs_siblings_and_margin():
    kids = [
        _span(f"s{i}", "r", "task:leaf", 1.0, 0.1,
              node_id=f"n{i}", worker_id=f"w{i}")
        for i in range(7)
    ]
    kids.append(
        _span("slow", "r", "task:leaf", 1.0, 0.9,
              node_id="n9", worker_id="w9")
    )
    spans = [_span("r", None, "trace:step", 0.0, 2.0)] + kids
    trace = {"trace_id": "t1", "spans": spans,
             "roots": ray_tpu.trace._assemble(spans)}
    flagged = ray_tpu.trace.stragglers(trace)
    assert [f["span_id"] for f in flagged] == ["slow"]
    assert flagged[0]["node_id"] == "n9"
    assert flagged[0]["worker_id"] == "w9"
    # 3 siblings is below the minimum group size: nothing flagged
    small = [_span("r", None, "root", 0.0, 2.0)] + kids[:2] + [spans[-1]]
    trace2 = {"trace_id": "t1", "spans": small,
              "roots": ray_tpu.trace._assemble(small)}
    assert ray_tpu.trace.stragglers(trace2) == []


def test_summarize_tasks_failed_cancelled_get_own_column(monkeypatch):
    from ray_tpu.util import state as state_api

    events = [
        {"task_id": "a", "state": "RUNNING", "name": "f", "ts": 1.0},
        {"task_id": "a", "state": "FINISHED", "name": "f", "ts": 2.0},
        {"task_id": "b", "state": "RUNNING", "name": "f", "ts": 1.0},
        {"task_id": "b", "state": "FAILED", "name": "f", "ts": 4.0},
        {"task_id": "c", "state": "RUNNING", "name": "f", "ts": 1.0},
        {"task_id": "c", "state": "CANCELLED", "name": "f", "ts": 1.5},
    ]
    monkeypatch.setattr(
        state_api, "_gcs_call", lambda *a, **k: events
    )
    out = state_api.summarize_tasks()
    entry = out["f"]
    # terminal states each counted, CANCELLED no longer collapses to RUNNING
    assert entry["FINISHED"] == 1
    assert entry["FAILED"] == 1
    assert entry["CANCELLED"] == 1
    # success durations unpolluted; failures get their own distribution
    assert entry["duration"]["count"] == 1
    assert entry["duration"]["mean_s"] == pytest.approx(1.0)
    assert entry["failed_duration"]["count"] == 2
    assert entry["failed_duration"]["mean_s"] == pytest.approx(1.75)
