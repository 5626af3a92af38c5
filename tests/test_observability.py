"""Observability: user metrics + worker-log streaming to the driver.

(reference: ray.util.metrics Counter/Gauge/Histogram + _private/
log_monitor.py streaming worker stdout through GCS pubsub)
"""

import json
import time

import pytest

import ray_tpu


def test_metrics_counter_gauge_histogram(ray_start_regular):
    from ray_tpu.util import metrics

    c = metrics.Counter("test_requests", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = metrics.Gauge("test_depth", "queue depth")
    g.set(7.0)
    h = metrics.Histogram(
        "test_latency", "latency", boundaries=(0.1, 1.0), tag_keys=()
    )
    h.observe(0.05)
    h.observe(0.5)
    h.observe(3.0)

    recs = {r["name"]: r for r in metrics.get_metrics()}
    series = recs["test_requests"]["series"]
    assert series[(("route", "/a"),)] == 3.0
    assert series[(("route", "/b"),)] == 1.0
    assert recs["test_depth"]["series"][()] == 7.0
    hist = recs["test_latency"]["series"][()]
    assert hist["buckets"] == [1, 1, 1] and hist["count"] == 3

    text = metrics.prometheus_text()
    assert 'test_requests{route="/a"} 3.0' in text
    assert "test_latency_bucket" in text and 'le="+Inf"' in text

    with pytest.raises(ValueError):
        c.inc(tags={"bogus": "x"})
    with pytest.raises(ValueError):
        c.inc(-1)


def test_metrics_aggregate_across_workers(ray_start_regular):
    from ray_tpu.util import metrics

    @ray_tpu.remote
    def work():
        from ray_tpu.util import metrics as m

        cnt = m.Counter("test_cross_proc", "x")
        cnt.inc(5.0)
        m.flush()
        return True

    assert ray_tpu.get([work.remote(), work.remote()], timeout=60) == [True, True]
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        recs = {r["name"]: r for r in metrics.get_metrics("test_cross_proc")}
        if recs and sum(recs["test_cross_proc"]["series"].values()) >= 10.0:
            break
        time.sleep(0.3)
    # two worker processes each reported a cumulative 5.0 -> sum 10
    assert sum(recs["test_cross_proc"]["series"].values()) == 10.0


def test_worker_logs_stream_to_driver(ray_start_regular):
    @ray_tpu.remote
    def chatty():
        print("hello from the worker side")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    core = ray_start_regular.core
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if any(
            "hello from the worker side" in line
            for _, line in list(core.captured_logs)
        ):
            return
        time.sleep(0.3)
    pytest.fail(f"worker print never reached the driver: {list(core.captured_logs)[:5]}")


def test_internal_metrics_after_workload(ray_start_regular):
    """The runtime instruments itself: after a plain workload (10 tasks +
    an object-store put + 5 serve requests) the ray_tpu_* internal metric
    families are present in the Prometheus exposition with no opt-in."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.util import metrics

    @ray_tpu.remote
    def unit(i):
        return i * 2

    try:
        assert ray_tpu.get(
            [unit.remote(i) for i in range(10)], timeout=60
        ) == [2 * i for i in range(10)]
        # >100KB put goes through plasma -> object-store counters move
        ref = ray_tpu.put(np.zeros(64 * 1024, dtype=np.float64))
        assert ray_tpu.get(ref, timeout=30).shape == (64 * 1024,)

        @serve.deployment
        class Echo:
            def __call__(self, x):
                return x

        handle = serve.run(Echo.bind())
        assert [
            handle.remote(i).result(timeout=30) for i in range(5)
        ] == list(range(5))

        # worker/replica-side metrics arrive with their processes' periodic
        # flush (metrics_report_period_s = 5s): poll the merged view
        want = {
            "ray_tpu_tasks_submitted_total",
            "ray_tpu_tasks_finished_total",
            "ray_tpu_task_submit_latency_seconds",
            "ray_tpu_tasks_executed_total",
            "ray_tpu_task_exec_latency_seconds",
            "ray_tpu_worker_pool_size",
            "ray_tpu_worker_leases_granted_total",
            "ray_tpu_object_store_bytes_written_total",
            "ray_tpu_serve_requests_total",
            "ray_tpu_serve_request_latency_seconds",
        }
        deadline = time.monotonic() + 25
        while time.monotonic() < deadline:
            recs = {r["name"]: r for r in metrics.get_metrics()}
            if want <= set(recs):
                break
            time.sleep(0.5)
        missing = want - set(recs)
        assert not missing, f"missing internal metrics: {missing}"

        finished = recs["ray_tpu_tasks_finished_total"]["series"]
        assert sum(finished.values()) > 0
        qps = recs["ray_tpu_serve_requests_total"]["series"]
        assert sum(qps.values()) >= 5
        lat = recs["ray_tpu_serve_request_latency_seconds"]["series"]
        assert sum(h["count"] for h in lat.values()) >= 5

        text = metrics.prometheus_text()
        families = {
            name
            for name in set(recs)
            if name.startswith("ray_tpu_") and name in text
        }
        assert len(families) >= 8, sorted(families)
    finally:
        serve.shutdown()


def test_timeline_always_on(ray_start_regular, tmp_path):
    """ray_tpu.timeline() works with no opt-in: every
    executed task shows up as a chrome-trace slice, laid out one pid lane
    per node / one tid per worker."""

    @ray_tpu.remote
    def traced(i):
        time.sleep(0.01)
        return i

    assert ray_tpu.get(
        [traced.remote(i) for i in range(10)], timeout=60
    ) == list(range(10))
    out = str(tmp_path / "timeline.json")
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        events = ray_tpu.timeline(out)
        slices = [
            e for e in events if e["ph"] == "X" and e["name"] == "traced"
        ]
        if len(slices) >= 10:
            break
        time.sleep(0.3)
    assert len(slices) >= 10, events
    # lanes: pid per node, tid per worker
    assert all(str(e["pid"]).startswith("node:") for e in slices)
    assert all(str(e["tid"]).startswith("worker:") for e in slices)
    dumped = json.load(open(out))
    assert len(dumped) >= 10  # valid chrome-trace JSON, round-tripped


def test_list_cluster_events_node_up(ray_start_regular):
    """The structured cluster event log surfaces the head node's
    registration without any setup."""
    from ray_tpu.util.state import list_cluster_events

    events = list_cluster_events()
    assert len(events) >= 1
    node_added = [e for e in events if e["type"] == "NODE_ADDED"]
    assert node_added, events
    ev = node_added[0]
    assert ev["severity"] == "INFO"
    assert ev["node_id"]
    assert ev["ts"] > 0
    assert "registered" in ev["message"]
    # server-side filtering
    assert all(
        e["type"] == "NODE_ADDED"
        for e in list_cluster_events(type="NODE_ADDED")
    )

