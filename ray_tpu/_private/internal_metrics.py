"""Always-on runtime instrumentation: internal ``ray_tpu_*`` metrics.

The reference runtime ships ~100 built-in Prometheus metrics (scheduler
queue depths, object-store usage, serve QPS — reference:
src/ray/stats/metric_defs.cc + dashboard/modules/metrics/). Here the
runtime's hot paths report through the same process-local registry user
code uses (``ray_tpu.util.metrics``), under a reserved ``ray_tpu_``
namespace, so one reporter thread, one GCS aggregation path, and one
``/metrics`` exposition endpoint serve both.

Design constraints:

- **Lazy + idempotent**: metric objects are created on first touch per
  process (workers, drivers, and the head's in-process raylet each get
  their own instance; the GCS merges by reporter key). Importing this
  module costs nothing — no registry entries, no reporter thread.
- **Never throws on the hot path**: the ``inc``/``observe``/``set_gauge``
  helpers swallow everything. A metrics bug must not fail a task push.
- **Catalog-driven**: every family is declared once in ``CATALOG`` so the
  docs table, the dashboard, and the tests share one source of truth.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

#: latency boundaries tuned for RPC-scale (sub-ms) through task-scale (s)
LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)

#: name -> (type, description, tag_keys)
CATALOG: Dict[str, Tuple[str, str, Tuple[str, ...]]] = {
    # -- core worker / task lifecycle ---------------------------------
    "ray_tpu_tasks_submitted_total": (
        "counter", "tasks submitted by this process (normal tasks)", ()),
    "ray_tpu_tasks_finished_total": (
        "counter", "task replies received with status=ok", ()),
    "ray_tpu_tasks_failed_total": (
        "counter", "tasks that terminally failed (after retries)", ()),
    "ray_tpu_task_submit_latency_seconds": (
        "histogram", "submit_task() wall time (serialize + route/push)", ()),
    "ray_tpu_tasks_executed_total": (
        "counter", "tasks executed on this worker", ("kind",)),
    "ray_tpu_task_exec_latency_seconds": (
        "histogram", "user-function execution wall time", ("kind",)),
    # -- raylet / scheduler -------------------------------------------
    "ray_tpu_scheduler_queue_depth": (
        "gauge", "lease requests parked in the raylet's wait loop", ()),
    "ray_tpu_worker_pool_size": (
        "gauge", "workers registered with this raylet", ()),
    "ray_tpu_workers_idle": (
        "gauge", "registered workers currently idle in the pool", ()),
    "ray_tpu_worker_leases_granted_total": (
        "counter", "worker leases granted by this raylet", ()),
    # -- object store -------------------------------------------------
    "ray_tpu_object_store_objects": (
        "gauge", "objects resident in the local plasma store", ()),
    "ray_tpu_object_store_allocated_bytes": (
        "gauge", "bytes allocated in the local plasma arena", ()),
    "ray_tpu_object_store_bytes_written_total": (
        "counter", "bytes of new objects created in the local store", ()),
    "ray_tpu_object_store_spills_total": (
        "counter", "objects spilled to disk under memory pressure", ()),
    "ray_tpu_object_store_spilled_bytes_total": (
        "counter", "bytes spilled to disk under memory pressure", ()),
    "ray_tpu_object_store_inplace_writes_total": (
        "counter",
        "large puts serialized directly into the reserved plasma region "
        "(reserve→serialize-in-place→seal path)", ()),
    # -- device plane / collectives -----------------------------------
    "ray_tpu_device_transfer_bytes_total": (
        "counter", "device plane DMA volume", ("direction",)),
    "ray_tpu_device_transfer_seconds_total": (
        "counter", "wall time spent in device plane DMA", ("direction",)),
    "ray_tpu_device_duty_cycle": (
        "gauge", "fraction of the last step spent in device transfers", ()),
    "ray_tpu_collective_ops_total": (
        "counter", "collective operations issued from this process", ("op",)),
    "ray_tpu_collective_bytes_total": (
        "counter", "bytes contributed to collectives", ("op",)),
    "ray_tpu_collective_latency_seconds": (
        "histogram", "collective op wall time (rendezvous round trip)", ("op",)),
    "ray_tpu_collective_duty_cycle": (
        "gauge", "fraction of the last step spent inside collectives", ()),
    "ray_tpu_collective_ring_chunks_total": (
        "counter", "shard chunks sealed by the ring backend", ("op",)),
    "ray_tpu_collective_chunk_retries_total": (
        "counter", "ring chunk pulls retried (peer not sealed yet / drop)",
        ("op",)),
    "ray_tpu_collective_throughput_gbps": (
        "gauge", "wire throughput of the last collective op", ("op", "backend")),
    "ray_tpu_collective_quantized_bytes_total": (
        "counter", "quantized payload bytes moved by collectives", ("op",)),
    "ray_tpu_train_sharded_update_seconds": (
        "histogram", "sharded weight-update phase wall time", ("phase",)),
    "ray_tpu_train_optimizer_state_bytes": (
        "gauge", "per-rank optimizer state footprint", ("mode",)),
    # -- serve --------------------------------------------------------
    "ray_tpu_serve_requests_total": (
        "counter", "requests handled by replicas", ("deployment",)),
    "ray_tpu_serve_request_latency_seconds": (
        "histogram", "replica request handling wall time", ("deployment",)),
    "ray_tpu_serve_request_errors_total": (
        "counter", "requests that raised inside the replica handler",
        ("deployment",)),
    "ray_tpu_serve_queue_depth": (
        "gauge", "in-flight requests on the replica", ("deployment",)),
    "ray_tpu_serve_proxy_requests_total": (
        "counter", "HTTP requests through the ingress proxy", ("route", "status")),
    "ray_tpu_serve_proxy_latency_seconds": (
        "histogram", "end-to-end HTTP request latency at the proxy", ("route",)),
    "ray_tpu_serve_dag_node_latency_seconds": (
        "histogram", "per-node latency inside DAGDriver graphs",
        ("deployment", "method")),
    "ray_tpu_serve_batch_steps_total": (
        "counter",
        "batch executions per batcher (mode=static|continuous; avg batch "
        "size = items/steps)",
        ("fn", "mode")),
    "ray_tpu_serve_batch_items_total": (
        "counter", "requests executed inside batches (mode=static|continuous)",
        ("fn", "mode")),
    "ray_tpu_serve_sheds_total": (
        "counter",
        "requests shed by admission control (where=handle|proxy)",
        ("deployment", "where")),
    "ray_tpu_serve_proxy_inflight": (
        "gauge", "requests currently admitted into the ingress proxy", ()),
    "ray_tpu_serve_mux_cache_events_total": (
        "counter",
        "multiplex model-cache events (event=hit|miss|evict)",
        ("loader", "event")),
    "ray_tpu_serve_mux_models_resident": (
        "gauge", "models resident in a replica's multiplex LRU", ("loader",)),
    "ray_tpu_serve_mux_load_seconds": (
        "histogram",
        "multiplex model load wall time (object-plane weight streaming)",
        ("loader",)),
    "ray_tpu_serve_replica_drains_total": (
        "counter",
        "replicas drained on scale-down (outcome=graceful|forced)",
        ("outcome",)),
    # -- llm serving --------------------------------------------------
    "ray_tpu_llm_kv_blocks_in_use": (
        "gauge",
        "paged KV-cache blocks currently referenced (active sequences + "
        "prefix cache)",
        ("deployment",)),
    "ray_tpu_llm_prefix_cache_hits_total": (
        "counter",
        "prompt blocks served from the prefix cache (prefill FLOPs skipped)",
        ("deployment",)),
    "ray_tpu_llm_prefill_tokens_total": (
        "counter", "prompt tokens run through bucketed prefill",
        ("deployment",)),
    "ray_tpu_llm_ttft_seconds": (
        "histogram", "time from enqueue to a request's first sampled token",
        ("deployment",)),
    "ray_tpu_llm_queue_seconds": (
        "histogram",
        "time from enqueue to admission into the engine's batch with the "
        "request's KV blocks (the part of ttft spent waiting, not prefilling)",
        ("deployment",)),
    # -- rpc ----------------------------------------------------------
    "ray_tpu_rpc_pump_failures": (
        "counter", "native poller pump-thread crashes (streams torn down)", ()),
    "ray_tpu_rpc_coalesced_frames_total": (
        "counter",
        "small outbound frames that left the coalescer as part of a "
        "multi-frame write (one syscall carrying several logical calls)",
        ()),
    "ray_tpu_rpc_local_calls_total": (
        "counter",
        "RPCs served over the same-process fast path (no socket; phase "
        "stats record these under side=local)",
        ()),
    "ray_tpu_rpc_phase_seconds": (
        "histogram",
        "per-phase RPC latency (client: serialize/send/wire/deserialize/"
        "total; server: deserialize/queue/handler/reply) — exported by the "
        "perf plane's ring/bucket accumulators, not Metric.observe",
        ("method", "phase", "side")),
    # -- tracing plane ------------------------------------------------
    "ray_tpu_trace_spans_total": (
        "counter",
        "spans recorded into this process's trace ring "
        "(kind=task|rpc|object|collective|server|driver|internal)",
        ("kind",)),
    "ray_tpu_trace_traces_started_total": (
        "counter",
        "traces minted by this process's head-based sampler "
        "(driver submit roots + serve ingress requests)",
        ()),
    "ray_tpu_trace_spans_dropped": (
        "gauge",
        "spans overwritten in this process's trace ring before harvest",
        ()),
    # -- perf plane ---------------------------------------------------
    "ray_tpu_perf_profile_runs_total": (
        "counter", "sampling-profiler runs executed in this process", ()),
    "ray_tpu_perf_profile_samples_total": (
        "counter", "stack samples collected by the sampling profiler", ()),
    # -- state API ----------------------------------------------------
    "ray_tpu_state_api_node_errors": (
        "counter",
        "per-node raylet failures during cluster-wide state listings "
        "(partial results)",
        ("api",)),
    # -- chaos / fault tolerance --------------------------------------
    "ray_tpu_chaos_injected_faults_total": (
        "counter",
        "faults injected by an armed chaos schedule in this process",
        ("action",)),
    "ray_tpu_rpc_retries_total": (
        "counter",
        "idempotent RPC calls retried after a reconnect or timeout",
        ("method",)),
    "ray_tpu_node_degraded": (
        "gauge",
        "nodes currently in the DEGRADED gray-failure state (GCS view)",
        ()),
    # -- metrics time-series + SLO plane ------------------------------
    "ray_tpu_alerts_firing": (
        "gauge", "SLO alert rules currently in the FIRING state", ()),
    "ray_tpu_metrics_ts_series": (
        "gauge",
        "distinct (metric, series) rings retained by the GCS time-series "
        "store",
        ()),
    "ray_tpu_metrics_ts_dropped_series_total": (
        "counter",
        "new series rejected by the metrics_ts_max_series cap (history "
        "not retained)",
        ()),
    # -- SLO controller -----------------------------------------------
    "ray_tpu_controller_actions_total": (
        "counter",
        "control actions taken by the SLO controller "
        "(action=scale_up|scale_down|drain_node|reroute, "
        "outcome=applied|failed|skipped)",
        ("action", "outcome")),
    "ray_tpu_controller_reconciles_total": (
        "counter", "SLO controller reconcile loop iterations", ()),
    # -- scale simulation ---------------------------------------------
    "ray_tpu_sim_virtual_nodes": (
        "gauge", "virtual nodes currently alive in an in-process sim", ()),
    "ray_tpu_sim_requests_total": (
        "counter",
        "requests driven through a scale sim (workload=serve|train|rollout)",
        ("workload",)),
    # -- cancellation / graceful drain --------------------------------
    "ray_tpu_tasks_cancelled_total": (
        "counter",
        "tasks cancelled via ray_tpu.cancel (mode=cooperative|force)",
        ("mode",)),
    "ray_tpu_node_drains_total": (
        "counter",
        "graceful node drains by outcome (completed|forced|failed)",
        ("outcome",)),
    "ray_tpu_drain_migrated_objects_total": (
        "counter",
        "primary plasma objects re-replicated to peers during a drain",
        ()),
    "ray_tpu_lineage_reconstructions_total": (
        "counter",
        "tasks re-submitted through lineage to reconstruct lost objects",
        ()),
}

_lock = threading.Lock()
_metrics: Dict[str, Any] = {}


def get(name: str):
    """The process-local metric object for a catalog family (lazy)."""
    m = _metrics.get(name)
    if m is not None:
        return m
    with _lock:
        m = _metrics.get(name)
        if m is None:
            from ray_tpu.util import metrics as user_metrics

            kind, desc, tag_keys = CATALOG[name]
            if kind == "counter":
                m = user_metrics.Counter(name, desc, tag_keys=tag_keys)
            elif kind == "gauge":
                m = user_metrics.Gauge(name, desc, tag_keys=tag_keys)
            else:
                m = user_metrics.Histogram(
                    name, desc, boundaries=LATENCY_BUCKETS, tag_keys=tag_keys
                )
            _metrics[name] = m
    return m


# -- hot-path helpers: cheap, and never let metrics break the runtime --


def inc(name: str, value: float = 1.0,
        tags: Optional[Dict[str, str]] = None) -> None:
    try:
        get(name).inc(value, tags=tags)
    except Exception:
        pass


def observe(name: str, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
    try:
        get(name).observe(value, tags=tags)
    except Exception:
        pass


def set_gauge(name: str, value: float,
              tags: Optional[Dict[str, str]] = None) -> None:
    try:
        get(name).set(value, tags=tags)
    except Exception:
        pass


# -- pre-bound series handles ------------------------------------------
#
# ``inc(name, tags={...})`` builds a dict, merges it with default tags and
# sorts the items — per call. Hot paths (task execution, rpc retries)
# resolve the series ONCE via these helpers and keep the returned handle:
# its inc()/observe() is lock + add, nothing else.


class _NullBound:
    __slots__ = ()

    def inc(self, value: float = 1.0) -> None:
        pass

    def observe(self, value: float) -> None:
        pass


_NULL_BOUND = _NullBound()


def bound_counter(name: str, tags: Optional[Dict[str, str]] = None):
    """Allocation-free counter handle for a fixed (family, tags) series.
    Never raises: falls back to a no-op handle on any error."""
    try:
        return get(name).bind(tags)
    except Exception:
        return _NULL_BOUND


def bound_histogram(name: str, tags: Optional[Dict[str, str]] = None):
    """Allocation-free histogram handle (see ``bound_counter``)."""
    try:
        return get(name).bind(tags)
    except Exception:
        return _NULL_BOUND
