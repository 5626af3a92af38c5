"""Typed, env-overridable config registry.

Mirrors the reference's RayConfig design (reference: src/ray/common/ray_config_def.h,
ray_config.h:67-74): every entry has a typed default, can be overridden by an
environment variable ``RAYTPU_<NAME>``, and can be overridden programmatically via a
``_system_config`` dict passed to ``ray_tpu.init``.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict

_ENV_PREFIX = "RAYTPU_"


def _coerce(value: str, default: Any) -> Any:
    if isinstance(default, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    if isinstance(default, (list, dict)):
        return json.loads(value)
    return value


class _Config:
    _DEFAULTS: Dict[str, Any] = {
        # --- object store ---
        "object_store_memory_bytes": 2 * 1024**3,
        "object_store_inline_max_bytes": 100 * 1024,  # small results returned inline
        "object_store_native": True,  # use the C++ shm allocator when built
        # fallocate the shm arena up front so big puts don't pay
        # allocate+zero page faults on first touch
        "object_store_prealloc": True,
        "object_spilling_enabled": True,
        "object_spilling_dir": "",
        "object_store_full_retry_s": 10.0,
        # --- scheduling ---
        "worker_lease_timeout_s": 30.0,
        # concurrent worker startups per raylet: overlaps interpreter boot
        # (reference: worker_pool.h maximum_startup_concurrency). Forked
        # workers cost ~10ms each, so a deeper pipeline keeps the core busy
        # during the RPC-bound parts of worker registration.
        "worker_spawn_parallelism": 12,
        "worker_pool_prestart": 0,
        # max normal tasks pipelined to one leased worker in a single frame
        # (reference: backlog-driven pipelined submission,
        # direct_task_transport.cc:346)
        "task_push_batch": 64,
        # fork workers from a pre-imported template process instead of
        # booting a fresh interpreter (~2s import cost) per worker
        # (reference: worker prestart/startup concurrency, worker_pool.h:167)
        "worker_forkserver": True,
        "worker_idle_timeout_s": 60.0,
        "max_workers_per_node": 64,
        "scheduler_spread_threshold": 0.5,
        "scheduler_top_k_fraction": 0.2,
        # --- memory monitor (reference: memory_monitor.h:52 +
        # worker_killing_policy*.cc) ---
        "memory_monitor_enabled": True,
        # kill workers when node memory usage exceeds this fraction
        "memory_usage_threshold": 0.95,
        "memory_monitor_period_s": 1.0,
        # --- health / fault tolerance ---
        "health_check_period_s": 1.0,
        # gray-failure detection: a node whose heartbeats arrive but whose
        # self-probes (peer data-plane pings + local store health) fail is
        # DEGRADED — drained of new leases — and escalates to DEAD if it
        # does not recover within this window
        "degraded_window_s": 10.0,
        "chaos_probe_period_s": 2.0,
        "probe_timeout_s": 1.0,
        "probe_failure_threshold": 2,
        # GCS->raylet resource-view gossip cadence (the ray_syncer
        # rebroadcast half); raylets spill from this cache when it is
        # younger than 3 periods
        "resource_broadcast_period_s": 0.5,
        "health_check_failure_threshold": 5,
        "task_max_retries_default": 3,
        "actor_max_restarts_default": 0,
        "lineage_max_resubmits": 3,  # per-object lineage re-executions
        "actor_max_inflight": 256,  # pipelined calls per (caller, actor)
        "gcs_rpc_timeout_s": 30.0,
        # sqlite file for GCS table persistence ("" = in-memory only);
        # a restarted GCS replays KV/jobs/actors/PGs from it
        "gcs_persistence_path": "",
        # --- rpc ---
        "rpc_connect_timeout_s": 10.0,
        # idempotency-classified client retry: read-only/idempotent methods
        # retry across reconnects with capped exponential backoff + full
        # jitter; non-idempotent methods fail fast (NonIdempotentRpcError)
        "rpc_retry_max_attempts": 3,
        "rpc_retry_backoff_base_s": 0.05,
        "rpc_retry_backoff_cap_s": 2.0,
        # default deadline for call_async callback slots: a peer that hangs
        # without closing can no longer pin slots forever (0 disables)
        "rpc_async_call_timeout_s": 120.0,
        # cap for the raylet->GCS heartbeat reconnect backoff (full jitter,
        # doubling from half the heartbeat period) so a GCS restart doesn't
        # see a synchronized re-registration stampede
        "heartbeat_reconnect_backoff_cap_s": 10.0,
        # dead-peer detection for sends is byte-based, not time-based: a
        # connection whose unflushed send buffer exceeds
        # 2 * rpc_max_frame_bytes is torn down (rpc._SendState._buffer)
        "rpc_max_frame_bytes": 512 * 1024**2,
        # dispatch pool size per RpcServer: large enough that long-poll
        # handlers (store gets, lease waits) cannot starve control traffic
        "rpc_dispatch_threads": 128,
        # C++ transport (native/rpc_core.cc): epoll + frame reassembly +
        # buffered sends without the GIL; falls back to the pure-Python
        # poller when the lib can't build (RAYTPU_RPC_NATIVE_TRANSPORT=0
        # forces the fallback)
        "rpc_native_transport": True,
        # same-process fast path: clients constructed with prefer_local
        # deliver frames straight into the target server's dispatch,
        # skipping the socket (phase stats record them under side=local)
        "rpc_local_fastpath": True,
        # Nagle-style outbound coalescing for latency-tolerant small
        # frames (async requests, notify pushes): frames queue per
        # connection and flush as ONE write when the next immediate send
        # drains them, the queued bytes/frames cross these thresholds, or
        # the armed flush job runs — whichever happens first
        "rpc_coalesce": True,
        "rpc_coalesce_flush_bytes": 64 * 1024,
        "rpc_coalesce_max_frames": 128,
        # frames larger than this are never held back by the coalescer
        "rpc_coalesce_max_frame_bytes": 32 * 1024,
        # grant-ahead window for worker leases: one request_worker_lease
        # round-trip may return up to this many already-idle workers when
        # the caller's queue is deep (extras park in the idle-lease cache)
        "lease_grant_window": 8,
        # --- task events / observability ---
        "task_events_enabled": True,
        "log_to_driver": True,  # stream worker stdout/stderr to the driver
        # head-based trace sampling rate in [0, 1] for the distributed
        # tracing plane (_private/trace.py): 0 disables the plane entirely
        # (hot-path hooks cost one attribute read); > 0 mints a TraceContext
        # at driver submit / serve ingress and samples that fraction of
        # traces. Task errors force-record their span regardless.
        "trace_sample": 0.0,
        "task_events_buffer_size": 100_000,
        "metrics_report_period_s": 5.0,
        # --- metrics time-series retention + SLO plane (gcs + metrics_ts) ---
        # fine ring: one cluster-aggregated sample per report period
        "metrics_ts_fine_samples": 360,
        # coarse ring keeps every Nth fold for the long horizon
        "metrics_ts_coarse_every": 12,
        "metrics_ts_coarse_samples": 720,
        # hard cap on distinct (metric, series) rings; overflow is counted
        # in ray_tpu_metrics_ts_dropped_series_total, not retained
        "metrics_ts_max_series": 2000,
        # a reporter idle longer than this makes its series STALE for SLO
        # evaluation (alerts hold state instead of flapping); 0 = auto
        # (3 x metrics_report_period_s). Reporters idle > 12 periods are
        # pruned entirely, with counters folded into the tombstone
        # accumulator so cluster totals stay monotonic.
        "metrics_stale_after_s": 0.0,
        # serve: define default per-deployment latency/availability SLO
        # rules at deploy time (targets generous enough to stay silent on
        # a healthy deployment; override per deployment via slo_p99_s /
        # slo_availability in the @serve.deployment config)
        "serve_default_slos": True,
        "serve_slo_default_p99_s": 60.0,
        "serve_slo_default_availability": 0.9,
        # --- SLO controller (controller.py, hosted in the GCS) ---
        # disabled by default: no reconcile thread is started and the hot
        # paths carry zero controller hooks, so the overhead budget gates
        # are unaffected until an operator opts in
        "controller_enabled": False,
        "controller_period_s": 2.0,
        "log_dir": "",
        # --- TPU topology ---
        "tpu_slice_gang_scheduling": True,
        "tpu_topology_env": "",  # override detected topology, e.g. "v5e-8"
        # --- train ---
        "train_heartbeat_period_s": 5.0,
        # --- collectives ---
        # end-to-end deadline for one collective op (was hardcoded 120 s)
        "collective_timeout_s": 120.0,
        # ring-backend groups fall back to the rendezvous actor below this
        # tensor size: chunking overhead beats the star only once the
        # payload amortizes the per-chunk put/pull round trips
        "collective_ring_min_bytes": 64 * 1024,
        # elements per scale block for quantized allreduce (EQuARX-style)
        "collective_quantize_block": 256,
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, Any] = {}
        self._load_env()

    def _load_env(self):
        for name, default in self._DEFAULTS.items():
            env = os.environ.get(_ENV_PREFIX + name.upper())
            if env is not None:
                self._values[name] = _coerce(env, default)

    def refresh_from_env(self):
        """Re-read RAYTPU_* env overrides. Needed by fork-server workers:
        the template imported this module (snapshotting os.environ) long
        before the per-fork env — including runtime_env env_vars — was
        applied in the child, so Popen-spawned and forked workers would
        otherwise honor different configs for the same runtime_env."""
        with self._lock:
            self._load_env()

    def initialize(self, system_config: Dict[str, Any] | None):
        """Apply a _system_config dict (wins over env)."""
        if not system_config:
            return
        with self._lock:
            for k, v in system_config.items():
                if k not in self._DEFAULTS:
                    raise ValueError(f"Unknown config entry: {k}")
                self._values[k] = v

    def apply_cluster(self, cluster_config: Dict[str, Any]):
        """Adopt the cluster-wide config (the head's GlobalConfig.dump()).
        Local env overrides (RAYTPU_*) keep precedence; otherwise any
        value the head changed from its default applies here too — this
        is how a driver's _system_config reaches worker processes."""
        with self._lock:
            for k, v in cluster_config.items():
                if k not in self._DEFAULTS:
                    continue  # newer head, older worker: skip unknown keys
                if k in self._values:
                    continue  # env/local override wins
                if v != self._DEFAULTS[k]:
                    self._values[k] = v

    def get(self, name: str) -> Any:
        with self._lock:
            if name in self._values:
                return self._values[name]
        try:
            return self._DEFAULTS[name]
        except KeyError:
            raise ValueError(f"Unknown config entry: {name}") from None

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        return self.get(name)

    def dump(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._DEFAULTS)
            out.update(self._values)
            return out


GlobalConfig = _Config()
