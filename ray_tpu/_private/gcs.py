"""GCS: the cluster metadata authority.

Hosts node membership + health, the actor table and its fault-tolerance state
machine, the internal KV (also the function/class export table), pubsub, and
job state (reference: src/ray/gcs/gcs_server/ — GcsActorManager restart logic
at gcs_actor_manager.cc:1100, GcsHealthCheckManager, GcsKvManager).

Runs as an RpcServer inside the head node process. Raylets register and
heartbeat; actor creation leases workers from raylets exactly like normal
tasks (the reference's ScheduleByRaylet default, gcs_actor_scheduler.h:355).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ActorID, NodeID, PlacementGroupID, WorkerID
from ray_tpu._private.rpc import RpcClient, RpcServer, ServerConn
from ray_tpu._private import metrics_ts
from ray_tpu._private import trace as _trace

logger = logging.getLogger(__name__)

# Actor lifecycle states (reference: gcs.proto ActorTableData.ActorState)
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"

# Placement group states (reference: gcs.proto PlacementGroupTableData)
PG_PENDING = "PENDING"
PG_CREATED = "CREATED"
PG_REMOVED = "REMOVED"
PG_RESCHEDULING = "RESCHEDULING"


class PlacementGroupInfo:
    def __init__(self, pg_id: PlacementGroupID, spec: Dict[str, Any]):
        self.pg_id = pg_id
        self.spec = spec  # {bundles: [ {res:amount} ], strategy, name, label_equal}
        self.state = PG_PENDING
        self.bundle_nodes: List[Optional[NodeID]] = [None] * len(spec["bundles"])
        self.failure: Optional[str] = None

    def public_view(self) -> Dict[str, Any]:
        return {
            "placement_group_id": self.pg_id,
            "name": self.spec.get("name", ""),
            "strategy": self.spec["strategy"],
            "bundles": self.spec["bundles"],
            "state": self.state,
            "bundle_nodes": list(self.bundle_nodes),
            "failure": self.failure,
        }


class ActorInfo:
    def __init__(self, actor_id: ActorID, spec: Dict[str, Any]):
        self.actor_id = actor_id
        self.spec = spec  # creation spec: serialized class, args, options
        self.state = PENDING_CREATION
        self.address: Optional[Tuple[str, int]] = None
        self.node_id: Optional[NodeID] = None
        self.worker_id: Optional[WorkerID] = None
        self.num_restarts = 0
        self.max_restarts = spec["options"].get("max_restarts", 0)
        self.name = spec["options"].get("name")
        self.death_cause: Optional[str] = None

    def public_view(self) -> Dict[str, Any]:
        return {
            "actor_id": self.actor_id,
            "state": self.state,
            "address": self.address,
            "node_id": self.node_id,
            "num_restarts": self.num_restarts,
            "max_restarts": self.max_restarts,
            "name": self.name,
            "death_cause": self.death_cause,
            "class_name": self.spec.get("class_name", ""),
            "max_concurrency": self.spec["options"].get("max_concurrency", 1),
        }


class NodeInfo:
    def __init__(self, node_id: NodeID, address: Tuple[str, int], resources: Dict[str, float], labels: Dict[str, str]):
        self.node_id = node_id
        self.address = address  # raylet rpc address
        self.total_resources = dict(resources)
        self.available_resources = dict(resources)
        self.labels = labels
        self.alive = True
        # gray-failure lifecycle: ALIVE -> DEGRADED (heartbeats arrive but
        # self-probes fail) -> back to ALIVE, or escalation to DEAD after
        # degraded_window_s. ``alive`` stays True while DEGRADED — the node
        # is drained of new leases, not declared lost.
        self.state = "ALIVE"
        self.degraded_since: Optional[float] = None
        self.probes: Dict[str, Any] = {}
        self.last_heartbeat = time.monotonic()
        self.store_path: str = labels.get("store_path", "")
        self.store_capacity: int = int(labels.get("store_capacity", "0"))
        self.pending_demand: List[Dict[str, float]] = []


class GcsServer:
    # heartbeats must never queue behind long-poll handlers (wait_for_actor
    # etc. can park the dispatch pool): they run inline on the read loop,
    # which is safe because they only touch _lock briefly
    RPC_INLINE = ("heartbeat",)

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        persistence_path: Optional[str] = None,
    ):
        from concurrent.futures import ThreadPoolExecutor

        # optional sqlite persistence (the Redis-equivalent;
        # gcs_storage.py): a restarted GCS replays KV/jobs/actors/PGs and
        # raylets re-register via their heartbeat reconnect
        self._storage = None
        if persistence_path or GlobalConfig.gcs_persistence_path:
            from ray_tpu._private.gcs_storage import GcsStorage

            self._storage = GcsStorage(
                persistence_path or GlobalConfig.gcs_persistence_path
            )

        self.server = RpcServer("gcs", host, port)
        _trace.init_from_config()
        self._lock = threading.Condition(threading.RLock())
        # bounded executors for actor/pg scheduling (a thread per schedule
        # would mean 10k threads at the reference's 10k-actor envelope);
        # separate pools because actors may wait on pg commits. Sized to
        # the host: 16 threads on a 1-core box is GIL contention, not
        # parallelism (SCALE_r04 thread census finding)
        sched_threads = min(16, max(4, (os.cpu_count() or 1) * 4))
        self._actor_sched_pool = ThreadPoolExecutor(
            max_workers=sched_threads, thread_name_prefix="gcs-actor-sched"
        )
        self._pg_sched_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="gcs-pg-sched"
        )
        self._kv: Dict[str, Dict[str, bytes]] = {}  # namespace -> key -> value
        self._nodes: Dict[NodeID, NodeInfo] = {}
        self._actors: Dict[ActorID, ActorInfo] = {}
        self._named_actors: Dict[str, ActorID] = {}
        self._jobs: Dict[str, Dict[str, Any]] = {}
        self._pgs: Dict[PlacementGroupID, PlacementGroupInfo] = {}
        self._subscribers: Dict[str, List[ServerConn]] = {}
        self._raylet_clients: Dict[NodeID, RpcClient] = {}
        # graceful drain: object migration maps stashed by the drain
        # orchestrator (node -> {oid binary: new (host, port)}), consumed
        # by unregister's "nodes removed" publish so owners rewrite
        # locations instead of declaring the objects lost
        self._drain_migrations: Dict[NodeID, Dict[bytes, Tuple[str, int]]] = {}
        # pooled GCS->worker connections for create_actor (LRU-bounded;
        # entries invalidate on call failure)
        from collections import OrderedDict as _OD

        self._worker_clients: "_OD[Tuple[str, int], RpcClient]" = _OD()
        self._task_events: List[Dict[str, Any]] = []
        # structured cluster event log (node up/down, actor restarts,
        # OOM/spill, autoscaler decisions); reference: gcs_event_manager +
        # the dashboard's event_agent. Ring-buffered, queryable via
        # rpc_list_cluster_events, live via the "cluster_events" channel.
        self._cluster_events: List[Dict[str, Any]] = []
        # metrics plane: latest cumulative snapshot per reporter, plus the
        # time-series retention + SLO layer fed once per report period by
        # _maybe_fold_metrics. Tombstones keep pruned (exited) reporters'
        # final counter/histogram values so cluster totals stay monotonic.
        self._metrics: Dict[str, Tuple[float, List[Dict[str, Any]]]] = {}
        self._metrics_tombstones: Dict[str, Dict[str, Any]] = {}
        self._ts_store = metrics_ts.TimeSeriesStore()
        self._slo_engine = metrics_ts.SloEngine(self._ts_store)
        self._slo_lock = threading.Lock()  # serializes engine + fold
        self._ts_last_fold = 0.0
        # monotonically increasing chaos schedule version: every apply or
        # clear bumps it so late subscribers can order arm/clear events
        self._chaos_version = 0
        self.server.chaos_identity = self._chaos_identity()
        # SLO controller (controller.py): hosted next to the SloEngine so
        # it reads alerts/nodes/traces under the same roof it acts on.
        # Construction is cheap; its reconcile thread only starts when
        # controller_enabled is set (config or rpc_controller_enable).
        from ray_tpu.controller import SloController

        self._controller = SloController(self)
        self._stopped = threading.Event()
        if self._storage is not None:
            self._reload_from_storage()
        self.server.register_all(self)
        self.server.on_disconnect = self._on_disconnect
        self._health_thread = threading.Thread(
            target=self._health_loop, name="gcs-health", daemon=True
        )
        self._health_thread.start()
        self._resource_bcast_thread = threading.Thread(
            target=self._resource_broadcast_loop, name="gcs-resync", daemon=True
        )
        self._resource_bcast_thread.start()

    @property
    def address(self) -> Tuple[str, int]:
        return self.server.address

    # ------------------------------------------------------------------
    # persistence (reference: gcs_table_storage.cc over store_client/)
    # ------------------------------------------------------------------

    def _persist_actor_locked(self, info: ActorInfo):
        if self._storage is None:
            return
        self._storage.put(
            "actors",
            info.actor_id.hex(),
            {
                "spec": info.spec,
                "state": info.state,
                "address": info.address,
                "node_id": info.node_id,
                "worker_id": info.worker_id,
                "num_restarts": info.num_restarts,
                "death_cause": info.death_cause,
            },
        )

    def _persist_pg_locked(self, info: PlacementGroupInfo):
        if self._storage is None:
            return
        self._storage.put(
            "pgs",
            info.pg_id.hex(),
            {
                "spec": info.spec,
                "state": info.state,
                "bundle_nodes": list(info.bundle_nodes),
                "failure": info.failure,
            },
        )

    def _reload_from_storage(self):
        resched_actors: List[ActorInfo] = []
        resched_pgs: List[PlacementGroupInfo] = []
        for k, v in self._storage.items("kv"):
            ns, key = k.split("\x00", 1)
            self._kv.setdefault(ns, {})[key] = v
        for k, v in self._storage.items("jobs"):
            self._jobs[k] = v
        for k, v in self._storage.items("actors"):
            info = ActorInfo(ActorID.from_hex(k), v["spec"])
            info.state = v["state"]
            info.address = v["address"]
            info.node_id = v["node_id"]
            info.worker_id = v["worker_id"]
            info.num_restarts = v["num_restarts"]
            info.death_cause = v["death_cause"]
            self._actors[info.actor_id] = info
            if info.name and info.state != DEAD:
                self._named_actors[info.name] = info.actor_id
            if info.state in (PENDING_CREATION, RESTARTING):
                # creation/restart was in flight when the GCS died: the
                # lease never completed, so schedule from scratch
                info.state = PENDING_CREATION
                resched_actors.append(info)
        for k, v in self._storage.items("pgs"):
            info = PlacementGroupInfo(PlacementGroupID.from_hex(k), v["spec"])
            info.state = v["state"]
            info.bundle_nodes = list(v["bundle_nodes"])
            info.failure = v["failure"]
            self._pgs[info.pg_id] = info
            if info.state in (PG_PENDING, PG_RESCHEDULING):
                info.state = PG_PENDING
                info.bundle_nodes = [None] * len(info.bundle_nodes)
                resched_pgs.append(info)
        if resched_actors or resched_pgs:
            logger.info(
                "GCS restart: rescheduling %d actors, %d placement groups",
                len(resched_actors),
                len(resched_pgs),
            )
        # defer actual scheduling until raylets have re-registered
        def _resched():
            deadline = time.monotonic() + GlobalConfig.health_check_period_s * 4
            while time.monotonic() < deadline and not self._stopped.is_set():
                with self._lock:
                    if any(n.alive for n in self._nodes.values()):
                        break
                time.sleep(0.2)
            if self._stopped.is_set():
                return
            try:
                for info in resched_pgs:
                    self._pg_sched_pool.submit(self._schedule_pg, info)
                for info in resched_actors:
                    self._actor_sched_pool.submit(self._schedule_actor, info)
            except RuntimeError:
                pass  # pools shut down under us: the GCS is stopping again

        if resched_actors or resched_pgs:
            threading.Thread(target=_resched, daemon=True).start()

    # ------------------------------------------------------------------
    # pubsub
    # ------------------------------------------------------------------

    def rpc_subscribe(self, conn: ServerConn, channel: str):
        with self._lock:
            self._subscribers.setdefault(channel, []).append(conn)
        return True

    def _publish(self, channel: str, message: Any):
        with self._lock:
            subs = list(self._subscribers.get(channel, ()))
            # every published transition also wakes long-poll waiters
            # (wait_for_actor / wait_placement_group)
            self._lock.notify_all()
            if channel == "actors" and self._storage is not None:
                info = self._actors.get(message["actor_id"])
                if info is not None:
                    self._persist_actor_locked(info)
        for conn in subs:
            conn.notify(channel, message)

    def rpc_publish(self, conn: ServerConn, payload):
        channel, message = payload
        self._publish(channel, message)
        return True

    def _on_disconnect(self, conn: ServerConn):
        with self._lock:
            for subs in self._subscribers.values():
                if conn in subs:
                    subs.remove(conn)

    # ------------------------------------------------------------------
    # KV (also the function table: namespace "fn")
    # ------------------------------------------------------------------

    def rpc_kv_put(self, conn, payload):
        ns, key, value, overwrite = payload
        with self._lock:
            space = self._kv.setdefault(ns, {})
            if not overwrite and key in space:
                return False
            space[key] = value
            if self._storage is not None:
                self._storage.put("kv", f"{ns}\x00{key}", value)
        return True

    def rpc_kv_get(self, conn, payload):
        ns, key = payload
        with self._lock:
            return self._kv.get(ns, {}).get(key)

    def rpc_kv_multi_get(self, conn, payload):
        ns, keys = payload
        with self._lock:
            space = self._kv.get(ns, {})
            return {k: space[k] for k in keys if k in space}

    def rpc_kv_del(self, conn, payload):
        ns, key = payload
        with self._lock:
            removed = self._kv.get(ns, {}).pop(key, None) is not None
            if removed and self._storage is not None:
                self._storage.delete("kv", f"{ns}\x00{key}")
            return removed

    def rpc_kv_keys(self, conn, payload):
        ns, prefix = payload
        with self._lock:
            return [k for k in self._kv.get(ns, {}) if k.startswith(prefix)]

    # ------------------------------------------------------------------
    # nodes
    # ------------------------------------------------------------------

    def rpc_register_node(self, conn, payload):
        node_id, address, resources, labels = payload
        info = NodeInfo(node_id, address, resources, labels)
        with self._lock:
            self._nodes[node_id] = info
        conn.meta["node_id"] = node_id
        self._publish("nodes", {"event": "added", "node": self._node_view(info)})
        self._record_cluster_event(
            "NODE_ADDED",
            f"node {node_id.hex()[:8]} registered at {address[0]}:{address[1]} "
            f"resources={resources}",
            node_id=node_id.hex(),
        )
        logger.info("node %s registered at %s resources=%s", node_id.hex()[:8], address, resources)
        return True

    def rpc_heartbeat(self, conn, payload):
        node_id, available = payload[0], payload[1]
        total = payload[2] if len(payload) > 2 else None
        demand = payload[3] if len(payload) > 3 else None
        # self-probe snapshot (peer data-plane pings + local store health):
        # the gray-failure signal — a node can heartbeat fine while its
        # data plane is partitioned or its store is wedged
        probes = payload[4] if len(payload) > 4 else None
        with self._lock:
            info = self._nodes.get(node_id)
            if info is None or not info.alive:
                # a dead/drained node stays dead: an in-flight heartbeat must
                # not resurrect it (it re-registers if it really came back)
                return False
            info.last_heartbeat = time.monotonic()
            info.available_resources = available
            if total is not None:
                # totals change when placement-group bundles commit/release
                info.total_resources = total
            if demand is not None:
                # parked lease requests: the autoscaler's scale-up signal
                info.pending_demand = demand
            if probes is not None:
                info.probes = probes
        return True

    def rpc_unregister_node(self, conn, payload):
        """Graceful node exit: mark dead immediately (no health-check wait).
        If a drain orchestrator stashed a migration map for this node, it
        rides the removal publish so owners re-point their object locations
        at the peers holding the re-replicated copies (zero lineage
        reconstructions) instead of marking them lost."""
        node_id = payload
        with self._lock:
            info = self._nodes.get(node_id)
            if info is None or not info.alive:
                return False
            was_draining = info.state == "DRAINING"
            info.alive = False
            info.state = "DEAD"
            migrated = self._drain_migrations.pop(node_id, None)
        removal = {"event": "removed", "node": self._node_view(info)}
        if migrated:
            removal["migrated"] = {
                oid: tuple(addr) for oid, addr in migrated.items()
            }
        self._publish("nodes", removal)
        self._record_cluster_event(
            "NODE_REMOVED",
            f"node {node_id.hex()[:8]} "
            + ("drained and deregistered" if was_draining
               else "deregistered (graceful unregister)")
            + (f" ({len(migrated)} objects migrated)" if migrated else ""),
            node_id=node_id.hex(),
        )
        self._handle_node_death(node_id)
        return True

    def rpc_get_nodes(self, conn, payload=None):
        with self._lock:
            return [self._node_view(n) for n in self._nodes.values()]

    # ------------------------------------------------------------------
    # graceful drain (ALIVE -> DRAINING -> DEAD; reference:
    # gcs_service.proto DrainNode + the autoscaler's drain-before-preempt)
    # ------------------------------------------------------------------

    def _resolve_node_locked(self, ident) -> Optional[NodeInfo]:
        """Resolve a node by NodeID, node_id hex prefix, or node_name
        label (callers hold self._lock)."""
        if isinstance(ident, NodeID):
            return self._nodes.get(ident)
        ident = str(ident or "")
        if not ident:
            return None
        for info in self._nodes.values():
            if info.node_id.hex().startswith(ident):
                return info
        for info in self._nodes.values():
            if info.labels.get("node_name") == ident:
                return info
        return None

    def rpc_drain_node(self, conn, payload):
        """Initiate a graceful drain (idempotent: re-issuing onto a node
        already DRAINING or DEAD is a no-op). The orchestration runs off
        the dispatch thread: tell the raylet to drain (stop leasing, let
        running work finish until the deadline, migrate its primary plasma
        objects), stash the returned migration map, then shut the raylet
        down so it deregisters cleanly."""
        p = payload or {}
        deadline_s = float(p.get("deadline_s", 30.0))
        with self._lock:
            info = self._resolve_node_locked(p.get("node_id"))
            if info is None:
                return {"status": "not_found", "node_id": None}
            node_hex = info.node_id.hex()
            if not info.alive:
                return {"status": "dead", "node_id": node_hex}
            if info.state == "DRAINING":
                return {"status": "draining", "node_id": node_hex}
            info.state = "DRAINING"
        self._publish(
            "nodes", {"event": "draining", "node": self._node_view(info)}
        )
        self._record_cluster_event(
            "NODE_DRAINING",
            f"node {node_hex[:8]} "
            f"({info.labels.get('node_name', '?')}) draining: new leases "
            f"rejected, running work has {deadline_s:.0f}s to finish",
            node_id=node_hex,
        )
        threading.Thread(
            target=self._drain_node_orchestrate,
            args=(info, deadline_s),
            name=f"drain-{node_hex[:8]}",
            daemon=True,
        ).start()
        return {"status": "draining", "node_id": node_hex}

    def _drain_node_orchestrate(self, info: NodeInfo, deadline_s: float):
        from ray_tpu._private import internal_metrics

        node_hex = info.node_id.hex()
        outcome = "completed"
        migrated: Dict[bytes, Tuple[str, int]] = {}
        moved_actors = self._migrate_actors_for_drain(info.node_id)
        try:
            reply = self._raylet_client(info).call(
                "drain", {"deadline_s": deadline_s}, timeout=deadline_s + 30.0
            )
            migrated = (reply or {}).get("migrated") or {}
            if migrated:
                with self._lock:
                    self._drain_migrations[info.node_id] = dict(migrated)
            self._raylet_client(info).call("shutdown", None, timeout=10.0)
        except Exception as e:
            outcome = "failed"
            logger.warning("drain of node %s failed: %r", node_hex[:8], e)
        # the raylet's stop() unregisters; give it a grace window, then
        # force the transition so a wedged raylet can't stay DRAINING
        # forever (its objects still migrate if the map came back)
        grace = time.monotonic() + 15.0
        while time.monotonic() < grace:
            with self._lock:
                if not info.alive:
                    break
            time.sleep(0.1)
        else:
            with self._lock:
                still_alive = info.alive
            if still_alive:
                outcome = "forced"
                self.rpc_unregister_node(None, info.node_id)
        internal_metrics.inc(
            "ray_tpu_node_drains_total", tags={"outcome": outcome}
        )
        self._record_cluster_event(
            "NODE_DRAINED",
            f"node {node_hex[:8]} drain {outcome}: "
            f"{len(migrated)} objects migrated to peers, "
            f"{moved_actors} actors relocated",
            severity="INFO" if outcome == "completed" else "WARNING",
            node_id=node_hex,
        )

    def _migrate_actors_for_drain(self, node_id: NodeID) -> int:
        """Proactively restart restartable actors away from a DRAINING
        node (an actor worker never releases its lease, so waiting for it
        would burn the whole drain deadline). The stale instance left on
        the draining node dies when its raylet shuts down; non-restartable
        actors ride out the drain and die with the node, exactly as on a
        preemption."""
        with self._lock:
            movable = [
                a.actor_id
                for a in self._actors.values()
                if a.node_id == node_id
                and a.state == ALIVE
                and (a.num_restarts < a.max_restarts or a.max_restarts < 0)
            ]
        for actor_id in movable:
            self._reconstruct_actor(
                actor_id, f"node {node_id.hex()[:8]} draining"
            )
        return len(movable)

    def _node_view(self, n: NodeInfo) -> Dict[str, Any]:
        return {
            "node_id": n.node_id,
            "address": n.address,
            "resources": n.total_resources,
            "available": n.available_resources,
            "labels": n.labels,
            "alive": n.alive,
            "state": n.state,
            "probes": dict(n.probes),
            "store_path": n.store_path,
            "store_capacity": n.store_capacity,
            "demand": list(n.pending_demand),
        }

    def _resource_broadcast_loop(self):
        """Bidirectional resource sync, GCS->raylet half: rebroadcast the
        aggregated per-node resource view to every subscribed raylet on a
        bounded-staleness cadence (reference: common/ray_syncer/
        ray_syncer.h:39 — raylets push their view up via heartbeats, the
        syncer fans the merged view back down). Raylets then make spillback
        decisions from the gossiped cache instead of a synchronous
        get_nodes RPC per decision."""
        period = GlobalConfig.resource_broadcast_period_s
        while not self._stopped.wait(period):
            with self._lock:
                if not self._subscribers.get("resource_view"):
                    continue
                views = [
                    self._node_view(n)
                    for n in self._nodes.values()
                    if n.alive
                ]
            self._publish("resource_view", {"ts": time.time(), "nodes": views})

    def _health_loop(self):
        period = GlobalConfig.health_check_period_s
        threshold = GlobalConfig.health_check_failure_threshold
        last_tick = time.monotonic()
        while not self._stopped.wait(period):
            now = time.monotonic()
            # how long this process itself did not run. Heartbeats that
            # arrived meanwhile are still unread (an in-process raylet could
            # not even send any), so that time is not silence from the
            # nodes: found on a v5e host, where the whole driver process
            # freezes for ~5 s while a worker starts the TPU runtime.
            stalled = now - last_tick - period
            last_tick = now
            window = GlobalConfig.degraded_window_s
            dead: List[Tuple[NodeInfo, str]] = []
            degraded: List[NodeInfo] = []
            recovered: List[NodeInfo] = []
            with self._lock:
                for info in self._nodes.values():
                    if not info.alive:
                        continue
                    if stalled > period:
                        info.last_heartbeat += stalled
                    if now - info.last_heartbeat > period * threshold:
                        info.alive = False
                        info.state = "DEAD"
                        dead.append(
                            (info,
                             f"failed health check (no heartbeat for "
                             f"{period * threshold:.1f}s)")
                        )
                        continue
                    # gray failure: heartbeats arrive, but the node's
                    # self-probes (peer pings / local store) report failure
                    probes_bad = bool(info.probes) and not info.probes.get(
                        "healthy", True
                    )
                    if info.state == "ALIVE" and probes_bad:
                        info.state = "DEGRADED"
                        info.degraded_since = now
                        degraded.append(info)
                    elif info.state == "DEGRADED":
                        if not probes_bad:
                            info.state = "ALIVE"
                            info.degraded_since = None
                            recovered.append(info)
                        elif now - (info.degraded_since or now) > window:
                            info.alive = False
                            info.state = "DEAD"
                            dead.append(
                                (info,
                                 f"gray failure escalated: DEGRADED for "
                                 f">{window:.1f}s without recovering")
                            )
                n_degraded = sum(
                    1
                    for i in self._nodes.values()
                    if i.alive and i.state == "DEGRADED"
                )
            from ray_tpu._private import internal_metrics

            internal_metrics.set_gauge("ray_tpu_node_degraded", float(n_degraded))
            for info in degraded:
                logger.warning(
                    "node %s DEGRADED (gray failure): probes=%s",
                    info.node_id.hex()[:8], info.probes,
                )
                self._publish("nodes", {"event": "degraded", "node": self._node_view(info)})
                self._record_cluster_event(
                    "NODE_DEGRADED",
                    f"node {info.node_id.hex()[:8]} entered DEGRADED: "
                    f"heartbeats healthy but self-probes failing "
                    f"({info.probes.get('detail', 'no detail')}); draining "
                    f"new leases away",
                    severity="WARNING",
                    node_id=info.node_id.hex(),
                )
            for info in recovered:
                logger.info("node %s recovered from DEGRADED", info.node_id.hex()[:8])
                self._publish("nodes", {"event": "recovered", "node": self._node_view(info)})
                self._record_cluster_event(
                    "NODE_RECOVERED",
                    f"node {info.node_id.hex()[:8]} recovered from DEGRADED "
                    f"(self-probes healthy again)",
                    node_id=info.node_id.hex(),
                )
            for info, why in dead:
                logger.warning("node %s %s", info.node_id.hex()[:8], why)
                self._publish("nodes", {"event": "removed", "node": self._node_view(info)})
                self._record_cluster_event(
                    "NODE_DIED",
                    f"node {info.node_id.hex()[:8]} {why}",
                    severity="ERROR",
                    node_id=info.node_id.hex(),
                )
                self._handle_node_death(info.node_id)

    # ------------------------------------------------------------------
    # chaos plane (deterministic fault injection, fault_injection.py)
    # ------------------------------------------------------------------

    def _chaos_cluster_nodes_locked(self) -> List[Dict[str, Any]]:
        """Topology snapshot embedded into an applied schedule so every
        process resolves rule identifiers (node names/ids) to addresses —
        and its own identity — the same way. The GCS itself appears as the
        pseudo-node "gcs" (partitioning a node from "gcs" drops its
        heartbeats, which is how escalation-to-DEAD is injected)."""
        from ray_tpu._private import fault_injection as fi

        entries = [
            {
                "node_id": n.node_id.hex(),
                "node_name": n.labels.get("node_name", ""),
                "addresses": [fi.addr_key(n.address)],
            }
            for n in self._nodes.values()
        ]
        entries.append(
            {"node_id": "gcs", "node_name": "gcs",
             "addresses": [fi.addr_key(self.server.address)]}
        )
        return entries

    def rpc_chaos_apply(self, conn, payload):
        """Validate, version, and distribute a fault schedule: persisted in
        KV (namespace "chaos") for late joiners, pushed over the "chaos"
        channel to every subscribed raylet/driver, and armed in the GCS's
        own process. Returns the assigned version."""
        from ray_tpu._private import fault_injection as fi

        schedule = dict(payload or {})
        fi.validate_schedule(schedule)
        with self._lock:
            self._chaos_version += 1
            schedule["version"] = self._chaos_version
            schedule["cluster_nodes"] = self._chaos_cluster_nodes_locked()
            blob = json.dumps(schedule).encode()
            self._kv.setdefault("chaos", {})["schedule"] = blob
            if self._storage is not None:
                self._storage.put("kv", "chaos\x00schedule", blob)
        fi.arm(schedule, local_node_id="gcs",
               local_addresses=[self.server.address])
        self._publish("chaos", {"event": "armed", "schedule": schedule})
        self._record_cluster_event(
            "CHAOS_ARMED",
            f"chaos schedule v{schedule['version']} armed: "
            f"{len(schedule.get('rules', []))} rules, "
            f"seed={schedule.get('seed', 0)}",
            severity="WARNING",
        )
        return schedule["version"]

    def rpc_chaos_clear(self, conn, payload=None):
        from ray_tpu._private import fault_injection as fi

        with self._lock:
            had = self._kv.get("chaos", {}).pop("schedule", None)
            self._chaos_version += 1
            if self._storage is not None:
                self._storage.delete("kv", "chaos\x00schedule")
        fi.disarm()
        self._publish("chaos", {"event": "cleared"})
        if had is not None:
            self._record_cluster_event("CHAOS_CLEARED", "chaos schedule cleared")
        return had is not None

    def rpc_chaos_status(self, conn, payload=None):
        from ray_tpu._private import fault_injection as fi

        with self._lock:
            blob = self._kv.get("chaos", {}).get("schedule")
            version = self._chaos_version
        return {
            "armed": blob is not None,
            "version": version,
            "schedule": json.loads(blob) if blob is not None else None,
        }

    def rpc_chaos_report(self, conn, payload=None):
        """Cluster-wide injection report: the GCS's own log plus every
        alive raylet's (best-effort — a partitioned raylet can't answer,
        which is the point), plus chaos-related cluster events."""
        from ray_tpu._private import fault_injection as fi

        with self._lock:
            nodes = [n for n in self._nodes.values() if n.alive]
            events = [
                dict(e)
                for e in self._cluster_events
                if e.get("type") in (
                    "CHAOS_ARMED", "CHAOS_CLEARED", "NODE_DEGRADED",
                    "NODE_RECOVERED", "NODE_DIED",
                )
            ]
        reports: Dict[str, Any] = {}
        own = fi.local_report()
        if own is not None:
            reports["gcs"] = own
        for node in nodes:
            try:
                r = self._raylet_client(node).call("chaos_report", None, timeout=2.0)
                if r is not None:
                    reports[node.node_id.hex()] = r
            except Exception:
                reports[node.node_id.hex()] = {"error": "unreachable"}
        # in-process clusters share one ArmedSchedule between all their
        # components, so identical instances must count once
        seen_instances = set()
        total = 0
        for r in reports.values():
            if not (isinstance(r, dict) and "counts" in r):
                continue
            instance = r.get("instance")
            if instance is not None and instance in seen_instances:
                continue
            seen_instances.add(instance)
            total += sum(r["counts"].values())
        return {
            "reports": reports,
            "events": events,
            "total_injected": total,
        }

    # ------------------------------------------------------------------
    # actors
    # ------------------------------------------------------------------

    def rpc_register_actor(self, conn, payload):
        """Register + schedule an actor; returns once scheduling has started.

        The creation task is pushed to a leased worker asynchronously; callers
        learn the address via the actor pubsub channel or rpc_get_actor.
        """
        actor_id, spec = payload
        info = ActorInfo(actor_id, spec)
        with self._lock:
            if info.name:
                if info.name in self._named_actors:
                    raise ValueError(f"actor name {info.name!r} already taken")
                self._named_actors[info.name] = actor_id
            self._actors[actor_id] = info
            self._persist_actor_locked(info)
        self._actor_sched_pool.submit(self._schedule_actor, info)
        return True

    def rpc_get_actor(self, conn, payload):
        actor_id = payload
        with self._lock:
            info = self._actors.get(actor_id)
            return None if info is None else info.public_view()

    def rpc_get_actor_by_name(self, conn, payload):
        name = payload
        with self._lock:
            actor_id = self._named_actors.get(name)
            if actor_id is None:
                return None
            return self._actors[actor_id].public_view()

    def rpc_list_actors(self, conn, payload=None):
        with self._lock:
            return [a.public_view() for a in self._actors.values()]

    def rpc_wait_for_actor(self, conn, payload):
        """Long-poll until the actor is ALIVE or DEAD; returns its view."""
        actor_id, timeout = payload
        deadline = time.monotonic() + (timeout if timeout is not None else 1e9)
        with self._lock:
            while True:
                info = self._actors.get(actor_id)
                if info is not None and info.state in (ALIVE, DEAD):
                    return info.public_view()
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._lock.wait(min(remaining, 1.0))

    def rpc_kill_actor(self, conn, payload):
        actor_id, no_restart = payload
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None:
                return False
            if no_restart:
                info.max_restarts = 0
            address, worker_id, node_id = info.address, info.worker_id, info.node_id
        if address is not None:
            try:
                client = RpcClient(address, connect_timeout=2.0, prefer_local=True)
                client.call("kill_self", None, timeout=2.0)
                client.close()
            except Exception:
                pass
        return True

    def _pick_node(
        self, resources: Dict[str, float], node_id: Optional[NodeID] = None
    ) -> Optional[NodeInfo]:
        with self._lock:
            candidates = [
                n
                for n in self._nodes.values()
                if n.alive
                # a DRAINING node is leaving: never place anything there
                and n.state != "DRAINING"
                # DEGRADED drains new leases away (explicit targeting wins:
                # a caller pinning node_id accepts the gray failure risk)
                and (n.state != "DEGRADED" or node_id is not None)
                and all(n.total_resources.get(k, 0) >= v for k, v in resources.items())
                and (node_id is None or n.node_id == node_id)
            ]
            if not candidates:
                return None
            # Hybrid policy (reference: scheduling/policy/
            # hybrid_scheduling_policy.h:50,85-118): below the spread
            # threshold of critical-resource utilization a node counts as
            # "low load"; pick uniformly among the top-k lowest-utilization
            # nodes so hot spots spread without stampeding one node.
            import random as _random

            def utilization(n: NodeInfo) -> float:
                worst = 0.0
                for k, v in resources.items():
                    total = n.total_resources.get(k, 0)
                    if total <= 0:
                        continue
                    used = total - n.available_resources.get(k, 0) + v
                    worst = max(worst, used / total)
                return worst

            ranked = sorted(candidates, key=utilization)
            threshold = GlobalConfig.scheduler_spread_threshold
            low = [n for n in ranked if utilization(n) <= threshold]
            pool = low or ranked
            k = max(1, int(len(pool) * GlobalConfig.scheduler_top_k_fraction))
            return _random.choice(pool[:k])

    def _worker_client(self, addr: Tuple[str, int]) -> RpcClient:
        with self._lock:
            client = self._worker_clients.get(addr)
            if client is not None and not client.closed:
                self._worker_clients.move_to_end(addr)
                return client
        client = RpcClient(addr, connect_timeout=5.0, prefer_local=True)
        with self._lock:
            racer = self._worker_clients.get(addr)
            if racer is not None and not racer.closed:
                client.close()
                return racer
            self._worker_clients[addr] = client
            # LRU bound: evictions (and failure drops below) close on a
            # DELAY — an immediate close() would fail concurrent in-flight
            # create_actor calls sharing the client; the grace period
            # exceeds the longest create timeout, after which closing a
            # still-open socket reclaims the fd instead of leaking it at
            # the 10k-actor envelope
            while len(self._worker_clients) > 512:
                _, victim = self._worker_clients.popitem(last=False)
                self._deferred_close(victim)
        return client

    def _deferred_close(self, client: RpcClient):
        delay = GlobalConfig.gcs_rpc_timeout_s * 10 + 5
        timer = threading.Timer(delay, client.close)
        timer.daemon = True
        timer.start()

    def _drop_worker_client(self, addr: Tuple[str, int]):
        with self._lock:
            client = self._worker_clients.pop(addr, None)
        if client is not None:
            self._deferred_close(client)

    def _raylet_client(self, node: NodeInfo) -> RpcClient:
        with self._lock:
            client = self._raylet_clients.get(node.node_id)
            if client is not None and not client.closed:
                return client
            client = RpcClient(node.address, prefer_local=True)
            client.chaos_identity = self._chaos_identity()
            self._raylet_clients[node.node_id] = client
            return client

    def _chaos_identity(self):
        from ray_tpu._private import fault_injection as fi

        return fi.identity_for("gcs", self.server.address)

    def _schedule_actor(self, info: ActorInfo, deadline: Optional[float] = None):
        spec = info.spec
        resources = spec["options"].get("resources_spec", {"CPU": 1.0})
        affinity = spec["options"].get("scheduling_node")
        soft = spec["options"].get("scheduling_soft", False)
        if deadline is None:
            deadline = time.monotonic() + GlobalConfig.worker_lease_timeout_s * 4
        while time.monotonic() < deadline:
            node = self._pick_node(resources, node_id=affinity)
            if node is None and affinity is not None and soft:
                node = self._pick_node(resources)
            if node is None:
                # wake immediately when a node registers/frees resources
                # (register/heartbeat paths notify via _publish)
                with self._lock:
                    self._lock.wait(0.5)
                continue
            try:
                client = self._raylet_client(node)
                lease = client.call(
                    "request_worker_lease",
                    {
                        "resources": resources,
                        "actor_id": info.actor_id,
                        "job_id": spec["job_id"],
                        "runtime_env": spec["options"].get("runtime_env"),
                        # the GCS picks the node itself; a raylet-side
                        # spillback redirect would only confuse this loop
                        "allow_spill": False,
                    },
                    timeout=GlobalConfig.worker_lease_timeout_s,
                )
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "actor %s lease attempt failed: %r", info.actor_id.hex()[:8], e
                )
                time.sleep(0.2)
                continue
            if lease is None or "retry_at" in lease:
                time.sleep(0.05)
                continue
            self._dispatch_actor_creation(info, node, client, lease, deadline)
            return
        with self._lock:
            info.state = DEAD
            info.death_cause = "scheduling failed: no feasible node in time"
        self._publish(f"actor:{info.actor_id.hex()}", info.public_view())
        self._publish("actors", info.public_view())

    def _dispatch_actor_creation(self, info, node, client, lease, deadline):
        """Send ``create_actor`` and wait for the constructor WITHOUT
        holding a scheduler-pool thread: the pool is 4 threads on a 1-core
        box, so four concurrent long-running constructors used to fill it
        and any creation submitted from INSIDE a constructor (a nested
        named actor, e.g. a collective rendezvous store) deadlocked
        behind its own dependents. The constructor wait is a call_async
        slot; success/failure resumes on the RPC callback executor."""
        from ray_tpu._private.rpc import ERROR, ConnectionLost, RpcError

        worker_addr = tuple(lease["address"])

        def _done(kind, payload):
            if kind != ERROR:
                with self._lock:
                    info.state = ALIVE
                    info.address = worker_addr
                    info.node_id = node.node_id
                    info.worker_id = lease["worker_id"]
                self._publish(f"actor:{info.actor_id.hex()}", info.public_view())
                self._publish("actors", info.public_view())
                return
            e = payload
            # the pooled connection may be mid-teardown: drop it so the
            # retry (or the next actor) dials fresh
            self._drop_worker_client(worker_addr)
            # return the lease so a failed creation doesn't leak resources
            try:
                client.call("return_worker", {"worker_id": lease["worker_id"]})
            except Exception:
                pass
            if not isinstance(e, (ConnectionLost, TimeoutError, OSError, RpcError)):
                # the actor constructor itself raised: surface the real
                # error instead of retrying (the user's bug won't go away)
                with self._lock:
                    info.state = DEAD
                    info.death_cause = f"actor constructor failed: {e!r}"
                self._publish(f"actor:{info.actor_id.hex()}", info.public_view())
                self._publish("actors", info.public_view())
                return
            logger.warning(
                "actor %s scheduling attempt failed: %r", info.actor_id.hex()[:8], e
            )
            try:
                self._actor_sched_pool.submit(self._reschedule_after, info, deadline)
            except RuntimeError:
                pass  # pool shut down mid-teardown

        try:
            # pooled connection: a fresh TCP connect + AUTH per actor was
            # ~2 round-trips of pure overhead in the many_actors envelope
            wclient = self._worker_client(worker_addr)
            wclient.call_async(
                "create_actor",
                {
                    "actor_id": info.actor_id,
                    "spec": info.spec,
                    "num_restarts": info.num_restarts,
                },
                _done,
                timeout=GlobalConfig.gcs_rpc_timeout_s * 10,
            )
        except Exception as e:  # noqa: BLE001
            _done(ERROR, e if isinstance(e, Exception) else ConnectionLost(str(e)))

    def _reschedule_after(self, info, deadline):
        time.sleep(0.2)
        self._schedule_actor(info, deadline)

    def rpc_report_worker_death(self, conn, payload):
        """Raylet tells us a worker died; restart or mark-dead its actors
        (reference: gcs_actor_manager.cc:1100 ReconstructActor)."""
        node_id, worker_id, actor_ids, cause = (
            payload["node_id"],
            payload["worker_id"],
            payload["actor_ids"],
            payload.get("cause", "worker died"),
        )
        for actor_id in actor_ids:
            with self._lock:
                info = self._actors.get(actor_id)
                # a stale report (e.g. node drain already restarted the actor
                # elsewhere, or a restart is in flight) must not burn another
                # restart
                if info is None or info.state != ALIVE or info.worker_id != worker_id:
                    continue
            self._reconstruct_actor(actor_id, cause)
        return True

    def _reconstruct_actor(self, actor_id: ActorID, cause: str):
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None or info.state == DEAD:
                return
            if info.num_restarts < info.max_restarts or info.max_restarts < 0:
                info.num_restarts += 1
                info.state = RESTARTING
                info.address = None
                info.worker_id = None  # a stale death report must not match
                restart = True
            else:
                info.state = DEAD
                info.death_cause = cause
                restart = False
        self._publish(f"actor:{actor_id.hex()}", info.public_view())
        self._publish("actors", info.public_view())
        if restart:
            self._record_cluster_event(
                "ACTOR_RESTARTED",
                f"actor {actor_id.hex()[:8]} restarting "
                f"({info.num_restarts}/{info.max_restarts}): {cause}",
                severity="WARNING",
                actor_id=actor_id.hex(),
            )
            logger.info(
                "restarting actor %s (%d/%s)",
                actor_id.hex()[:8],
                info.num_restarts,
                info.max_restarts,
            )
            self._actor_sched_pool.submit(self._schedule_actor, info)
        else:
            self._record_cluster_event(
                "ACTOR_DEAD",
                f"actor {actor_id.hex()[:8]} dead (restarts exhausted): "
                f"{cause}",
                severity="ERROR",
                actor_id=actor_id.hex(),
            )

    def _handle_node_death(self, node_id: NodeID):
        with self._lock:
            affected = [a.actor_id for a in self._actors.values() if a.node_id == node_id and a.state == ALIVE]
        for actor_id in affected:
            self._reconstruct_actor(actor_id, f"node {node_id.hex()[:8]} died")
        # placement groups with a bundle on the dead node: tear down the whole
        # gang and re-place it (a pod slice is the failure domain — partial
        # gangs are useless for SPMD meshes)
        with self._lock:
            broken = [
                p
                for p in self._pgs.values()
                if p.state == PG_CREATED and node_id in p.bundle_nodes
            ]
            survivors: Dict[Any, List[Tuple[int, NodeID]]] = {}
            for p in broken:
                p.state = PG_RESCHEDULING
                self._persist_pg_locked(p)
                survivors[p.pg_id] = [
                    (i, nid)
                    for i, nid in enumerate(p.bundle_nodes)
                    if nid is not None and nid != node_id
                ]
                p.bundle_nodes = [None] * len(p.bundle_nodes)
        for p in broken:
            logger.warning(
                "placement group %s lost node %s; rescheduling the gang",
                p.pg_id.hex()[:8],
                node_id.hex()[:8],
            )
            self._release_bundles(p.pg_id, survivors[p.pg_id])
            self._pg_sched_pool.submit(self._schedule_pg, p)

    # ------------------------------------------------------------------
    # placement groups (two-phase prepare/commit, reference:
    # gcs_placement_group_scheduler.cc + node_manager.proto:380-387)
    # ------------------------------------------------------------------

    def rpc_create_placement_group(self, conn, payload):
        pg_id, spec = payload
        info = PlacementGroupInfo(pg_id, spec)
        with self._lock:
            self._pgs[pg_id] = info
            self._persist_pg_locked(info)
        self._pg_sched_pool.submit(self._schedule_pg, info)
        return True

    def rpc_wait_placement_group(self, conn, payload):
        """Long-poll until the group is CREATED or REMOVED (failed)."""
        pg_id, timeout = payload
        deadline = time.monotonic() + (timeout if timeout is not None else 1e9)
        with self._lock:
            while True:
                info = self._pgs.get(pg_id)
                if info is not None and info.state in (PG_CREATED, PG_REMOVED):
                    return info.public_view()
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._lock.wait(min(remaining, 1.0))

    def rpc_remove_placement_group(self, conn, payload):
        pg_id = payload
        with self._lock:
            info = self._pgs.get(pg_id)
            if info is None or info.state == PG_REMOVED:
                return False
            info.state = PG_REMOVED
            self._persist_pg_locked(info)
            self._lock.notify_all()
            assignment = [
                (i, node_id)
                for i, node_id in enumerate(info.bundle_nodes)
                if node_id is not None
            ]
            info.bundle_nodes = [None] * len(info.bundle_nodes)
        self._release_bundles(pg_id, assignment)
        return True

    def rpc_placement_group_table(self, conn, payload=None):
        with self._lock:
            return [p.public_view() for p in self._pgs.values()]

    def _candidate_nodes_locked(self, label_equal: Optional[str]) -> List[List[NodeInfo]]:
        """Groups of candidate nodes. With a label-equality constraint (e.g.
        tpu_slice_id for gang-scheduling a pod slice) each group shares one
        label value; otherwise a single group of all alive nodes."""
        alive = [
            n for n in self._nodes.values()
            if n.alive and n.state not in ("DEGRADED", "DRAINING")
        ]
        if not label_equal:
            return [alive]
        groups: Dict[str, List[NodeInfo]] = {}
        for n in alive:
            value = n.labels.get(label_equal)
            if value is not None:
                groups.setdefault(value, []).append(n)
        return list(groups.values())

    def _plan_bundles(
        self, bundles: List[Dict[str, float]], strategy: str, label_equal: Optional[str]
    ) -> Optional[List[NodeID]]:
        """Pick a node per bundle, respecting the strategy, against the
        current resource view. Returns None when no feasible plan exists."""
        with self._lock:
            for group in self._candidate_nodes_locked(label_equal):
                avail = {
                    n.node_id: dict(n.available_resources) for n in group
                }
                nodes = {n.node_id: n for n in group}
                order = sorted(
                    avail,
                    key=lambda nid: -min(avail[nid].values(), default=0.0),
                )

                def fits(nid, bundle):
                    return all(avail[nid].get(k, 0.0) >= v for k, v in bundle.items())

                def take(nid, bundle):
                    for k, v in bundle.items():
                        avail[nid][k] = avail[nid].get(k, 0.0) - v

                plan: List[Optional[NodeID]] = [None] * len(bundles)
                if strategy in ("STRICT_PACK",):
                    for nid in order:
                        trial = dict(avail[nid])
                        ok = True
                        for b in bundles:
                            if all(trial.get(k, 0.0) >= v for k, v in b.items()):
                                for k, v in b.items():
                                    trial[k] = trial.get(k, 0.0) - v
                            else:
                                ok = False
                                break
                        if ok:
                            return [nid] * len(bundles)
                    continue
                if strategy in ("STRICT_SPREAD",):
                    used: set = set()
                    ok = True
                    for i, b in enumerate(bundles):
                        chosen = next(
                            (nid for nid in order if nid not in used and fits(nid, b)),
                            None,
                        )
                        if chosen is None:
                            ok = False
                            break
                        used.add(chosen)
                        take(chosen, b)
                        plan[i] = chosen
                    if ok:
                        return plan  # type: ignore[return-value]
                    continue
                # PACK / SPREAD: soft preferences, always succeed if capacity
                prefer_same = strategy == "PACK"
                ok = True
                last: Optional[NodeID] = None
                used = set()
                for i, b in enumerate(bundles):
                    candidates = [nid for nid in order if fits(nid, b)]
                    if not candidates:
                        ok = False
                        break
                    chosen = None
                    if prefer_same and last in candidates:
                        chosen = last
                    elif not prefer_same:
                        fresh = [nid for nid in candidates if nid not in used]
                        chosen = fresh[0] if fresh else candidates[0]
                    if chosen is None:
                        chosen = candidates[0]
                    take(chosen, b)
                    plan[i] = chosen
                    last = chosen
                    used.add(chosen)
                if ok:
                    return plan  # type: ignore[return-value]
            return None

    def _schedule_pg(self, info: PlacementGroupInfo):
        spec = info.spec
        bundles = spec["bundles"]
        deadline = time.monotonic() + GlobalConfig.worker_lease_timeout_s * 4
        while time.monotonic() < deadline:
            with self._lock:
                if info.state == PG_REMOVED:
                    return
            plan = self._plan_bundles(
                bundles, spec["strategy"], spec.get("label_equal")
            )
            if plan is None:
                time.sleep(0.2)
                continue
            # bundles grouped per raylet: ONE prepare/commit RPC per node
            # instead of one per bundle (batched phase-1/phase-2 — the
            # per-bundle round-trips dominated pg create/remove latency)
            by_node: Dict[NodeID, List[int]] = {}
            for i, node_id in enumerate(plan):
                by_node.setdefault(node_id, []).append(i)
            # phase 1: prepare every node's bundles (atomic per node)
            prepared: List[Tuple[int, NodeID]] = []
            ok = True
            for node_id, idxs in by_node.items():
                with self._lock:
                    node = self._nodes.get(node_id)
                if node is None or not node.alive:
                    ok = False
                    break
                try:
                    granted = self._raylet_client(node).call(
                        "prepare_bundles",
                        (info.pg_id, [(i, bundles[i]) for i in idxs]),
                        timeout=10.0,
                    )
                except Exception:
                    granted = False
                if not granted:
                    ok = False
                    break
                prepared.extend((i, node_id) for i in idxs)
            if not ok:
                self._release_bundles(info.pg_id, prepared)
                time.sleep(0.2)
                continue
            # phase 2: commit (rollback everything on any failure)
            committed: List[Tuple[int, NodeID]] = []
            commit_ok = True
            for node_id, idxs in by_node.items():
                with self._lock:
                    node = self._nodes.get(node_id)
                try:
                    if node is None or not node.alive:
                        raise RuntimeError("node died between prepare and commit")
                    if not self._raylet_client(node).call(
                        "commit_bundles", (info.pg_id, idxs), timeout=10.0
                    ):
                        raise RuntimeError("commit_bundles refused")
                    committed.extend((i, node_id) for i in idxs)
                except Exception:
                    logger.warning(
                        "commit_bundles(%s, %s) failed; rolling back",
                        info.pg_id.hex()[:8],
                        idxs,
                    )
                    commit_ok = False
                    break
            if not commit_ok:
                self._release_bundles(info.pg_id, prepared)
                time.sleep(0.2)
                continue
            with self._lock:
                all_alive = all(
                    (n := self._nodes.get(nid)) is not None and n.alive for nid in plan
                )
                if info.state == PG_REMOVED:
                    # a concurrent remove ran during prepare/commit: undo
                    outcome = "removed"
                elif not all_alive:
                    # a plan node died during commit and _handle_node_death
                    # could not see the group (state was still PENDING): undo
                    # and re-plan (both paths hold _lock, so no window)
                    outcome = "replan"
                else:
                    info.bundle_nodes = list(plan)
                    info.state = PG_CREATED
                    outcome = "created"
                    self._persist_pg_locked(info)
                self._lock.notify_all()
            if outcome == "removed":
                self._release_bundles(info.pg_id, committed)
                return
            if outcome == "replan":
                self._release_bundles(info.pg_id, committed)
                time.sleep(0.2)
                continue
            self._publish(f"pg:{info.pg_id.hex()}", info.public_view())
            return
        with self._lock:
            info.state = PG_REMOVED
            info.failure = "scheduling failed: no feasible placement in time"
            self._persist_pg_locked(info)
            self._lock.notify_all()
        self._publish(f"pg:{info.pg_id.hex()}", info.public_view())

    def _release_bundles(self, pg_id, assignment: List[Tuple[int, NodeID]]):
        by_node: Dict[NodeID, List[int]] = {}
        for i, node_id in assignment:
            by_node.setdefault(node_id, []).append(i)
        for node_id, idxs in by_node.items():
            with self._lock:
                node = self._nodes.get(node_id)
            if node is None or not node.alive:
                continue
            try:
                self._raylet_client(node).call(
                    "return_bundles", (pg_id, idxs), timeout=10.0
                )
            except Exception:
                logger.warning(
                    "return_bundles(%s, %s) failed", pg_id.hex()[:8], idxs
                )

    # ------------------------------------------------------------------
    # jobs + task events
    # ------------------------------------------------------------------

    def rpc_add_job(self, conn, payload):
        with self._lock:
            self._jobs[payload["job_id"].hex()] = payload
            if self._storage is not None:
                self._storage.put("jobs", payload["job_id"].hex(), payload)
        return True

    def rpc_get_jobs(self, conn, payload=None):
        with self._lock:
            return list(self._jobs.values())

    # ------------------------------------------------------------------
    # cluster event log
    # ------------------------------------------------------------------

    def _record_cluster_event(
        self, type: str, message: str, severity: str = "INFO", **fields
    ):
        """Append one structured event; raylets/autoscalers report theirs
        via rpc_report_cluster_event, GCS-internal transitions call this
        directly."""
        event = {
            "type": type,
            "severity": severity,
            "message": message,
            "ts": time.time(),
            **fields,
        }
        # distributed tracing: the RPC dispatch installed the reporting
        # caller's context on this thread, so any event recorded while
        # handling a traced request joins that trace (NODE_DRAINING from a
        # traced drain call, etc.) unless the reporter stamped one already
        if _trace._active and "trace_id" not in event:
            ctx = _trace.current()
            if ctx is not None and ctx.sampled:
                event["trace_id"] = ctx.trace_id
        with self._lock:
            self._cluster_events.append(event)
            if len(self._cluster_events) > 10_000:
                del self._cluster_events[: len(self._cluster_events) - 10_000]
        self._publish("cluster_events", event)

    def rpc_report_cluster_event(self, conn, payload):
        event = dict(payload)
        # OOM kills: the raylet only knows the victim's worker_id — resolve
        # the trace the victim was executing from its latest RUNNING task
        # event so the kill shows up inside the affected trace
        if (
            event.get("type") == "WORKER_OOM_KILLED"
            and "trace_id" not in event
            and event.get("worker_id")
        ):
            wid = event["worker_id"]
            with self._lock:
                running = [
                    e
                    for e in self._task_events
                    if e["state"] == "RUNNING"
                    and e.get("worker_id") == wid
                    and e.get("trace_id")
                ]
            if running:
                event["trace_id"] = max(running, key=lambda e: e["ts"])["trace_id"]
        self._record_cluster_event(
            event.pop("type", "UNKNOWN"),
            event.pop("message", ""),
            event.pop("severity", "INFO"),
            **event,
        )
        return True

    def rpc_list_cluster_events(self, conn, payload=None):
        with self._lock:
            events = list(self._cluster_events)
        if isinstance(payload, dict):
            etype = payload.get("type")
            if etype:
                events = [e for e in events if e["type"] == etype]
            limit = payload.get("limit")
            if limit:
                events = events[-int(limit):]
        return events

    def rpc_add_task_events(self, conn, payload):
        with self._lock:
            self._task_events.extend(payload)
            limit = GlobalConfig.task_events_buffer_size
            if len(self._task_events) > limit:
                del self._task_events[: len(self._task_events) - limit]
        return True

    def rpc_get_task_events(self, conn, payload=None):
        with self._lock:
            return list(self._task_events)

    def rpc_locate_worker(self, conn, payload):
        """Resolve a task or actor id (full hex or prefix) to the worker and
        node that execute(d) it — the log plane's ``get_log(task_id=...)``
        resolution step, answered from GCS-held state instead of shipping
        the whole event table to the client."""
        p = payload or {}
        tid = p.get("task_id")
        if tid:
            with self._lock:
                # RUNNING events carry the *executing* worker's identity
                # (PENDING/FINISHED are emitted by the owner)
                events = [
                    e
                    for e in self._task_events
                    if e["state"] == "RUNNING"
                    and e["task_id"].startswith(tid)
                    and e.get("worker_id")
                ]
            if not events:
                return None
            ev = max(events, key=lambda e: e["ts"])
            return {
                "task_id": ev["task_id"],
                "worker_id": ev["worker_id"],
                "node_id": ev.get("node_id") or "",
            }
        aid = p.get("actor_id")
        if aid:
            with self._lock:
                for info in self._actors.values():
                    if (
                        info.actor_id.hex().startswith(aid)
                        and info.worker_id is not None
                    ):
                        return {
                            "actor_id": info.actor_id.hex(),
                            "worker_id": info.worker_id.hex(),
                            "node_id": info.node_id.hex() if info.node_id else "",
                        }
        return None

    def rpc_get_config(self, conn, payload=None):
        return GlobalConfig.dump()

    # ------------------------------------------------------------------
    # metrics (reference: per-node metrics agent -> Prometheus; here each
    # process reports cumulative snapshots keyed by pid)
    # ------------------------------------------------------------------

    def rpc_report_metrics(self, conn, payload):
        reporter, records = payload  # cluster-unique "worker_id:pid" key
        with self._lock:
            self._metrics[reporter] = (time.time(), records)
        self._maybe_fold_metrics()
        return True

    def _live_metric_records(self, now: Optional[float] = None):
        """Snapshot of per-process metric reports, evicting reporters that
        stopped refreshing (dead workers — like a Prometheus target
        dropping out of a scrape). A pruned reporter's final counter and
        histogram values fold into the tombstone accumulator first, so
        cluster totals stay monotonic and ``rate()`` never sees a phantom
        negative spike when a worker exits; its gauges (point-in-time
        readings from a dead process) do disappear. Returns
        ``(tombstone_records, [per-live-reporter record lists])``."""
        stale_after = 12 * GlobalConfig.metrics_report_period_s
        if now is None:
            now = time.time()
        with self._lock:
            for reporter in [
                r for r, (ts, _) in self._metrics.items()
                if now - ts > stale_after
            ]:
                _, records = self._metrics.pop(reporter)
                metrics_ts.merge_records(
                    self._metrics_tombstones,
                    [rec for rec in records if rec["type"] != "gauge"],
                )
            return (
                list(self._metrics_tombstones.values()),
                [records for _, records in self._metrics.values()],
            )

    def _aggregate_metrics(
        self, name_filter: Optional[str] = None, now: Optional[float] = None
    ) -> List[Dict[str, Any]]:
        """Cluster-wide aggregate: sum counters + histogram buckets (over
        live reporters AND tombstoned exited ones), last-write gauges."""
        tombstones, per_proc = self._live_metric_records(now)
        merged: Dict[str, Dict[str, Any]] = {}
        metrics_ts.merge_records(merged, tombstones, name_filter)
        for records in per_proc:
            metrics_ts.merge_records(merged, records, name_filter)
        return list(merged.values())

    def rpc_get_metrics(self, conn, payload=None):
        return self._aggregate_metrics(payload)

    # -- time-series retention + SLO evaluation ------------------------

    def _maybe_fold_metrics(self):
        """At most once per report period: fold the current cluster
        aggregate into the retained rings and run the SLO engine. Driven
        by incoming report_metrics traffic (reporters push every period,
        loaded or not, so evaluation cadence is sustained)."""
        if not self._slo_lock.acquire(blocking=False):
            return  # another report is already folding
        transitions = []
        firing = series = dropped = None
        try:
            now = time.time()
            if now - self._ts_last_fold < GlobalConfig.metrics_report_period_s:
                return
            self._ts_last_fold = now
            self._ts_store.append_records(now, self._aggregate_metrics(now=now))
            transitions = self._slo_engine.evaluate(
                now, self._stale_metric_names(now)
            )
            firing = self._slo_engine.firing_count()
            series = self._ts_store.series_count()
            dropped = self._ts_store.dropped_series
        finally:
            self._slo_lock.release()
        if firing is None:
            return
        from ray_tpu._private import internal_metrics

        internal_metrics.set_gauge("ray_tpu_alerts_firing", float(firing))
        internal_metrics.set_gauge("ray_tpu_metrics_ts_series", float(series))
        last_dropped = getattr(self, "_ts_dropped_reported", 0)
        if dropped > last_dropped:
            internal_metrics.inc(
                "ray_tpu_metrics_ts_dropped_series_total",
                dropped - last_dropped,
            )
            self._ts_dropped_reported = dropped
        for t in transitions:
            alert = t["alert"]
            win = (alert.get("windows") or [{}])[0]
            if t["to"] == "firing":
                exemplars = [e["trace_id"] for e in alert.get("exemplars", [])]
                self._record_cluster_event(
                    "ALERT_FIRING",
                    f"SLO {t['name']} firing: value={alert.get('value')} "
                    f"threshold={win.get('threshold')}",
                    severity="WARNING",
                    rule=t["name"],
                    value=alert.get("value"),
                    exemplars=exemplars,
                )
            elif t["from"] == "firing":
                self._record_cluster_event(
                    "ALERT_RESOLVED",
                    f"SLO {t['name']} resolved: value={alert.get('value')}",
                    severity="INFO",
                    rule=t["name"],
                    value=alert.get("value"),
                )

    def _stale_metric_names(self, now: float):
        """Metric names whose reporters stopped refreshing recently enough
        that we can't tell outage from partition — SLO rules over them
        hold their alert state instead of flapping."""
        stale_after = (
            GlobalConfig.metrics_stale_after_s
            or 3 * GlobalConfig.metrics_report_period_s
        )
        names = set()
        with self._lock:
            for ts, records in self._metrics.values():
                if now - ts > stale_after:
                    names.update(rec["name"] for rec in records)
        return frozenset(names)

    def rpc_query_metrics(self, conn, payload=None):
        """Retained history: ``{"list": True}`` for known names, else
        ``{"name", "tags"?, "window_s"?}`` -> samples (see
        TimeSeriesStore.query)."""
        p = payload or {}
        if p.get("list"):
            return {"names": self._ts_store.names()}
        return self._ts_store.query(
            p.get("name", ""), p.get("tags"), p.get("window_s")
        )

    def rpc_slo_define(self, conn, payload):
        """Define (or replace) SLO rules; payload is one rule dict or a
        list of them. Validation errors raise back to the caller."""
        rules = payload if isinstance(payload, list) else [payload]
        with self._slo_lock:
            out = [self._slo_engine.define(r) for r in rules]
        return out if isinstance(payload, list) else out[0]

    def rpc_slo_remove(self, conn, payload):
        with self._slo_lock:
            return self._slo_engine.remove(str(payload))

    def rpc_slo_list(self, conn, payload=None):
        with self._slo_lock:
            return self._slo_engine.rules()

    def rpc_alerts(self, conn, payload=None):
        with self._slo_lock:
            return self._slo_engine.alerts()

    def rpc_trace_spans(self, conn, payload=None):
        """Trace-harvest GCS leg: this process's own span ring (the GCS
        records rpc-server spans for traced control calls)."""
        return _trace.snapshot()

    # -- SLO controller (controller.py) --------------------------------

    def rpc_controller_enable(self, conn, payload=None):
        return self._controller.enable()

    def rpc_controller_disable(self, conn, payload=None):
        return self._controller.disable()

    def rpc_controller_status(self, conn, payload=None):
        return self._controller.status()

    def rpc_controller_rules(self, conn, payload=None):
        return self._controller.rule_rows()

    def rpc_controller_log(self, conn, payload=None):
        return self._controller.log(int((payload or {}).get("limit", 50)))

    def rpc_perf_profile(self, conn, payload=None):
        """Cluster sampling profiler, GCS leg: sample THIS process (the
        handler blocks a dispatch-pool thread for the window — the pool
        is dynamic, so concurrent control traffic keeps flowing)."""
        from ray_tpu._private import perf as _perf_mod

        p = payload or {}
        return _perf_mod.sample_self(
            min(float(p.get("duration_s", 2.0)), 30.0),
            float(p.get("hz", 100.0)),
            role="gcs",
        )

    def stop(self):
        self._stopped.set()
        self._controller.shutdown()
        self.server.stop()
        self._actor_sched_pool.shutdown(wait=False)
        self._pg_sched_pool.shutdown(wait=False)
        with self._lock:
            for c in self._raylet_clients.values():
                c.close()
            for c in self._worker_clients.values():
                c.close()
        if self._storage is not None:
            self._storage.close()
