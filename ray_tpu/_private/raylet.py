"""Raylet: the per-node manager.

Owns the worker pool, grants lease-based worker leases against the node's
resource view, embeds the plasma store's metadata service, heartbeats
resources to the GCS, and reports worker deaths (reference: src/ray/raylet/
node_manager.cc:1848 HandleRequestWorkerLease, worker_pool.h:156,
local_task_manager.cc:101).

One raylet == one node. The in-process ``Cluster`` test fixture starts
several raylets against one GCS to simulate multi-node (reference:
python/ray/cluster_utils.py:99).
"""

from __future__ import annotations

import json
import logging
import os
import random
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import fault_injection
from ray_tpu._private import internal_metrics
from ray_tpu._private import object_store
from ray_tpu._private import trace as _trace
from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import ActorID, NodeID, ObjectID, WorkerID
from ray_tpu._private.rpc import RpcClient, RpcServer, ServerConn
from ray_tpu._private.runtime_env_packaging import (
    ensure_extracted,
    runtime_env_key,
)

logger = logging.getLogger(__name__)


class ForkedProc:
    """Popen-shaped handle for a worker forked by the fork-server template.

    The child is the TEMPLATE's child, not ours, so Popen semantics are
    emulated with signals: liveness via ``kill(pid, 0)`` (the template reaps
    zombies promptly, so a dead child stops answering within its reap tick).
    """

    def __init__(self, pid: int):
        self.pid = pid
        self.returncode: Optional[int] = None
        # pidfd (linux 5.3+): race-free liveness + signaling. The template
        # is the child's parent and reaps it promptly, so the PID can be
        # recycled while this raylet still tracks it — kill(pid, 0) against
        # a recycled PID reports an unrelated process as "our worker", and
        # signals would hit that stranger (ADVICE r4). A pidfd pins the
        # kernel's process identity: it polls readable exactly when OUR
        # child exits, regardless of reaping or PID reuse.
        self._pidfd: Optional[int] = None
        try:
            self._pidfd = os.pidfd_open(pid)
        except (AttributeError, OSError):
            # already exited+reaped (dead) or pre-5.3 kernel (fall back)
            try:
                os.kill(pid, 0)
            except (ProcessLookupError, PermissionError):
                self.returncode = -1

    def __del__(self):
        if self._pidfd is not None:
            try:
                os.close(self._pidfd)
            except OSError:
                pass

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        if self._pidfd is not None:
            import select as _select

            try:
                # poll(), not select(): a pidfd numbered >= FD_SETSIZE
                # (plenty of sockets on a busy raylet) makes select raise
                # ValueError and would kill the monitor loop
                p = _select.poll()
                p.register(self._pidfd, _select.POLLIN)
                ready = p.poll(0)
            except (OSError, ValueError):
                ready = [(self._pidfd, 0)]
            if ready:
                # exit status is unobservable (the template is the parent
                # and already reaped it); crash detail lives in the worker
                # log, -1 just marks "gone"
                self.returncode = -1
            return self.returncode
        try:
            os.kill(self.pid, 0)
            return None
        except (ProcessLookupError, PermissionError):
            self.returncode = -1
            return self.returncode

    def _signal(self, sig: int):
        if self._pidfd is not None:
            import signal as _signal_mod

            try:
                _signal_mod.pidfd_send_signal(self._pidfd, sig)
            except (AttributeError, ProcessLookupError, OSError):
                pass
            return
        try:
            os.kill(self.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass

    def terminate(self):
        self._signal(15)

    def kill(self):
        self._signal(9)

    def send_signal(self, sig: int):
        self._signal(sig)

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("forked-worker", timeout)
            time.sleep(0.02)
        return self.returncode


class ForkServer:
    """Process-wide client to ONE worker fork-server template (shared by
    every in-process raylet — per-fork requests carry the full worker
    identity, so multi-raylet test clusters reuse a single template).
    Template boot (~2-5 s: interpreter + framework imports) is paid once,
    lazily, on the first CPU-worker spawn."""

    _instance: Optional["ForkServer"] = None
    _ilock = threading.Lock()

    @classmethod
    def get(cls, session_dir: str) -> "ForkServer":
        with cls._ilock:
            if cls._instance is None or not cls._instance.alive():
                old = cls._instance
                if old is not None:
                    # reap the dead template (poll() waits the zombie) and
                    # release its socket before standing up a replacement
                    try:
                        old._proc.poll()
                        if old._conn is not None:
                            old._conn.close()
                    except OSError:
                        pass
                cls._instance = cls(session_dir)
                import atexit

                atexit.register(cls._instance.stop)
            return cls._instance

    def __init__(self, session_dir: str):
        import socket as _socket

        self._lock = threading.Lock()
        self._sock_path = os.path.join(
            session_dir, f"forkserver_{os.getpid()}.sock"
        )
        env = dict(os.environ)
        env["RAYTPU_FORKSERVER_SOCK"] = self._sock_path
        env["JAX_PLATFORMS"] = "cpu"  # forked workers are CPU workers
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (pkg_root, env.get("PYTHONPATH", "")) if p
        )
        log_path = os.path.join(session_dir, "logs", "forkserver.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        with open(log_path, "ab") as logfile:
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.worker_forkserver"],
                env=env,
                stdout=logfile,
                stderr=subprocess.STDOUT,
            )
        # the template accepts connections only after its imports finish
        deadline = time.monotonic() + 120
        self._conn = None
        while time.monotonic() < deadline:
            if self._proc.poll() is not None:
                raise RuntimeError(
                    f"fork-server template exited with {self._proc.returncode} "
                    f"(see {log_path})"
                )
            try:
                c = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
                c.connect(self._sock_path)
                # a wedged template (mid-fork signal, partial write) must
                # surface as an exception, not block every future spawn on
                # the node behind self._lock forever (ADVICE r4): timed out
                # requests mark this instance dead and the Popen fallback +
                # ForkServer.get() replacement take over
                c.settimeout(15.0)
                self._conn = c
                break
            except OSError:
                time.sleep(0.1)
        if self._conn is None:
            raise RuntimeError("fork-server template did not come up")

    def alive(self) -> bool:
        return self._proc.poll() is None and self._conn is not None

    def fork_worker(
        self,
        env: Dict[str, str],
        log_path: str,
        cwd: Optional[str],
        sys_path: List[str],
    ) -> ForkedProc:
        import socket as _socket

        from ray_tpu._private.worker_forkserver import _read_msg, _send_msg

        with self._lock:
            try:
                _send_msg(
                    self._conn,
                    {"env": env, "log_path": log_path, "cwd": cwd, "sys_path": sys_path},
                )
                reply = _read_msg(self._conn)
            except (_socket.timeout, OSError) as e:
                # template wedged or died: kill this instance so alive() is
                # False (ForkServer.get stands up a replacement) and let the
                # caller's Popen fallback handle THIS spawn. The template
                # PROCESS is killed too — a timed-out request cannot be
                # cancelled, so a merely-slow template could otherwise still
                # complete the fork late and leak an orphan worker.
                conn, self._conn = self._conn, None
                try:
                    conn.close()
                except OSError:
                    pass
                try:
                    self._proc.kill()
                except OSError:
                    pass
                raise RuntimeError(f"fork-server request failed: {e}") from e
        if not reply or "pid" not in reply:
            raise RuntimeError("fork-server did not return a pid")
        return ForkedProc(reply["pid"])

    def stop(self):
        try:
            from ray_tpu._private.worker_forkserver import _send_msg

            with self._lock:
                if self._conn is not None:
                    _send_msg(self._conn, {"op": "shutdown"})
        except OSError:
            pass
        try:
            self._proc.terminate()
        except OSError:
            pass


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, proc: Optional[subprocess.Popen], tpu: bool = False,
                 env_hash: tuple = ()):
        self.worker_id = worker_id
        self.proc = proc
        self.tpu = tpu
        self.env_hash = env_hash  # runtime_env env_vars this worker runs with
        self.address: Optional[Tuple[str, int]] = None
        self.registered = threading.Event()
        self.idle = True
        self.actor_ids: List[ActorID] = []
        self.conn: Optional[ServerConn] = None
        self.last_idle_at = time.monotonic()
        self.lease_resources: Dict[str, float] = {}


class Raylet:
    # data-plane liveness probes must answer even when the dispatch pool
    # is saturated by long-poll handlers — that saturation is exactly the
    # gray failure the probes exist to detect
    RPC_INLINE = ("ping",)

    def __init__(
        self,
        session_dir: str,
        gcs_address: Tuple[str, int],
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        store_capacity: Optional[int] = None,
        node_name: str = "node",
    ):
        self.node_id = NodeID.from_random()
        self.session_dir = session_dir
        self.gcs_address = gcs_address
        _trace.init_from_config()
        self.server = RpcServer(f"raylet-{node_name}")
        # chaos attribution: this node's identity rides on every client,
        # server, and store hook so partition/kill/slow-read rules resolve
        # per logical node even when several nodes share one process
        self._chaos_identity = fault_injection.identity_for(
            self.node_id, self.server.address
        )
        self.server.chaos_identity = self._chaos_identity
        self._chaos_armed: Optional[fault_injection.ArmedSchedule] = None
        self.store = object_store.PlasmaStore(
            session_dir, capacity=store_capacity, name=node_name
        )
        self.store.chaos_identity = self._chaos_identity
        # same-process workers (the head-node driver, in-process test
        # clusters) bypass the RPC hop for store metadata ops
        object_store.register_local_store(self.server.address, self.store)
        if resources is None:
            resources = {"CPU": float(os.cpu_count() or 1)}
        resources.setdefault("node", 1.0)
        self.total_resources = dict(resources)
        self.available = dict(resources)
        self.labels = dict(labels or {})
        self.labels["store_path"] = self.store.path
        self.labels["store_capacity"] = str(self.store.capacity)
        self.labels.setdefault("node_name", node_name)
        self._workers: Dict[WorkerID, WorkerHandle] = {}
        # spawns reserved but not yet in _workers, keyed by (tpu, env_hash):
        # the lease loop's parallelism gate counts these, so N racing
        # requests can't all pass the gate while the first Popen is in flight
        self._spawns_inflight: Dict[tuple, int] = {}
        self._res_cv = threading.Condition()
        self._peers: Dict[Tuple[str, int], RpcClient] = {}
        self._peers_lock = threading.Lock()
        self._prepared_bundles: Dict[Tuple[Any, int], Dict[str, float]] = {}
        self._committed_bundles: Dict[Tuple[Any, int], Dict[str, float]] = {}
        # unfulfilled lease requests currently parked in
        # rpc_request_worker_lease, keyed by request identity; reported in
        # heartbeats as the autoscaler's demand signal (the reference's
        # resource_load via ray_syncer)
        self._demand: Dict[int, Dict[str, float]] = {}
        # spill watermark: heartbeats diff against it to report OBJECT_SPILL
        # cluster events exactly once per spill burst
        self._spill_event_bytes = 0
        # graceful drain (GCS ALIVE->DRAINING->DEAD): a draining raylet
        # redirects new lease requests and migrates its primary objects
        # before deregistering
        self._draining = False
        self._drain_stop_scheduled = False
        self._stopped = threading.Event()
        self.server.register_all(self)
        self.server.on_disconnect = self._on_disconnect
        # the gossiped cluster resource view (GCS resource_view channel);
        # spillback decisions read this cache instead of a synchronous
        # get_nodes RPC per decision (reference: ray_syncer.h:39 — the
        # NodeResourceInfo downstream half)
        self._peer_view: Dict[str, Any] = {"at": 0.0, "nodes": []}
        self.gcs = RpcClient(
            gcs_address, on_notify=self._on_gcs_notify, prefer_local=True
        )
        self.gcs.chaos_identity = self._chaos_identity
        self.gcs.call(
            "register_node",
            (self.node_id, self.server.address, self.total_resources, self.labels),
        )
        try:
            self.gcs.call("subscribe", "resource_view", timeout=5.0)
        except Exception:
            pass  # older GCS: spillback falls back to get_nodes
        try:
            self.gcs.call("subscribe", "chaos", timeout=5.0)
            blob = self.gcs.call("kv_get", ("chaos", "schedule"), timeout=5.0)
            if blob:
                # late joiner: a schedule armed before this node existed
                self._arm_chaos(json.loads(blob))
        except Exception:
            pass  # older GCS without a chaos plane: stay disarmed
        # gray-failure self-probes feed heartbeat payloads (see _probe_loop)
        self._probe_failures: Dict[str, int] = {}
        self._probe_snapshot: Dict[str, Any] = {"healthy": True}
        self._probe_rr = 0
        self._probe_thread = threading.Thread(
            target=self._probe_loop, name=f"probe-{node_name}", daemon=True
        )
        self._probe_thread.start()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop, daemon=True)
        self._hb_thread.start()
        # memory monitor: kill the newest-leased worker under node memory
        # pressure (reference: common/memory_monitor.h:52 + the
        # retriable-FIFO worker killing policy, worker_killing_policy.cc)
        if GlobalConfig.memory_monitor_enabled:
            self._memmon_thread = threading.Thread(
                target=self._memory_monitor_loop,
                name=f"memmon-{node_name}",
                daemon=True,
            )
            self._memmon_thread.start()
        # tail worker logs -> GCS "logs" pubsub -> driver stdout
        # (reference: _private/log_monitor.py:102 LogMonitor,
        # check_log_files_and_publish_updates:309)
        self._log_offsets: Dict[str, int] = {}
        self._log_thread = threading.Thread(
            target=self._log_monitor_loop, name=f"logmon-{node_name}", daemon=True
        )
        self._log_thread.start()
        for _ in range(GlobalConfig.worker_pool_prestart):
            self._spawn_worker()

    @property
    def address(self) -> Tuple[str, int]:
        return self.server.address

    # ------------------------------------------------------------------
    # worker pool
    # ------------------------------------------------------------------

    def _spawn_worker(self, tpu: bool = False,
                      runtime_env: Optional[Dict[str, Any]] = None) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        renv = runtime_env or {}
        # env OVERRIDES relative to this process's environment: applied on
        # top of os.environ for the Popen path, or inside the forked child
        # for the fork-server path (whose template inherited os.environ)
        overrides: Dict[str, str] = {}
        if renv.get("env_vars"):
            # runtime_env: workers are pooled per runtime_env hash (the
            # reference keys its worker pool the same way)
            overrides.update(renv["env_vars"])
        # working_dir / py_modules: extract once per node into the session
        # cache; the worker starts with cwd inside the working_dir and the
        # extracted roots on PYTHONPATH (reference:
        # _private/runtime_env/{working_dir,py_modules}.py)
        cwd = None
        env_paths: List[str] = []
        if renv.get("working_dir"):
            cwd = ensure_extracted(
                self.session_dir, renv["working_dir"], self.gcs.call
            )
            env_paths.append(cwd)
        for uri in renv.get("py_modules") or ():
            env_paths.append(
                ensure_extracted(self.session_dir, uri, self.gcs.call)
            )
        from ray_tpu._private import rpc as rpc_mod

        if rpc_mod.session_token():
            overrides["RAYTPU_AUTH_TOKEN"] = rpc_mod.session_token()
        overrides["RAYTPU_WORKER_ID"] = worker_id.hex()
        overrides["RAYTPU_RAYLET_HOST"] = self.server.host
        overrides["RAYTPU_RAYLET_PORT"] = str(self.server.port)
        overrides["RAYTPU_GCS_HOST"] = self.gcs_address[0]
        overrides["RAYTPU_GCS_PORT"] = str(self.gcs_address[1])
        overrides["RAYTPU_SESSION_DIR"] = self.session_dir
        overrides["RAYTPU_NODE_ID"] = self.node_id.hex()
        overrides["PYTHONUNBUFFERED"] = "1"  # prints stream to the log monitor
        # per-node log dir: each raylet's log monitor tails only ITS OWN
        # workers (a shared dir made every monitor scan every worker's log —
        # O(nodes x workers) file churn and duplicate publishes)
        log_path = os.path.join(
            self.session_dir, "logs", self.node_id.hex()[:12],
            f"worker-{worker_id.hex()[:12]}.log",
        )
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        env_hash = runtime_env_key(renv)
        # fast path: fork from the pre-imported template (~10 ms) instead of
        # booting an interpreter (~2 s). TPU workers keep the Popen path —
        # the template pinned JAX_PLATFORMS=cpu at its own import time — and
        # pip envs need a different interpreter entirely.
        from ray_tpu._private.runtime_env_plugins import (
            apply_plugins,
            check_fields_known,
            plugin_fields,
        )

        # a field with no plugin registered IN THIS PROCESS fails the spawn
        # loudly (the driver validated against ITS registry; silently
        # dropping the field here would hand out a worker missing its env)
        check_fields_known(renv)
        needs_plugin = any(renv.get(f) is not None for f in plugin_fields())
        if (
            GlobalConfig.worker_forkserver
            and not tpu
            and not renv.get("pip")
            and not needs_plugin
        ):
            try:
                proc = ForkServer.get(self.session_dir).fork_worker(
                    overrides, log_path, cwd, env_paths
                )
                handle = WorkerHandle(worker_id, proc, tpu=tpu, env_hash=env_hash)
                with self._res_cv:
                    self._workers[worker_id] = handle
                return handle
            except Exception:
                logger.exception(
                    "fork-server spawn failed; falling back to subprocess"
                )
                # a timed-out fork may still complete late in the (killed)
                # template; a FRESH worker id for the fallback guarantees the
                # two can never collide in the registration table
                worker_id = WorkerID.from_random()
                overrides["RAYTPU_WORKER_ID"] = worker_id.hex()
                log_path = os.path.join(
                    self.session_dir, "logs", self.node_id.hex()[:12],
                    f"worker-{worker_id.hex()[:12]}.log",
                )
        env = dict(os.environ)
        env.update(overrides)
        if tpu:
            # pinned, so a TPU runtime that fails to initialize is an error
            # in the worker and never a quiet fall back to the CPU backend
            # (whatever list of platforms the driver's environment carries)
            env["JAX_PLATFORMS"] = "tpu"
        else:
            # CPU workers must not claim the TPU runtime
            env["JAX_PLATFORMS"] = "cpu"
        # ensure the worker can import ray_tpu regardless of the driver's cwd;
        # runtime_env roots come first so working_dir modules shadow others
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (*env_paths, pkg_root, env.get("PYTHONPATH", "")) if p
        )
        interpreter = sys.executable
        if renv.get("pip"):
            # per-requirements venv (cached by hash); the worker runs under
            # its interpreter so the extra packages are importable
            # (reference: _private/runtime_env/pip.py)
            from ray_tpu._private.runtime_env_pip import ensure_pip_env

            interpreter = ensure_pip_env(
                self.session_dir,
                list(renv["pip"]),
                renv.get("pip_find_links"),
            )
        argv = [interpreter, "-m", "ray_tpu._private.default_worker"]
        if needs_plugin:
            # conda swaps the interpreter, container wraps the command
            # (reference: _private/runtime_env/plugin.py dispatch)
            env, argv = apply_plugins(renv, self.session_dir, env, argv)
        logfile = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                argv,
                env=env,
                cwd=cwd,
                stdout=logfile,
                stderr=subprocess.STDOUT,
            )
        finally:
            logfile.close()  # the child holds its own inherited fd
        handle = WorkerHandle(
            worker_id, proc, tpu=tpu, env_hash=env_hash,
        )
        with self._res_cv:
            self._workers[worker_id] = handle
        return handle

    def rpc_register_worker(self, conn: ServerConn, payload):
        worker_id, address, pid = payload["worker_id"], tuple(payload["address"]), payload["pid"]
        is_driver = payload.get("is_driver", False)
        with self._res_cv:
            handle = self._workers.get(worker_id)
            if handle is None:  # driver or externally started worker
                handle = WorkerHandle(worker_id, None)
                self._workers[worker_id] = handle
            handle.address = address
            handle.conn = conn
            handle.registered.set()
            handle.idle = not is_driver  # drivers are never leased out
            handle.last_idle_at = time.monotonic()
            self._res_cv.notify_all()
        conn.meta["worker_id"] = worker_id
        return {"store_path": self.store.path, "store_capacity": self.store.capacity,
                "node_id": self.node_id}

    def _on_disconnect(self, conn: ServerConn):
        worker_id = conn.meta.get("worker_id")
        if worker_id is None or self._stopped.is_set():
            # during drain the node death was already reported via
            # unregister_node; per-worker reports here would double-count
            return
        with self._res_cv:
            handle = self._workers.pop(worker_id, None)
            if handle is None:
                return
            self._return_lease_resources_locked(handle)
            self._res_cv.notify_all()
        if handle.proc is not None and handle.proc.poll() is None:
            handle.proc.terminate()
        logger.info("worker %s died (actors=%d)", worker_id.hex()[:8], len(handle.actor_ids))
        try:
            self.gcs.call(
                "report_worker_death",
                {
                    "node_id": self.node_id,
                    "worker_id": worker_id,
                    "actor_ids": handle.actor_ids,
                    "cause": "worker process died",
                },
            )
        except Exception:
            pass

    # ------------------------------------------------------------------
    # leases (two-level scheduling: callers lease workers from this node)
    # ------------------------------------------------------------------

    def _on_gcs_notify(self, channel: str, message: Any):
        if channel == "resource_view":
            self._peer_view = {
                "at": time.monotonic(),
                "nodes": message.get("nodes") or [],
            }
        elif channel == "chaos":
            if message.get("event") == "cleared":
                self._chaos_armed = None
                fault_injection.disarm()
            else:
                schedule = message.get("schedule")
                if schedule:
                    self._arm_chaos(schedule)

    # ------------------------------------------------------------------
    # chaos plane (fault_injection.py)
    # ------------------------------------------------------------------

    def _arm_chaos(self, schedule: Dict[str, Any]):
        """Arm a schedule in this process and execute any kill_worker /
        kill_raylet rules aimed at this node (once per rule, off-thread —
        a kill must not run on the poller's notify path)."""
        armed = fault_injection.arm(
            schedule,
            local_node_id=self.node_id.hex(),
            local_addresses=[self.server.address],
        )
        if armed is None:
            self._chaos_armed = None
            return
        self._chaos_armed = armed
        logger.warning(
            "chaos schedule v%s armed on %s (%d rules, seed=%s)",
            armed.version, self.labels.get("node_name"), len(armed.rules),
            armed.seed,
        )
        for item in fault_injection.take_process_actions(
            armed, identity=self._chaos_identity
        ):
            threading.Thread(
                target=self._execute_chaos_kill, args=(item,), daemon=True
            ).start()

    def _execute_chaos_kill(self, item: Dict[str, Any]):
        rule = item["rule"]
        grace = float(rule.get("delay_ms", 0) or 0) / 1000.0
        if grace > 0:
            time.sleep(grace)
        if rule["action"] == "kill_worker":
            with self._res_cv:
                victims = sorted(
                    (w for w in self._workers if self._workers[w].proc is not None),
                    key=lambda w: w.hex(),
                )
            if not victims:
                return
            victim = item["rng"].choice(victims)  # seeded: reproducible pick
            handle = self._workers.get(victim)
            if handle is None or handle.proc is None:
                return
            logger.warning("chaos: killing worker %s", victim)
            try:
                handle.proc.kill()
            except Exception:
                pass
        elif rule["action"] == "kill_raylet":
            logger.warning(
                "chaos: killing raylet %s", self.labels.get("node_name")
            )
            # no unregister: the GCS must discover the death the hard way
            # (missed heartbeats), exactly like a crashed node
            self.stop(unregister=False)

    def rpc_ping(self, conn: ServerConn, payload=None):
        """Data-plane liveness probe (inline: answers even when the
        dispatch pool is wedged). Subject to chaos hooks like any RPC, so
        a partitioned peer's probes genuinely fail."""
        return True

    def rpc_chaos_report(self, conn: ServerConn, payload=None):
        armed = self._chaos_armed
        return armed.local_report() if armed is not None else None

    def _probe_loop(self):
        """Self-probe: round-robin one peer raylet data-plane ping per tick
        plus a local store health check. Consecutive failures are counted
        PER PEER (a healthy peer next tick must not reset a failing peer's
        streak); any streak >= probe_failure_threshold flips the snapshot
        unhealthy. The snapshot rides heartbeats to the GCS, which is the
        gray-failure signal: heartbeats arriving + probes failing =>
        DEGRADED."""
        while not self._stopped.wait(GlobalConfig.chaos_probe_period_s):
            threshold = GlobalConfig.probe_failure_threshold
            peers = sorted(
                tuple(n["address"])
                for n in self._peer_view["nodes"]
                if n.get("alive") and n.get("node_id") != self.node_id
            )
            live = {f"{a[0]}:{a[1]}" for a in peers}
            for k in [k for k in self._probe_failures if k not in live]:
                # a peer that left the view (e.g. escalated to DEAD) must
                # not pin this node unhealthy forever
                self._probe_failures.pop(k, None)
            if peers:
                addr = peers[self._probe_rr % len(peers)]
                self._probe_rr += 1
                key = f"{addr[0]}:{addr[1]}"
                try:
                    self._peer_client(addr).call(
                        "ping", None, timeout=GlobalConfig.probe_timeout_s
                    )
                    self._probe_failures.pop(key, None)
                except Exception:
                    self._probe_failures[key] = (
                        self._probe_failures.get(key, 0) + 1
                    )
            store_ok = True
            try:
                self.store.stats()
            except Exception:
                store_ok = False
            failing = {
                k: v for k, v in self._probe_failures.items() if v >= threshold
            }
            snapshot: Dict[str, Any] = {
                "healthy": store_ok and not failing,
            }
            detail = []
            if failing:
                detail.append(f"unreachable peers: {sorted(failing)}")
            if not store_ok:
                detail.append("local store unhealthy")
            if detail:
                snapshot["detail"] = "; ".join(detail)
            self._probe_snapshot = snapshot

    def _find_spill_node(
        self, resources: Dict[str, float], against: str, fresh: bool = False
    ) -> Optional[Tuple[str, int]]:
        """Pick another node that fits the request, preferring the gossiped
        resource view (bounded staleness <= 3 broadcast periods) over a
        synchronous GCS round-trip (the reference's spillback reply,
        direct_task_transport.cc:501, fed by the ray_syncer view).

        ``fresh=True`` forces the synchronous fetch: callers about to make
        a CORRECTNESS decision (declaring a request globally infeasible)
        must not do it from a stale cache — a node registered milliseconds
        ago may be missing from the last broadcast, and "infeasible" is a
        user-visible error, not a routing hint."""
        view = self._peer_view
        max_age = GlobalConfig.resource_broadcast_period_s * 3
        if (
            not fresh
            and view["nodes"]
            and time.monotonic() - view["at"] <= max_age
        ):
            nodes = view["nodes"]
        else:
            try:
                nodes = self.gcs.call("get_nodes", timeout=5.0)
            except Exception:
                return None
        best = None
        best_slack = None
        for n in nodes:
            if not n["alive"] or n["node_id"] == self.node_id:
                continue
            if n.get("state") in ("DEGRADED", "DRAINING"):
                continue  # degraded/draining: no new spillback leases
            pool = n["resources"] if against == "total" else n["available"]
            if all(pool.get(k, 0) >= v for k, v in resources.items() if v > 0):
                slack = min(
                    (n["available"].get(k, 0) - v for k, v in resources.items()),
                    default=0.0,
                )
                if best_slack is None or slack > best_slack:
                    best, best_slack = tuple(n["address"]), slack
        return best

    def rpc_request_worker_lease(self, conn: ServerConn, payload) -> Optional[Dict[str, Any]]:
        resources: Dict[str, float] = dict(payload.get("resources") or {"CPU": 1.0})
        actor_id: Optional[ActorID] = payload.get("actor_id")
        timeout = payload.get("timeout", GlobalConfig.worker_lease_timeout_s)
        allow_spill = payload.get("allow_spill", True)
        if self._draining:
            # draining node: grant nothing new — redirect to a peer with
            # capacity, or make the caller retry elsewhere
            spill = (
                self._find_spill_node(resources, against="total", fresh=True)
                if allow_spill
                else None
            )
            return {"retry_at": spill} if spill is not None else None
        deadline = time.monotonic() + timeout
        with self._res_cv:
            # infeasible check against total
            for k, v in resources.items():
                if v > 0 and self.total_resources.get(k, 0) < v:
                    if allow_spill:
                        self._res_cv.release()
                        try:
                            spill = self._find_spill_node(
                                resources, against="total", fresh=True
                            )
                        finally:
                            self._res_cv.acquire()
                        if spill is not None:
                            return {"retry_at": spill}
                    raise ValueError(
                        f"resource request {resources} infeasible on node with "
                        f"{self.total_resources}"
                        + (" (and on every other alive node)" if allow_spill else "")
                    )
            need_tpu = any(
                v > 0
                and (
                    k == "TPU"
                    or ((p := self._parse_bundle_key(k)) is not None and p[0] == "TPU")
                )
                for k, v in resources.items()
            )
            renv = payload.get("runtime_env") or {}
            env_hash = runtime_env_key(renv)
            spill_checked = False
            demand_key = id(payload)
            self._demand[demand_key] = dict(resources)
            try:
                return self._lease_loop_locked(
                    resources, actor_id, deadline, allow_spill, need_tpu,
                    spill_checked, env_hash, renv,
                    count=max(1, int(payload.get("count", 1))),
                )
            finally:
                self._demand.pop(demand_key, None)

    def _lease_loop_locked(
        self, resources, actor_id, deadline, allow_spill, need_tpu,
        spill_checked, env_hash=(), runtime_env=None, count=1,
    ):
        """The parked-request wait loop; runs with _res_cv held (the caller
        registered this request in self._demand for heartbeat reporting).

        ``count > 1`` is the grant-ahead window: once the FIRST worker is
        granted, additional already-idle workers (no waiting, no spawning)
        are granted in the same reply under ``"extra"`` — a deep task
        queue pays one lease round-trip per window instead of per task."""
        my_spawned = False  # this request's one in-flight spawn credit
        while not self._stopped.is_set():
            if self._draining:
                # drain started while this request was parked: evict it to
                # a peer (the owner follows retry_at) or let it retry
                self._res_cv.release()
                try:
                    spill = (
                        self._find_spill_node(resources, against="total")
                        if allow_spill
                        else None
                    )
                finally:
                    self._res_cv.acquire()
                return {"retry_at": spill} if spill is not None else None
            effective = self._expand_pg_request_locked(resources)
            have_resources = effective is not None and all(
                self.available.get(k, 0) >= v for k, v in effective.items()
            )
            idle = (
                self._pop_idle_locked(need_tpu, env_hash)
                if have_resources
                else None
            )
            if have_resources and idle is not None:
                grant = self._grant_worker_locked(effective, idle, actor_id)
                extras = []
                # pipelined extras: only what is idle RIGHT NOW and only
                # for plain task leases (an actor binds to exactly one
                # worker) — never park or spawn for them
                while actor_id is None and len(extras) < count - 1:
                    eff = self._expand_pg_request_locked(resources)
                    if eff is None or not all(
                        self.available.get(k, 0) >= v for k, v in eff.items()
                    ):
                        break
                    w = self._pop_idle_locked(need_tpu, env_hash)
                    if w is None:
                        break
                    extras.append(self._grant_worker_locked(eff, w, None))
                if extras:
                    grant["extra"] = extras
                return grant
            if have_resources and idle is None:
                self._reap_dead_locked()
                spawning = sum(
                    1
                    for h in self._workers.values()
                    if not h.registered.is_set()
                    and h.tpu == need_tpu
                    and h.env_hash == env_hash
                ) + self._spawns_inflight.get((need_tpu, env_hash), 0)
                env_building = False
                if runtime_env and runtime_env.get("pip"):
                    # pip venv builds can take minutes: run them in the
                    # background and keep this request parked (its server-
                    # side deadline returns None and the client retries)
                    # instead of wedging the lease handler past the client
                    # RPC timeout
                    from ray_tpu._private.runtime_env_pip import (
                        ensure_pip_env_async,
                    )

                    env_building = (
                        ensure_pip_env_async(
                            self.session_dir,
                            list(runtime_env["pip"]),
                            runtime_env.get("pip_find_links"),
                        )
                        is None
                    )
                # each parked request holds one spawn credit, so concurrent
                # requests overlap worker startups (up to the cap) instead
                # of serializing on a single spawn-per-registration cycle;
                # the spawning==0 fallback re-arms a request whose spawned
                # worker was taken by a competing lease
                if (
                    not env_building
                    and (not my_spawned or spawning == 0)
                    and spawning < GlobalConfig.worker_spawn_parallelism
                    and len(self._workers) < GlobalConfig.max_workers_per_node
                ):
                    my_spawned = True
                    key = (need_tpu, env_hash)
                    self._spawns_inflight[key] = (
                        self._spawns_inflight.get(key, 0) + 1
                    )
                    self._res_cv.release()
                    try:
                        self._spawn_worker(
                            tpu=need_tpu,
                            runtime_env=runtime_env,
                        )
                    finally:
                        self._res_cv.acquire()
                        left = self._spawns_inflight.get(key, 1) - 1
                        if left > 0:
                            self._spawns_inflight[key] = left
                        else:
                            self._spawns_inflight.pop(key, None)
            if not have_resources and allow_spill and not spill_checked:
                # locally saturated: redirect to a node with free capacity
                spill_checked = True
                self._res_cv.release()
                try:
                    spill = self._find_spill_node(resources, against="available")
                finally:
                    self._res_cv.acquire()
                if spill is not None:
                    return {"retry_at": spill}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            self._res_cv.wait(min(remaining, 0.5))
        return None

    def _grant_worker_locked(self, effective, idle, actor_id):
        for k, v in effective.items():
            self.available[k] = self.available.get(k, 0) - v
        idle.idle = False
        idle.lease_resources = dict(effective)
        if actor_id is not None:
            idle.actor_ids.append(actor_id)
        internal_metrics.inc("ray_tpu_worker_leases_granted_total")
        return {"worker_id": idle.worker_id, "address": idle.address}

    def _reap_dead_locked(self):
        """Remove workers whose process exited before registering (e.g. the
        worker crashed at import); otherwise they'd count as 'spawning'
        forever and starve the lease loop."""
        dead = [
            wid
            for wid, h in self._workers.items()
            if not h.registered.is_set() and h.proc is not None and h.proc.poll() is not None
        ]
        for wid in dead:
            h = self._workers.pop(wid)
            logger.warning(
                "worker %s exited with code %s before registering (see %s/logs)",
                wid.hex()[:8],
                h.proc.returncode,
                self.session_dir,
            )

    def _return_lease_resources_locked(self, handle: WorkerHandle):
        """Return a worker's leased resources, dropping keys whose bundle was
        released in the meantime (the bundle release already re-credited the
        physical resources; re-adding here would recreate the dead names)."""
        for k, v in handle.lease_resources.items():
            if "_group_" in k and k not in self.total_resources:
                continue
            self.available[k] = self.available.get(k, 0) + v
        handle.lease_resources = {}

    def _pop_idle_locked(self, need_tpu: bool = False,
                         env_hash: tuple = ()) -> Optional[WorkerHandle]:
        for handle in self._workers.values():
            if (
                handle.idle
                and handle.registered.is_set()
                and not handle.actor_ids
                and handle.tpu == need_tpu
                and handle.env_hash == env_hash
            ):
                return handle
        return None

    def rpc_return_worker(self, conn: ServerConn, payload):
        worker_id = payload["worker_id"]
        kill = payload.get("kill", False)
        with self._res_cv:
            handle = self._workers.get(worker_id)
            if handle is None:
                return False
            self._return_lease_resources_locked(handle)
            # a worker returned to the pool hosts no actors (failed actor
            # creation must not leave the worker marked as an actor host)
            handle.actor_ids = []
            handle.idle = True
            handle.last_idle_at = time.monotonic()
            self._res_cv.notify_all()
        if kill and handle.proc is not None:
            handle.proc.terminate()
        return True

    # ------------------------------------------------------------------
    # cancellation + graceful drain
    # ------------------------------------------------------------------

    def rpc_cancel_task(self, conn: ServerConn, payload) -> Dict[str, Any]:
        """Forward a cancel to the worker executing the task (idempotent —
        an unknown worker is a no-op: the task already finished, or the
        worker died and the owner's failure path takes over)."""
        p = dict(payload or {})
        worker_id = p.pop("worker_id", None)
        if isinstance(worker_id, bytes):
            worker_id = WorkerID(worker_id)
        addr = None
        if worker_id is not None:
            with self._res_cv:
                handle = self._workers.get(worker_id)
                if handle is not None and handle.address and handle.address[1]:
                    addr = tuple(handle.address)
        if addr is None:
            return {"status": "unknown"}
        try:
            return self._peer_client(addr).call("cancel_task", p, timeout=5.0)
        except Exception:
            return {"status": "unreachable"}

    def rpc_drain(self, conn: ServerConn, payload) -> Dict[str, Any]:
        """Graceful drain (idempotent — a re-issued drain re-walks the same
        migration set and peer store_pull no-ops on objects it already
        holds): stop granting leases, wait for leased workers to finish
        until the deadline, then re-replicate every sealed primary object
        to peer nodes. Returns the migration map so the GCS can rewrite
        owner-side locations when this node deregisters — a drained node
        causes zero lineage reconstructions."""
        p = payload or {}
        deadline = time.monotonic() + float(p.get("deadline_s", 30.0))
        self._draining = True
        with self._res_cv:
            self._res_cv.notify_all()  # wake parked lease requests to redirect
        while time.monotonic() < deadline:
            with self._res_cv:
                # actor workers hold their lease for life — the GCS
                # orchestrator migrates restartable actors before this
                # call, so waiting on them would just burn the deadline
                busy = any(
                    h.lease_resources and not h.actor_ids
                    for h in self._workers.values()
                )
            if not busy or self._stopped.is_set():
                break
            time.sleep(0.05)
        migrated = self._migrate_objects(deadline)
        return {"node_id": self.node_id, "migrated": migrated}

    def _migrate_objects(
        self, deadline: float
    ) -> Dict[bytes, Tuple[str, int]]:
        """Re-replicate this node's sealed plasma objects onto alive,
        non-draining peers (pull-based: the peer's idempotent store_pull
        does the chunked transfer). Returns oid binary -> new address for
        every object that made it; objects left behind at the deadline
        fall back to lineage reconstruction."""
        try:
            nodes = self.gcs.call("get_nodes", timeout=5.0)
        except Exception:
            nodes = []
        peers = [
            tuple(n["address"])
            for n in nodes
            if n.get("alive")
            and n.get("node_id") != self.node_id
            and n.get("state") not in ("DEGRADED", "DRAINING")
        ]
        migrated: Dict[bytes, Tuple[str, int]] = {}
        if not peers:
            return migrated
        entries = self.store.list_objects()
        for i, e in enumerate(entries):
            if not e.get("sealed"):
                continue
            if time.monotonic() > deadline:
                logger.warning(
                    "drain deadline hit: %d/%d objects migrated",
                    len(migrated), len(entries),
                )
                break
            oid = ObjectID(bytes.fromhex(e["object_id"]))
            for attempt in range(len(peers)):
                peer = peers[(i + attempt) % len(peers)]
                try:
                    ok = self._peer_client(peer).call(
                        "store_pull",
                        (oid, self.server.address),
                        timeout=max(5.0, deadline - time.monotonic()),
                    )
                except Exception:
                    ok = False
                if ok:
                    migrated[oid.binary()] = peer
                    internal_metrics.inc(
                        "ray_tpu_drain_migrated_objects_total"
                    )
                    break
        return migrated

    def rpc_shutdown(self, conn: ServerConn, payload=None) -> bool:
        """Deregister and stop this raylet shortly after replying — the
        drain orchestrator's final step. Idempotent: repeat deliveries see
        the stop already scheduled."""
        if self._stopped.is_set() or self._drain_stop_scheduled:
            return True
        self._drain_stop_scheduled = True

        def _go():
            time.sleep(0.5)  # let the reply flush before the server dies
            self.stop(unregister=True)

        threading.Thread(target=_go, daemon=True).start()
        return True

    # ------------------------------------------------------------------
    # placement-group bundles: two-phase reservation (reference:
    # node_manager.proto:380-387 PrepareBundleResources/CommitBundleResources,
    # raylet/placement_group_resource_manager.cc)
    # ------------------------------------------------------------------

    @staticmethod
    def bundle_resource_names(pg_id, index: int, resources: Dict[str, float]):
        """Indexed + wildcard bundle resource names (reference format:
        ``{resource}_group_{index}_{pg_id}`` / ``{resource}_group_{pg_id}``)."""
        out: Dict[str, float] = {}
        hex_id = pg_id.hex()
        for k, v in resources.items():
            out[f"{k}_group_{index}_{hex_id}"] = v
            out[f"{k}_group_{hex_id}"] = out.get(f"{k}_group_{hex_id}", 0.0) + v
        # synthetic marker so zero-resource requests can still be pinned to
        # the bundle (reference: the bundle_group_* marker resource)
        out[f"bundle_group_{index}_{hex_id}"] = 1000.0
        out[f"bundle_group_{hex_id}"] = 1000.0
        return out

    @staticmethod
    def _parse_bundle_key(key: str):
        """``CPU_group_0_<hex>`` -> ("CPU", 0, hex); ``CPU_group_<hex>`` ->
        ("CPU", None, hex); plain keys -> None."""
        if "_group_" not in key:
            return None
        base, rest = key.split("_group_", 1)
        head, _, tail = rest.partition("_")
        if tail and head.isdigit():
            return base, int(head), tail
        return base, None, rest

    def _expand_pg_request_locked(
        self, resources: Dict[str, float]
    ) -> Optional[Dict[str, float]]:
        """Make a lease request consume BOTH the indexed and wildcard pools of
        its placement-group bundle, so the two names stay one physical
        reservation. Wildcard-only requests are pinned to a concrete committed
        bundle here. Returns None when no bundle currently fits."""
        if not any("_group_" in k for k in resources):
            return dict(resources)
        effective: Dict[str, float] = {}
        wildcard_by_pg: Dict[str, Dict[str, float]] = {}
        for k, v in resources.items():
            parsed = self._parse_bundle_key(k)
            if parsed is None:
                effective[k] = effective.get(k, 0.0) + v
                continue
            base, index, hex_id = parsed
            if index is not None:
                effective[k] = effective.get(k, 0.0) + v
                wk = f"{base}_group_{hex_id}"
                effective[wk] = effective.get(wk, 0.0) + v
            else:
                wildcard_by_pg.setdefault(hex_id, {})[base] = (
                    wildcard_by_pg.setdefault(hex_id, {}).get(base, 0.0) + v
                )
        for hex_id, bases in wildcard_by_pg.items():
            indices = sorted(
                i for (pg, i) in self._committed_bundles if pg.hex() == hex_id
            )
            chosen = None
            for i in indices:
                if all(
                    self.available.get(f"{b}_group_{i}_{hex_id}", 0.0)
                    >= v + effective.get(f"{b}_group_{i}_{hex_id}", 0.0)
                    for b, v in bases.items()
                ):
                    chosen = i
                    break
            if chosen is None:
                return None
            for b, v in bases.items():
                ik = f"{b}_group_{chosen}_{hex_id}"
                wk = f"{b}_group_{hex_id}"
                effective[ik] = effective.get(ik, 0.0) + v
                effective[wk] = effective.get(wk, 0.0) + v
        return effective

    def rpc_prepare_bundle(self, conn, payload):
        """Phase 1: reserve the bundle's resources (revertible)."""
        pg_id, index, resources = payload
        with self._res_cv:
            if (pg_id, index) in self._prepared_bundles or (
                pg_id,
                index,
            ) in self._committed_bundles:
                return True  # idempotent retry
            if not all(self.available.get(k, 0.0) >= v for k, v in resources.items()):
                return False
            for k, v in resources.items():
                self.available[k] = self.available.get(k, 0.0) - v
            self._prepared_bundles[(pg_id, index)] = dict(resources)
        return True

    def rpc_commit_bundle(self, conn, payload):
        """Phase 2: expose the reservation as bundle-scoped resources that
        only tasks/actors scheduled into the group can consume."""
        pg_id, index = payload
        with self._res_cv:
            resources = self._prepared_bundles.pop((pg_id, index), None)
            if resources is None:
                return (pg_id, index) in self._committed_bundles
            names = self.bundle_resource_names(pg_id, index, resources)
            for k, v in names.items():
                self.total_resources[k] = self.total_resources.get(k, 0.0) + v
                self.available[k] = self.available.get(k, 0.0) + v
            self._committed_bundles[(pg_id, index)] = dict(resources)
            self._res_cv.notify_all()
        self._heartbeat_now()
        return True

    def rpc_return_bundle(self, conn, payload):
        """Release a prepared or committed bundle back to the general pool.

        Workers still leased against the bundle are killed first (the
        reference also kills tasks when their group is removed) so the
        physical resources really are free when re-credited."""
        pg_id, index = payload
        victims: List[WorkerHandle] = []
        with self._res_cv:
            ok, heartbeat = self._return_bundle_locked(pg_id, index, victims)
        for handle in victims:
            if handle.proc is not None and handle.proc.poll() is None:
                handle.proc.terminate()
        if heartbeat:
            self._heartbeat_now()
        return ok

    def _return_bundle_locked(self, pg_id, index, victims) -> Tuple[bool, bool]:
        """Release one prepared/committed bundle (``_res_cv`` held).
        Appends still-leased workers to ``victims`` (killed by the caller,
        outside the lock) and returns (ok, needs_heartbeat)."""
        resources = self._prepared_bundles.pop((pg_id, index), None)
        if resources is not None:
            for k, v in resources.items():
                self.available[k] = self.available.get(k, 0.0) + v
            self._res_cv.notify_all()
            return True, False
        resources = self._committed_bundles.pop((pg_id, index), None)
        if resources is None:
            return False, False
        suffix = f"_group_{index}_{pg_id.hex()}"
        for handle in self._workers.values():
            if any(k.endswith(suffix) for k in handle.lease_resources):
                handle.lease_resources = {}  # disconnect must not re-credit
                victims.append(handle)
        names = self.bundle_resource_names(pg_id, index, resources)
        for k, v in names.items():
            parsed = self._parse_bundle_key(k)
            if parsed is not None and parsed[1] is not None:
                # indexed pool: dies with the bundle regardless of leases
                self.total_resources.pop(k, None)
                self.available.pop(k, None)
            else:
                # wildcard pool: other bundles of the group may remain
                self.total_resources[k] = self.total_resources.get(k, 0.0) - v
                if self.total_resources.get(k, 0.0) <= 1e-9:
                    self.total_resources.pop(k, None)
                    self.available.pop(k, None)
                else:
                    self.available[k] = max(
                        0.0, self.available.get(k, 0.0) - v
                    )
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0.0) + v
        self._res_cv.notify_all()
        return True, True

    # Batched bundle RPCs: the GCS groups a placement group's bundles by
    # target raylet and issues ONE prepare/commit/return call per raylet
    # instead of one per bundle (the per-bundle round-trips dominated
    # pg_create_remove at 0.80x baseline; reference batches the same way —
    # node_manager.proto PrepareBundleResources takes repeated bundle specs).

    def rpc_prepare_bundles(self, conn, payload):
        """Phase 1 for several bundles at once, all-or-nothing: either every
        bundle's resources are reserved on this raylet or none are."""
        pg_id, items = payload  # [(index, resources), ...]
        with self._res_cv:
            todo = [
                (i, r)
                for i, r in items
                if (pg_id, i) not in self._prepared_bundles
                and (pg_id, i) not in self._committed_bundles
            ]
            need: Dict[str, float] = {}
            for _, r in todo:
                for k, v in r.items():
                    need[k] = need.get(k, 0.0) + v
            if not all(self.available.get(k, 0.0) >= v for k, v in need.items()):
                return False
            for k, v in need.items():
                self.available[k] = self.available.get(k, 0.0) - v
            for i, r in todo:
                self._prepared_bundles[(pg_id, i)] = dict(r)
        return True

    def rpc_commit_bundles(self, conn, payload):
        """Phase 2 for several bundles; one resource heartbeat at the end
        instead of one per bundle."""
        pg_id, indices = payload
        ok = True
        with self._res_cv:
            for index in indices:
                resources = self._prepared_bundles.pop((pg_id, index), None)
                if resources is None:
                    ok = ok and (pg_id, index) in self._committed_bundles
                    continue
                names = self.bundle_resource_names(pg_id, index, resources)
                for k, v in names.items():
                    self.total_resources[k] = self.total_resources.get(k, 0.0) + v
                    self.available[k] = self.available.get(k, 0.0) + v
                self._committed_bundles[(pg_id, index)] = dict(resources)
            self._res_cv.notify_all()
        self._heartbeat_now()
        return ok

    def rpc_return_bundles(self, conn, payload):
        """Release several bundles under ONE lock acquisition: victims are
        terminated in a single pass and one resource heartbeat covers the
        whole batch (per-bundle lock+heartbeat dominated pg remove)."""
        pg_id, indices = payload
        ok = True
        heartbeat = False
        victims: List[WorkerHandle] = []
        with self._res_cv:
            for index in indices:
                one_ok, one_hb = self._return_bundle_locked(pg_id, index, victims)
                ok = ok and one_ok
                heartbeat = heartbeat or one_hb
        for handle in victims:
            if handle.proc is not None and handle.proc.poll() is None:
                handle.proc.terminate()
        if heartbeat:
            self._heartbeat_now()
        return ok

    def _report_store_gauges(self):
        """Mirror plasma stats into gauges and surface spill bursts as
        cluster events (one event per burst, diffed against a watermark)."""
        try:
            stats = self.store.stats()
        except Exception:
            return
        internal_metrics.set_gauge(
            "ray_tpu_object_store_objects", float(stats.get("num_objects", 0))
        )
        internal_metrics.set_gauge(
            "ray_tpu_object_store_allocated_bytes",
            float(stats.get("allocated_bytes", 0)),
        )
        spilled = int(stats.get("spilled_bytes_total", 0))
        if spilled > self._spill_event_bytes:
            delta, self._spill_event_bytes = (
                spilled - self._spill_event_bytes,
                spilled,
            )
            try:
                self.gcs.call(
                    "report_cluster_event",
                    {
                        "type": "OBJECT_SPILL",
                        "severity": "WARNING",
                        "node_id": self.node_id.hex(),
                        "message": f"spilled {delta} bytes to disk "
                        f"({spilled} total on this node)",
                        "spilled_bytes": delta,
                    },
                    timeout=5.0,
                )
            except Exception:
                pass  # event log is best-effort; never block heartbeats

    def _heartbeat_now(self) -> bool:
        """One heartbeat attempt. Returns False when the GCS was
        unreachable (the loop applies jittered backoff before retrying)."""
        try:
            with self._res_cv:
                available = dict(self.available)
                total = dict(self.total_resources)
                demand = [dict(d) for d in self._demand.values()]
                num_workers = len(self._workers)
                num_idle = sum(1 for h in self._workers.values() if h.idle)
            internal_metrics.set_gauge(
                "ray_tpu_scheduler_queue_depth", float(len(demand))
            )
            internal_metrics.set_gauge(
                "ray_tpu_worker_pool_size", float(num_workers)
            )
            internal_metrics.set_gauge("ray_tpu_workers_idle", float(num_idle))
            self._report_store_gauges()
            ok = self.gcs.call(
                "heartbeat",
                (self.node_id, available, total, demand, self._probe_snapshot),
                timeout=5.0,
            )
            if ok is False and not self._stopped.is_set():
                # the GCS doesn't know us: it restarted (persistence reload
                # drops node liveness on purpose) — re-register, replaying
                # our live resource view (reference: NotifyGCSRestart,
                # node_manager.proto:358). The transport may have healed
                # silently (idempotent-retry reconnect), so subscriptions
                # need re-establishing too.
                self._register_with_gcs()
                self._resubscribe_gcs()
            return True
        except Exception:
            if self._stopped.is_set():
                return True
            # connection to the GCS lost: reconnect and re-register
            try:
                new_client = RpcClient(
                    self.gcs_address,
                    on_notify=self._on_gcs_notify,
                    connect_timeout=2.0,
                    prefer_local=True,
                )
                new_client.chaos_identity = self._chaos_identity
                old, self.gcs = self.gcs, new_client
                try:
                    old.close()
                except Exception:
                    pass
                self._register_with_gcs()
                self._resubscribe_gcs()
                logger.info(
                    "node %s reconnected to restarted GCS", self.node_id.hex()[:8]
                )
                return True
            except Exception:
                return False  # GCS still down; the loop backs off

    def _resubscribe_gcs(self):
        """Re-establish pubsub + chaos state after a GCS reconnect or
        restart (subscriptions are per-connection on the GCS side)."""
        try:
            self.gcs.call("subscribe", "resource_view", timeout=5.0)
        except Exception:
            pass
        try:
            self.gcs.call("subscribe", "chaos", timeout=5.0)
            blob = self.gcs.call("kv_get", ("chaos", "schedule"), timeout=5.0)
            if blob:
                self._arm_chaos(json.loads(blob))
            else:
                self._chaos_armed = None
                fault_injection.disarm()
        except Exception:
            pass

    def _register_with_gcs(self):
        with self._res_cv:
            available = dict(self.available)
            total = dict(self.total_resources)
            demand = [dict(d) for d in self._demand.values()]
        self.gcs.call(
            "register_node",
            (self.node_id, self.server.address, total, self.labels),
            timeout=5.0,
        )
        self.gcs.call(
            "heartbeat", (self.node_id, available, total, demand), timeout=5.0
        )

    def rpc_get_node_info(self, conn, payload=None):
        with self._res_cv:
            return {
                "node_id": self.node_id,
                "resources": self.total_resources,
                "available": self.available,
                "store_path": self.store.path,
                "store_capacity": self.store.capacity,
                "num_workers": len(self._workers),
                "labels": self.labels,
            }

    # ------------------------------------------------------------------
    # store metadata service (data plane is direct shm)
    # ------------------------------------------------------------------

    def rpc_store_create(self, conn, payload):
        object_id, size = payload
        return self.store.create(object_id, size)

    def rpc_store_put(self, conn, payload):
        object_id, data = payload
        self.store.put_bytes(object_id, data)
        return True

    def rpc_store_seal(self, conn, payload):
        self.store.seal(payload)
        return True

    def rpc_store_get(self, conn, payload):
        object_ids, timeout = payload
        return self.store.get_locations(object_ids, timeout)

    def rpc_store_contains(self, conn, payload):
        return self.store.contains(payload)

    def rpc_store_release(self, conn, payload):
        self.store.release(payload)
        return True

    def rpc_store_delete(self, conn, payload):
        self.store.delete(payload)
        return True

    def rpc_store_delete_batch(self, conn, payload):
        for oid in payload:
            self.store.delete(oid)
        return True

    def rpc_store_abort(self, conn, payload):
        self.store.abort(payload)
        return True

    def rpc_store_stats(self, conn, payload=None):
        return self.store.stats()

    def rpc_store_list(self, conn, payload=None):
        return self.store.list_objects()

    # ------------------------------------------------------------------
    # node-to-node object transfer (pull-based, chunked; reference:
    # src/ray/object_manager/pull_manager.cc / push_manager.cc)
    # ------------------------------------------------------------------

    _PULL_CHUNK = 8 * 1024 * 1024

    def _peer_client(self, addr: Tuple[str, int]) -> RpcClient:
        addr = tuple(addr)
        with self._peers_lock:
            client = self._peers.get(addr)
            if client is not None and not client.closed:
                return client
            client = RpcClient(addr, prefer_local=True)
            client.chaos_identity = self._chaos_identity
            self._peers[addr] = client
            return client

    def rpc_store_fetch(self, conn, payload):
        """Serve a chunk of a sealed local object to a peer raylet.

        Returned as a PickleBuffer view straight into the shm arena: wire v3
        ships it out-of-band (no serialize copy here, no deserialize copy on
        the puller). The puller holds a remote pin for the duration of the
        pull, so the viewed range cannot be evicted mid-send."""
        import pickle as _pickle

        object_id, offset, length = payload
        view = self.store.read_view(object_id, offset, length)
        if view is None:
            return None
        return _pickle.PickleBuffer(view)

    def _pull_chunks_pipelined(
        self, client: RpcClient, object_id, view, size: int, window: int = 4
    ) -> bool:
        """Keep ``window`` chunk fetches in flight so the wire never idles
        while this thread memcpys the previous chunk into the arena
        (reference: object_manager.h:63 object_chunk_size + the push
        manager's in-flight chunk pipeline, push_manager.cc). The serial
        request-per-chunk loop this replaces left a full RTT gap between
        chunks — the put/weights path sat at ~0.26x reference bandwidth."""
        from ray_tpu._private import rpc as rpc_mod

        done: Dict[int, Any] = {}
        req_len: Dict[int, int] = {}  # offset -> bytes requested at it
        cv = threading.Condition()

        def make_cb(pos: int):
            def cb(kind, payload):
                with cv:
                    done[pos] = (kind, payload)
                    cv.notify_all()

            return cb

        def send(offset: int, n: int):
            req_len[offset] = n
            client.call_async("store_fetch", (object_id, offset, n), make_cb(offset))

        next_send = 0
        next_write = 0
        while next_write < size:
            while (
                next_send < size
                and next_send - next_write < window * self._PULL_CHUNK
            ):
                n = min(self._PULL_CHUNK, size - next_send)
                send(next_send, n)
                next_send += n
            with cv:
                deadline = time.monotonic() + 60.0
                while next_write not in done:
                    if not cv.wait(timeout=max(0.0, deadline - time.monotonic())):
                        raise TimeoutError(
                            f"chunk fetch at {next_write} timed out"
                        )
                kind, payload = done.pop(next_write)
            if kind != rpc_mod.RESPONSE or payload is None or len(payload) == 0:
                if isinstance(payload, BaseException):
                    raise payload
                return False
            view[next_write : next_write + len(payload)] = payload
            requested = req_len.pop(next_write)
            next_write += len(payload)
            if len(payload) < requested:
                # short read (metadata/size disagreement): re-request ONLY
                # the remainder of THIS chunk — its key is exactly the new
                # next_write, so the ordered wait picks it up next; ranges
                # already in flight at higher offsets are untouched
                send(next_write, requested - len(payload))
        return True

    def rpc_store_pull(self, conn, payload):
        """Fetch an object from a peer raylet into the local store.

        Idempotent: returns True once the object is sealed locally. Concurrent
        pulls of the same object serialize on the store's create/seal states.
        """
        object_id, remote_addr = payload[0], tuple(payload[1])
        if self.store.contains(object_id):
            return True
        if remote_addr == self.server.address:
            return False
        client = self._peer_client(remote_addr)
        # pin remotely while we copy (store_get pins; released below)
        locs = client.call("store_get", ([object_id], 30.0), timeout=60.0)
        if locs is None:
            return False
        try:
            _, size = locs[object_id]
            try:
                offset = self.store.create(object_id, size)
            except ValueError:
                # another pull (or a local producer) is creating it: wait for seal
                return (
                    self.store.get_locations([object_id], timeout=60.0, pin=False)
                    is not None
                )
            if size > 8 * 1024 * 1024:
                object_store._populate_range(self.store._map, offset, size)
            view = self.store.view(offset, size)
            try:
                if not self._pull_chunks_pipelined(client, object_id, view, size):
                    self.store.abort(object_id)
                    return False
            except Exception:
                self.store.abort(object_id)
                raise
            self.store.seal(object_id)
            return True
        finally:
            try:
                client.call("store_release", object_id, timeout=10.0)
            except Exception:
                pass

    # ------------------------------------------------------------------

    def _heartbeat_loop(self):
        period = GlobalConfig.health_check_period_s
        failures = 0
        while True:
            if failures == 0:
                delay = period / 2
            else:
                # capped exponential backoff with FULL jitter: after a GCS
                # restart every raylet retries at a decorrelated moment
                # instead of the whole fleet stampeding re-registration on
                # a shared period (reference: gcs_rpc_client.h retry +
                # the classic exponential-backoff-and-jitter result)
                cap = GlobalConfig.heartbeat_reconnect_backoff_cap_s
                delay = max(
                    0.05,
                    random.uniform(0.0, min(cap, (period / 2) * (2 ** failures))),
                )
            if self._stopped.wait(delay):
                return
            failures = 0 if self._heartbeat_now() else failures + 1
            self._reap_idle_workers()

    def _reap_idle_workers(self):
        """Kill pooled workers idle past worker_idle_timeout_s (reference:
        worker_pool.h idle worker eviction), keeping the prestart floor."""
        timeout = GlobalConfig.worker_idle_timeout_s
        if timeout <= 0:
            return
        now = time.monotonic()
        to_kill: List[WorkerHandle] = []
        with self._res_cv:
            idle = [
                h
                for h in self._workers.values()
                if h.idle
                and h.proc is not None  # never reap drivers/external workers
                and h.registered.is_set()
                and not h.actor_ids
                and now - h.last_idle_at > timeout
            ]
            floor = GlobalConfig.worker_pool_prestart
            total_idle = sum(
                1
                for h in self._workers.values()
                if h.idle and h.registered.is_set() and not h.actor_ids
            )
            for h in idle:
                if total_idle <= floor:
                    break
                self._workers.pop(h.worker_id, None)
                total_idle -= 1
                to_kill.append(h)
        for h in to_kill:
            logger.info(
                "reaping worker %s idle for >%gs", h.worker_id.hex()[:8], timeout
            )
            if h.proc.poll() is None:
                h.proc.terminate()

    # -- memory monitor ------------------------------------------------

    @staticmethod
    def _memory_usage_fraction() -> float:
        """Node memory usage in [0,1] from /proc/meminfo (MemAvailable)."""
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, v = line.split(":", 1)
                    info[k] = int(v.split()[0])
            total = info["MemTotal"]
            avail = info.get("MemAvailable", info.get("MemFree", total))
            return 1.0 - avail / total
        except (OSError, KeyError, ValueError):
            return 0.0

    def _memory_monitor_loop(self):
        period = GlobalConfig.memory_monitor_period_s
        threshold = GlobalConfig.memory_usage_threshold
        while not self._stopped.wait(period):
            usage = self._memory_usage_fraction()
            if usage <= threshold:
                continue
            self._kill_for_memory(usage)

    def _kill_for_memory(self, usage: float) -> bool:
        """Pick a victim: the most recently leased busy worker that hosts
        no actors (retriable work first — its owner re-submits; actors
        would need a restart). Returns True if something was killed."""
        with self._res_cv:
            busy = [
                h
                for h in self._workers.values()
                if not h.idle
                and h.proc is not None
                and h.registered.is_set()
                and not h.actor_ids
            ]
            victim = max(busy, key=lambda h: h.last_idle_at, default=None)
        if victim is None:
            return False
        logger.warning(
            "memory pressure (%.0f%% > %.0f%%): killing worker %s to "
            "reclaim memory (its task will error and may retry)",
            usage * 100,
            GlobalConfig.memory_usage_threshold * 100,
            victim.worker_id.hex()[:8],
        )
        # hard kill: the worker is presumed wedged in allocation; the
        # disconnect path reports the death and frees its lease
        victim.proc.kill()
        try:
            self.gcs.call(
                "report_cluster_event",
                {
                    "type": "WORKER_OOM_KILLED",
                    "severity": "WARNING",
                    "node_id": self.node_id.hex(),
                    "worker_id": victim.worker_id.hex(),
                    "message": f"memory pressure at {usage * 100:.0f}%: "
                    f"killed worker {victim.worker_id.hex()[:8]}",
                },
                timeout=5.0,
            )
        except Exception:
            pass
        return True

    # -- log monitor ---------------------------------------------------

    def _log_monitor_loop(self):
        log_dir = os.path.join(self.session_dir, "logs", self.node_id.hex()[:12])
        while not self._stopped.wait(0.5):
            try:
                names = [
                    n for n in os.listdir(log_dir)
                    if n.startswith("worker-") and n.endswith(".log")
                ]
            except OSError:
                continue
            for name in names:
                path = os.path.join(log_dir, name)
                try:
                    size = os.path.getsize(path)
                    offset = self._log_offsets.get(name, 0)
                    if size <= offset:
                        continue
                    with open(path, "rb") as f:
                        f.seek(offset)
                        chunk = f.read(min(size - offset, 512 * 1024))
                    # only ship complete lines; partial tail re-reads next tick
                    cut = chunk.rfind(b"\n")
                    if cut < 0:
                        continue
                    raw_lines = chunk[:cut].split(b"\n")
                    # cap the batch; the offset advances only past what is
                    # actually published, so the remainder ships next tick
                    # instead of being skipped
                    batch = raw_lines[:200]
                    published_bytes = sum(len(l) + 1 for l in batch)
                    lines = [l.decode(errors="replace") for l in batch]
                    # task boundary markers are machine-readable metadata for
                    # get_log(task_id=...); keep them out of the driver's
                    # stdout mirror (the offset still advances past them)
                    lines = [l for l in lines if not l.startswith("::task_")]
                except OSError:
                    continue
                if not lines:
                    self._log_offsets[name] = offset + published_bytes
                    continue
                try:
                    self.gcs.call(
                        "publish",
                        (
                            "logs",
                            {
                                "worker": name[len("worker-"):-len(".log")],
                                "node": self.labels.get("node_name", ""),
                                "lines": lines,
                            },
                        ),
                        timeout=5.0,
                    )
                    # advance only after a successful publish so a GCS
                    # hiccup re-ships rather than drops the lines
                    self._log_offsets[name] = offset + published_bytes
                except Exception:
                    pass

    # -- log plane (reference: ray logs / GetLogService: raylet serves its
    # own session log dir so any node's output is reachable from anywhere) --

    def _log_root(self) -> str:
        return os.path.join(self.session_dir, "logs", self.node_id.hex()[:12])

    def _resolve_log_path(self, filename: str) -> Optional[str]:
        """Map a client-supplied filename into this node's log dir, rejecting
        path traversal (.., absolute paths, symlink escapes)."""
        root = os.path.realpath(self._log_root())
        full = os.path.realpath(os.path.join(root, filename))
        if full != root and not full.startswith(root + os.sep):
            return None
        return full

    def rpc_list_logs(self, conn, payload=None):
        """Enumerate this node's log files: name, size, mtime."""
        root = self._log_root()
        files: List[Dict[str, Any]] = []
        try:
            names = sorted(os.listdir(root))
        except OSError:
            names = []
        for name in names:
            try:
                st = os.stat(os.path.join(root, name))
            except OSError:
                continue
            if not os.path.isfile(os.path.join(root, name)):
                continue
            files.append(
                {"filename": name, "size": st.st_size, "mtime": st.st_mtime}
            )
        return {"node_id": self.node_id.hex(), "files": files}

    @staticmethod
    def _tail_offset(path: str, size: int, n: int) -> int:
        """Byte offset where the last ``n`` lines of ``path`` begin."""
        if n <= 0:
            return size
        block = 64 * 1024
        data = b""
        end = size
        while end > 0 and data.count(b"\n") <= n:
            start = max(0, end - block)
            with open(path, "rb") as f:
                f.seek(start)
                data = f.read(end - start) + data
            end = start
        lines = data.splitlines(keepends=True)
        if not lines:
            return end
        return size - sum(len(l) for l in lines[-n:])

    def rpc_read_log(self, conn, payload):
        """Byte-ranged read of one log file; ``follow=True`` long-polls until
        bytes appear past ``offset`` (or the poll window expires). Dispatch
        runs on the dynamic pool, so a parked follow call cannot starve
        other RPCs."""
        p = payload or {}
        filename = p.get("filename") or ""
        full = self._resolve_log_path(filename)
        if full is None:
            return {"error": f"invalid log filename {filename!r}"}
        offset = p.get("offset")
        max_bytes = min(int(p.get("max_bytes", 1 << 20)), 8 << 20)
        tail_lines = p.get("tail_lines")
        follow = bool(p.get("follow"))
        deadline = time.monotonic() + min(float(p.get("timeout_s", 10.0)), 30.0)
        while True:
            try:
                size = os.path.getsize(full)
            except OSError:
                if follow and time.monotonic() < deadline:
                    # file not created yet (job log registered before first
                    # write): park until it appears or the window expires
                    if self._stopped.wait(0.1):
                        return {"error": f"no such log {filename!r}"}
                    continue
                return {"error": f"no such log {filename!r}"}
            if offset is None:
                offset = (
                    self._tail_offset(full, size, int(tail_lines))
                    if tail_lines is not None and int(tail_lines) >= 0
                    else 0
                )
            if size > offset or not follow:
                break
            if time.monotonic() >= deadline or self._stopped.wait(0.1):
                break
        data = b""
        if size > offset:
            try:
                with open(full, "rb") as f:
                    f.seek(offset)
                    data = f.read(min(size - offset, max_bytes))
            except OSError as e:
                return {"error": f"read failed: {e!r}"}
        return {
            "node_id": self.node_id.hex(),
            "filename": filename,
            "offset": offset,
            "next_offset": offset + len(data),
            "size": size,
            "data": data,
            "eof": offset + len(data) >= size,
        }

    def rpc_dump_stacks(self, conn, payload=None):
        """Fan the per-worker ``profile`` RPC (one short sampling pass ==
        a stack snapshot) across every registered worker on this node."""
        p = payload or {}
        duration = min(float(p.get("duration_s", 0.05)), 2.0)
        with self._res_cv:
            targets = [
                (h.worker_id, tuple(h.address))
                for h in self._workers.values()
                # drivers register with a ("", 0) placeholder address and run
                # no task server — nothing to profile there
                if h.registered.is_set() and h.address and h.address[1]
            ]
        workers: Dict[str, Any] = {}

        def _one(wid: WorkerID, addr: Tuple[str, int]):
            try:
                prof = self._peer_client(addr).call(
                    "profile",
                    {"duration_s": duration, "interval_s": duration},
                    timeout=duration + 10.0,
                )
                workers[wid.hex()] = {
                    "pid": prof.get("pid"),
                    "folded": prof.get("folded", {}),
                }
            except Exception as e:
                workers[wid.hex()] = {"error": repr(e)}

        threads = [
            threading.Thread(target=_one, args=t, daemon=True) for t in targets
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(duration + 15.0)
        return {"node_id": self.node_id.hex(), "workers": workers}

    def rpc_trace_spans(self, conn, payload=None):
        """Trace-harvest node leg: this raylet's own span ring plus every
        registered worker's (same per-worker fan-out as rpc_dump_stacks).
        Returns ``{"node_id", "processes": {key: snapshot|{"error"}}}``."""
        nid = self.node_id.hex()
        with self._res_cv:
            targets = [
                (h.worker_id, tuple(h.address))
                for h in self._workers.values()
                if h.registered.is_set() and h.address and h.address[1]
            ]
        processes: Dict[str, Any] = {
            f"raylet:{nid[:8]}": _trace.snapshot()
        }

        def _one(wid: WorkerID, addr: Tuple[str, int]):
            key = f"worker:{wid.hex()[:8]}@{nid[:8]}"
            try:
                processes[key] = self._peer_client(addr).call(
                    "trace_spans", {}, timeout=10.0
                )
            except Exception as e:
                processes[key] = {"error": repr(e)}

        threads = [
            threading.Thread(target=_one, args=t, daemon=True) for t in targets
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15.0)
        return {"node_id": nid, "processes": processes}

    def rpc_perf_profile(self, conn, payload=None):
        """Cluster sampling profiler, node leg: sample this raylet process
        AND fan the per-worker ``profile`` RPC across registered workers,
        all concurrently for the same window (``ray_tpu.perf.profile``
        merges the per-node results; same fan-out as rpc_dump_stacks)."""
        from ray_tpu._private import perf as _perf_mod

        p = payload or {}
        duration = min(float(p.get("duration_s", 2.0)), 30.0)
        hz = float(p.get("hz", 100.0))
        nid = self.node_id.hex()
        with self._res_cv:
            targets = [
                (h.worker_id, tuple(h.address))
                for h in self._workers.values()
                if h.registered.is_set() and h.address and h.address[1]
            ]
        processes: Dict[str, Any] = {}

        def _self():
            processes[f"raylet:{nid[:8]}"] = _perf_mod.sample_self(
                duration, hz, role="raylet"
            )

        def _one(wid: WorkerID, addr: Tuple[str, int]):
            key = f"worker:{wid.hex()[:8]}@{nid[:8]}"
            try:
                processes[key] = self._peer_client(addr).call(
                    "profile",
                    {"duration_s": duration, "interval_s": 1.0 / max(hz, 1.0)},
                    timeout=duration + 10.0,
                )
            except Exception as e:
                processes[key] = {"error": repr(e)}

        threads = [threading.Thread(target=_self, daemon=True)] + [
            threading.Thread(target=_one, args=t, daemon=True) for t in targets
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(duration + 15.0)
        return {"node_id": nid, "processes": processes}

    def stop(self, unregister: bool = True):
        object_store.unregister_local_store(self.server.address)
        if unregister:
            try:
                self.gcs.call("unregister_node", self.node_id, timeout=5.0)
            except Exception:
                pass
        self._stopped.set()
        with self._peers_lock:
            for c in self._peers.values():
                c.close()
        with self._res_cv:
            workers = list(self._workers.values())
            self._res_cv.notify_all()
        for handle in workers:
            if handle.proc is not None and handle.proc.poll() is None:
                handle.proc.terminate()
        for handle in workers:
            if handle.proc is not None:
                try:
                    handle.proc.wait(timeout=3)
                except subprocess.TimeoutExpired:
                    handle.proc.kill()
        self.server.stop()
        self.gcs.close()
        self.store.close()
