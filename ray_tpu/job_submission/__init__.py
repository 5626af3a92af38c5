"""Job submission: run driver scripts on the cluster with tracked status.

Reference: python/ray/job_submission/ SDK + dashboard/modules/job/
job_manager.py:508 (JobManager, submit_job:823) — each job runs under a
supervisor actor on the cluster which spawns the entrypoint as a
subprocess, streams its output into the GCS KV, and records status
transitions (PENDING → RUNNING → SUCCEEDED/FAILED/STOPPED).

The entrypoint process receives ``RAYTPU_ADDRESS`` so its
``ray_tpu.init(address=...)`` joins the same cluster.
"""

from __future__ import annotations

import os
import subprocess
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu

_NS = "job_submission"


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    STOPPED = "STOPPED"

    TERMINAL = (SUCCEEDED, FAILED, STOPPED)


@ray_tpu.remote
class JobSupervisor:
    """One per job; lives on the cluster (reference: job_manager.py's
    JobSupervisor actor). Runs the entrypoint, pumps logs to GCS KV.

    ``run`` blocks for the job's whole lifetime on the actor's single
    ordered thread, so stop/ping are control methods — they run on the
    dispatch pool and can terminate a wedged job."""

    __ray_control_methods__ = ("stop", "ping")

    def __init__(self, submission_id: str, entrypoint: str,
                 env_vars: Dict[str, str], gcs_address: str):
        self.submission_id = submission_id
        self.entrypoint = entrypoint
        self.env_vars = env_vars
        self.gcs_address = gcs_address
        self.proc: Optional[subprocess.Popen] = None
        self._stop = threading.Event()

    def _kv_put(self, key: str, value: bytes):
        import ray_tpu._private.worker as worker_mod

        worker_mod.global_worker.core.gcs.call(
            "kv_put", (_NS, f"{self.submission_id}:{key}", value, True)
        )

    def _set_status(self, status: str, message: str = ""):
        import pickle

        self._kv_put(
            "status",
            pickle.dumps({"status": status, "message": message, "ts": time.time()}),
        )

    def _open_job_log(self):
        """Create ``job-<submission_id>.log`` in this node's session log dir
        and register its location in KV so clients stream it through the
        cluster log plane. Returns the open file (or None when this process
        has no session dir — then logs fall back to KV buffering)."""
        import pickle

        session_dir = os.environ.get("RAYTPU_SESSION_DIR")
        node_hex = os.environ.get("RAYTPU_NODE_ID", "")
        if not session_dir or not node_hex:
            return None
        log_dir = os.path.join(session_dir, "logs", node_hex[:12])
        filename = f"job-{self.submission_id}.log"
        try:
            os.makedirs(log_dir, exist_ok=True)
            f = open(os.path.join(log_dir, filename), "ab")
        except OSError:
            return None
        self._kv_put(
            "logmeta",
            pickle.dumps({"node_id": node_hex, "filename": filename}),
        )
        return f

    def run(self) -> str:
        """Blocking: returns the terminal status."""
        env = dict(os.environ)
        # this supervisor is a CPU worker, pinned to JAX_PLATFORMS=cpu by
        # its raylet; the job's driver is not, unless its env_vars say so
        env.pop("JAX_PLATFORMS", None)
        env.update(self.env_vars)
        env["RAYTPU_ADDRESS"] = self.gcs_address
        self._set_status(JobStatus.RUNNING)
        log_file = self._open_job_log()
        try:
            self.proc = subprocess.Popen(
                self.entrypoint,
                shell=True,
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=False,
                start_new_session=True,  # own process group: stop() kills
                # the whole tree, not just the `sh -c` wrapper
            )
        except OSError as e:
            if log_file is not None:
                log_file.close()
            self._set_status(JobStatus.FAILED, f"spawn failed: {e}")
            return JobStatus.FAILED
        chunks: List[bytes] = []
        try:
            for line in self.proc.stdout:
                if log_file is not None:
                    # the log plane serves (and follows) this file; flush per
                    # line so a follow stream sees output promptly
                    log_file.write(line)
                    log_file.flush()
                else:
                    chunks.append(line)
                    if len(chunks) % 20 == 0:
                        self._kv_put("logs", b"".join(chunks))
        finally:
            if log_file is not None:
                log_file.close()
        self.proc.wait()
        if log_file is None:
            self._kv_put("logs", b"".join(chunks))
        if self._stop.is_set():
            status = JobStatus.STOPPED
        elif self.proc.returncode == 0:
            status = JobStatus.SUCCEEDED
        else:
            status = JobStatus.FAILED
        self._set_status(status, f"exit code {self.proc.returncode}")
        return status

    def stop(self) -> bool:
        self._stop.set()
        if self.proc is not None and self.proc.poll() is None:
            import signal

            try:
                # the entrypoint runs under `sh -c`: signal the whole
                # process group or only the shell dies and the real job
                # keeps running
                os.killpg(os.getpgid(self.proc.pid), signal.SIGTERM)
            except (OSError, ProcessLookupError):
                self.proc.terminate()
            return True
        return False

    def ping(self) -> bool:
        return True


class JobSubmissionClient:
    """SDK entry point (reference: python/ray/job_submission/
    JobSubmissionClient). ``address`` is the GCS host:port; when None the
    already-connected driver is used."""

    def __init__(self, address: Optional[str] = None):
        if address is not None and not ray_tpu.is_initialized():
            ray_tpu.init(address=address, log_level="WARNING")
        if not ray_tpu.is_initialized():
            raise RuntimeError("not connected: pass address='host:port'")
        import ray_tpu._private.worker as worker_mod

        self._worker = worker_mod.global_worker
        host, port = self._worker.core.gcs.address
        self._gcs_address = f"{host}:{port}"
        self._supervisors: Dict[str, Any] = {}
        self._runs: Dict[str, Any] = {}

    def _kv_get(self, submission_id: str, key: str) -> Optional[bytes]:
        return self._worker.core.gcs.call(
            "kv_get", (_NS, f"{submission_id}:{key}")
        )

    def submit_job(
        self,
        *,
        entrypoint: str,
        submission_id: Optional[str] = None,
        runtime_env: Optional[Dict[str, Any]] = None,
        metadata: Optional[Dict[str, str]] = None,
    ) -> str:
        import pickle

        submission_id = submission_id or f"raytpu-job-{uuid.uuid4().hex[:10]}"
        if ":" in submission_id:
            raise ValueError("submission_id may not contain ':'")
        env_vars = dict((runtime_env or {}).get("env_vars", {}))
        sup = JobSupervisor.options(name=f"_job_supervisor:{submission_id}").remote(
            submission_id, entrypoint, env_vars, self._gcs_address
        )
        self._supervisors[submission_id] = sup
        self._worker.core.gcs.call(
            "kv_put",
            (
                _NS,
                f"{submission_id}:meta",
                pickle.dumps(
                    {
                        "submission_id": submission_id,
                        "entrypoint": entrypoint,
                        "metadata": metadata or {},
                        "submitted_at": time.time(),
                    }
                ),
                True,
            ),
        )
        self._worker.core.gcs.call(
            "kv_put",
            (_NS, f"{submission_id}:status",
             pickle.dumps({"status": JobStatus.PENDING, "message": "", "ts": time.time()}),
             True),
        )
        self._runs[submission_id] = sup.run.remote()
        return submission_id

    def get_job_status(self, submission_id: str) -> str:
        import pickle

        raw = self._kv_get(submission_id, "status")
        if raw is None:
            raise ValueError(f"unknown job {submission_id!r}")
        return pickle.loads(raw)["status"]

    def get_job_info(self, submission_id: str) -> Dict[str, Any]:
        import pickle

        meta = self._kv_get(submission_id, "meta")
        status = self._kv_get(submission_id, "status")
        if meta is None:
            raise ValueError(f"unknown job {submission_id!r}")
        info = pickle.loads(meta)
        info.update(pickle.loads(status) if status else {})
        return info

    def _log_location(self, submission_id: str) -> Optional[Dict[str, str]]:
        import pickle

        raw = self._kv_get(submission_id, "logmeta")
        return pickle.loads(raw) if raw is not None else None

    def get_job_logs(self, submission_id: str) -> str:
        """The job's full output so far: read live through the cluster log
        plane from the node running the supervisor; the pre-log-plane KV
        buffer is the fallback."""
        meta = self._log_location(submission_id)
        if meta is not None:
            from ray_tpu.util import state as state_api

            try:
                lines = list(
                    state_api.get_log(
                        node_id=meta["node_id"], filename=meta["filename"],
                        tail=-1,
                    )
                )
                return "".join(line + "\n" for line in lines)
            except Exception:  # noqa: BLE001 - node gone: fall back to KV
                pass
        raw = self._kv_get(submission_id, "logs")
        return (raw or b"").decode(errors="replace")

    def tail_job_logs(
        self, submission_id: str, *, timeout: float = 600.0, poll_s: float = 0.2
    ):
        """Yield the job's output lines as they are produced (the SDK's
        ``follow=True`` streaming, reference: JobSubmissionClient.tail_job_logs).
        Returns once the job reaches a terminal status and the log is fully
        drained."""
        from ray_tpu.util import state as state_api

        deadline = time.monotonic() + timeout
        meta = None
        while meta is None:
            meta = self._log_location(submission_id)
            if meta is not None:
                break
            if self.get_job_status(submission_id) in JobStatus.TERMINAL:
                # terminal before a log file existed (spawn failure or a
                # supervisor without a session dir): replay the KV copy
                for line in self.get_job_logs(submission_id).splitlines():
                    yield line
                return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"job {submission_id} produced no log within {timeout}s"
                )
            time.sleep(poll_s)
        offset = 0
        buf = b""
        terminal = False
        while True:
            chunk = state_api.read_log_chunk(
                node_id=meta["node_id"],
                filename=meta["filename"],
                offset=offset,
                follow=not terminal,
                timeout_s=1.0,
            )
            if chunk.get("error"):
                if self.get_job_status(submission_id) in JobStatus.TERMINAL:
                    return
                time.sleep(poll_s)
                continue
            offset = chunk["next_offset"]
            buf += chunk["data"]
            while b"\n" in buf:
                raw, buf = buf.split(b"\n", 1)
                yield raw.decode(errors="replace")
            if chunk.get("eof"):
                if terminal:
                    if buf:
                        yield buf.decode(errors="replace")
                    return
                # every write strictly precedes the terminal status, so one
                # more (non-follow) read after observing it drains anything
                # written between this read and the status check
                terminal = (
                    self.get_job_status(submission_id) in JobStatus.TERMINAL
                )
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"job {submission_id} still streaming after {timeout}s"
                )

    def list_jobs(self) -> List[Dict[str, Any]]:
        keys = self._worker.core.gcs.call("kv_keys", (_NS, ""))
        ids = sorted({k.split(":", 1)[0] for k in keys})
        return [self.get_job_info(i) for i in ids]

    def stop_job(self, submission_id: str) -> bool:
        sup = self._supervisors.get(submission_id)
        if sup is None:
            try:
                sup = ray_tpu.get_actor(f"_job_supervisor:{submission_id}")
            except Exception:
                return False
        return ray_tpu.get(sup.stop.remote(), timeout=30)

    def wait_until_finish(
        self, submission_id: str, timeout: float = 600.0, poll_s: float = 0.2
    ) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            status = self.get_job_status(submission_id)
            if status in JobStatus.TERMINAL:
                return status
            time.sleep(poll_s)
        raise TimeoutError(f"job {submission_id} still running after {timeout}s")
