"""Worker process entry point.

Spawned by the raylet (reference: python/ray/_private/workers/default_worker.py).
Connects back to its raylet, registers, serves the direct task transport, and
hosts the per-process CoreWorker so tasks can themselves call
``ray_tpu.get/put/remote`` (nested tasks).
"""

from __future__ import annotations

import logging
import os
import sys
import threading


def main():
    import faulthandler

    faulthandler.enable()  # native crashes leave a stack in the worker log
    logging.basicConfig(
        level=os.environ.get("RAYTPU_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    from ray_tpu._private.core_worker import CoreWorker
    from ray_tpu._private.ids import JobID, WorkerID
    from ray_tpu._private.rpc import RpcServer
    from ray_tpu._private.task_executor import TaskExecutor
    import ray_tpu._private.worker as worker_mod

    from ray_tpu._private import rpc as rpc_mod

    token = rpc_mod.load_or_create_token(
        os.environ.get("RAYTPU_SESSION_DIR", "/tmp")
    ) or os.environ.get("RAYTPU_AUTH_TOKEN")
    if token:
        rpc_mod.configure_auth(token)

    if os.environ.get("JAX_PLATFORMS") == "tpu":
        # the raylet pins the platform of every worker it spawns: this one
        # holds a TPU lease and is about to take the chip
        from ray_tpu._private.accelerator import enable_compile_cache

        enable_compile_cache()

    worker_id = WorkerID.from_hex(os.environ["RAYTPU_WORKER_ID"])
    raylet_addr = (os.environ["RAYTPU_RAYLET_HOST"], int(os.environ["RAYTPU_RAYLET_PORT"]))
    gcs_addr = (os.environ["RAYTPU_GCS_HOST"], int(os.environ["RAYTPU_GCS_PORT"]))
    session_dir = os.environ.get("RAYTPU_SESSION_DIR", "/tmp")

    import time as _time

    _boot_t0 = _time.monotonic()
    _timing = os.environ.get("RAYTPU_BOOT_TIMING") == "1"

    def _mark(stage: str):
        if _timing:
            print(
                f"[boot-timing] {stage} +{_time.monotonic() - _boot_t0:.3f}s"
                f" wall={_time.time():.3f}",
                flush=True,
            )

    _mark("main_entry")

    core = CoreWorker(
        mode="worker",
        job_id=JobID.from_int(0),
        gcs_address=gcs_addr,
        raylet_address=raylet_addr,
        worker_id=worker_id,
        session_dir=session_dir,
    )
    _mark("core_worker")
    # adopt the cluster-wide config (the driver's _system_config) before
    # any task runs; local RAYTPU_* env overrides keep precedence
    from ray_tpu._private.config import GlobalConfig

    try:
        GlobalConfig.apply_cluster(core.gcs.call("get_config", timeout=10.0))
    except Exception:
        logging.getLogger(__name__).warning("could not fetch cluster config")
    # the trace sample rate may have arrived with the cluster config (it
    # was read once already, inside CoreWorker.__init__, before the fetch)
    from ray_tpu._private import trace as _trace_mod

    _trace_mod.init_from_config()
    _mark("cluster_config")
    server = RpcServer(f"worker-{worker_id.hex()[:8]}")
    TaskExecutor(core, server)
    _mark("task_executor")
    core.late_register(server.address)
    _mark("late_register")

    # expose the runtime to user code running in tasks
    worker_mod.global_worker = worker_mod.Worker(core, session_dir, is_driver=False)

    # park until the raylet connection drops: a worker must never outlive
    # its raylet (reference: core_worker.h:1317 ExitIfParentRayletDies) —
    # a SIGKILL'd driver/raylet would otherwise strand hundreds of idle
    # workers. Normal shutdown also arrives as SIGTERM from the raylet.
    core.raylet._closed.wait()
    logging.getLogger(__name__).info("raylet connection lost; exiting")
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
