"""Node bootstrap: starts and supervises the per-node services.

A head node hosts the GCS and one raylet; additional nodes (in tests, the
in-process ``Cluster`` fixture; in production, other TPU-VM hosts) host one
raylet each pointing at the head's GCS (reference: python/ray/_private/
node.py:37, services.py — here the services are in-process servers rather
than spawned binaries; worker processes are real subprocesses).
"""

from __future__ import annotations

import os
import tempfile
import time
import uuid
from typing import Any, Dict, Optional, Tuple

from ray_tpu._private.accelerator import detect_tpu_chips
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet


def _detect_tpu_resources() -> Dict[str, float]:
    """Surface TPU chips as a first-class resource (the reference has no TPU
    resource at all — util/accelerators/accelerators.py is GPU-only).

    Detection reads the host's device nodes, NOT ``import jax``:
    initializing the TPU runtime claims the chip for this process, and the
    driver must leave it free for TPU-leased workers. ``RAYTPU_TPU_TOPOLOGY``
    overrides what the host shows: "v5e" is one chip, "v5e-8" is 8 chips on
    this host.
    """
    topo = os.environ.get("RAYTPU_TPU_TOPOLOGY")
    if topo:
        if "-" in topo:
            try:
                return {"TPU": float(int(topo.rsplit("-", 1)[1]))}
            except ValueError:
                pass
        return {"TPU": 1.0}
    chips = detect_tpu_chips()
    return {"TPU": float(chips)} if chips else {}


class Node:
    def __init__(
        self,
        head: bool = True,
        gcs_address: Optional[Tuple[str, int]] = None,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        store_capacity: Optional[int] = None,
        session_dir: Optional[str] = None,
        num_cpus: Optional[float] = None,
        detect_tpu: bool = True,
        node_name: str = "head",
        gcs_host: str = "127.0.0.1",
        gcs_port: int = 0,
    ):
        if session_dir is None:
            session_dir = os.path.join(
                tempfile.gettempdir(), f"raytpu_session_{uuid.uuid4().hex[:12]}"
            )
        os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
        self.session_dir = session_dir
        # session auth: the head mints the shared-secret token; joining
        # nodes bring one (process-global, env, or pre-seeded session file)
        # and persist it into their own session dir so the workers they
        # spawn inherit it (rpc.py AUTH frames)
        from ray_tpu._private import rpc as rpc_mod

        if head:
            rpc_mod.configure_auth(
                rpc_mod.load_or_create_token(session_dir, create=True)
            )
        else:
            token = (
                rpc_mod.session_token()
                or os.environ.get("RAYTPU_AUTH_TOKEN")
                or rpc_mod.load_or_create_token(session_dir)
            )
            if token:
                rpc_mod.configure_auth(token)
                rpc_mod.persist_token(session_dir, token)
        self.gcs: Optional[GcsServer] = None
        if head:
            assert gcs_address is None
            self.gcs = GcsServer(host=gcs_host, port=gcs_port)
            gcs_address = self.gcs.address
        self.gcs_address = gcs_address

        res = dict(resources or {})
        if "CPU" not in res:
            res["CPU"] = float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))
        if detect_tpu and "TPU" not in res:
            res.update(_detect_tpu_resources())
        labels = dict(labels or {})
        if res.get("TPU"):
            # pod-slice topology labels drive gang scheduling (util/tpu.py);
            # a single host defaults to being its own slice
            labels.setdefault(
                "tpu_slice_id",
                os.environ.get(
                    "RAYTPU_TPU_SLICE_ID",
                    # host-unique fallback: unrelated single hosts must never
                    # look like one ICI-connected slice
                    f"slice-{node_name}-{uuid.uuid4().hex[:8]}",
                ),
            )
            labels.setdefault(
                "tpu_topology", os.environ.get("RAYTPU_TPU_TOPOLOGY", "")
            )
            labels.setdefault(
                "tpu_worker_index", os.environ.get("RAYTPU_TPU_WORKER_INDEX", "0")
            )
        self.raylet = Raylet(
            session_dir,
            gcs_address,
            resources=res,
            labels=labels,
            store_capacity=store_capacity,
            node_name=node_name,
        )

    @property
    def raylet_address(self) -> Tuple[str, int]:
        return self.raylet.address

    def stop(self, graceful: bool = True):
        """``graceful=False`` simulates a crash: no unregister, the GCS
        health checker must detect the death."""
        self.raylet.stop(unregister=graceful)
        if self.gcs is not None:
            self.gcs.stop()
