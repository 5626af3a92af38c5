"""WorkerGroup: a gang of train-worker actors.

(reference: python/ray/train/_internal/worker_group.py:100 — here the gang is
placement-group backed, and on TPU it is one worker per slice host.)
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train import session as session_mod
from ray_tpu.train.checkpoint import Checkpoint

logger = logging.getLogger(__name__)


@ray_tpu.remote
class TrainWorker:
    """Hosts one rank of the training job. ``run`` executes the user loop;
    ``poll_reports`` / ``finished`` are called concurrently by the driver
    (max_concurrency set at creation)."""

    def __init__(self, world_size: int, rank: int, coordinator: Dict[str, Any]):
        self.world_size = world_size
        self.rank = rank
        os.environ["RAYTPU_TRAIN_WORLD_SIZE"] = str(world_size)
        os.environ["RAYTPU_TRAIN_RANK"] = str(rank)
        # one gang worker per host in this framework, so local rank is 0;
        # torch get_device and tooling read the standard LOCAL_RANK name
        os.environ["RAYTPU_TRAIN_LOCAL_RANK"] = "0"
        os.environ.setdefault("LOCAL_RANK", "0")
        for k, v in (coordinator or {}).items():
            os.environ[k] = str(v)
        self._session = None
        self._error: Optional[str] = None

    def make_coordinator(self) -> str:
        """Rank 0 picks a coordinator address ON ITS OWN HOST (multi-host
        jax.distributed needs a port reachable from every other rank; a
        driver-probed port would be on the wrong machine)."""
        import socket

        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        try:
            host = socket.gethostbyname(socket.gethostname())
        except OSError:
            host = "127.0.0.1"
        return f"{host}:{port}"

    def set_coordinator(self, address: str) -> bool:
        os.environ["RAYTPU_COORDINATOR_ADDRESS"] = address
        os.environ["JAX_COORDINATOR_ADDRESS"] = address
        return True

    def init_jax_distributed(self, local_device_count=None) -> bool:
        """The dist.init_process_group moment (reference:
        train/torch/config.py:113): join the gang's jax.distributed world
        so device_count spans every rank. On CPU workers the collectives
        ride gloo; on TPU hosts the coordination service uses the native
        backend. Must run before ANY other jax call in this process."""
        if local_device_count:
            # n virtual CPU devices per rank (must precede backend init)
            from ray_tpu._private.virtual_mesh import set_virtual_cpu_env

            set_virtual_cpu_env(local_device_count)
        import jax

        if os.environ.get("JAX_PLATFORMS", "") == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
            num_processes=self.world_size,
            process_id=self.rank,
        )
        return True

    def setup_collective(
        self,
        group_name: str,
        backend: str = "host",
        sharded_update: bool = False,
    ) -> bool:
        """Join the gang's host collective group (the DDP-equivalent plane
        for host tensors; device tensors use in-program XLA collectives).
        The env exports are what ``ShardedUpdate`` reads for its defaults,
        so a user loop needs no plumbing beyond ``sharded_update=True`` on
        the trainer."""
        from ray_tpu.util import collective

        os.environ["RAYTPU_TRAIN_COLLECTIVE_GROUP"] = group_name
        os.environ["RAYTPU_TRAIN_SHARDED_UPDATE"] = "1" if sharded_update else "0"
        if not collective.is_group_initialized(group_name):
            collective.init_collective_group(
                self.world_size, self.rank, backend=backend, group_name=group_name
            )
        return True

    def run(
        self,
        train_fn: Callable,
        config: Dict[str, Any],
        checkpoint: Optional[Checkpoint],
        dataset_shard: Optional[Dict[str, Any]],
        experiment_name: str = "",
    ):
        """Run the user training loop to completion (blocking actor call)."""
        self._session = session_mod._init_session(
            world_size=self.world_size,
            world_rank=self.rank,
            local_rank=0,
            checkpoint=checkpoint,
            dataset_shards=dataset_shard,
            experiment_name=experiment_name,
        )
        try:
            import inspect

            params = [
                p
                for p in inspect.signature(train_fn).parameters.values()
                if p.kind
                in (inspect.Parameter.POSITIONAL_ONLY, inspect.Parameter.POSITIONAL_OR_KEYWORD)
            ]
            return train_fn(config or {}) if params else train_fn()
        finally:
            self._session.end()

    def init_torch_distributed(self, backend: str = "gloo") -> bool:
        """torch.distributed bring-up over the gang's coordinator
        (reference: train/torch/config.py _setup_torch_process_group):
        rank 0's host:port becomes the TCP rendezvous; gloo rides CPU
        workers, nccl would ride GPU hosts. Must precede any collective
        in the user loop."""
        import torch.distributed as dist

        if dist.is_initialized():
            return True
        address = os.environ["RAYTPU_COORDINATOR_ADDRESS"]
        dist.init_process_group(
            backend,
            init_method=f"tcp://{address}",
            rank=self.rank,
            world_size=self.world_size,
        )
        return True

    def set_tf_config(self, worker_addresses: List[str]) -> bool:
        """Export TF_CONFIG for MultiWorkerMirroredStrategy (reference:
        train/tensorflow/config.py _setup_tensorflow_environment): the full
        worker list plus this rank's index. Must precede the tf import in
        the user loop. The per-rank ports are probe-then-release (same
        scheme as the reference's get_free_port): a small window exists
        between probing and the strategy's gRPC bind — collisions surface
        as a bind error and a retried fit()."""
        import json as _json

        os.environ["TF_CONFIG"] = _json.dumps(
            {
                "cluster": {"worker": list(worker_addresses)},
                "task": {"type": "worker", "index": self.rank},
            }
        )
        return True

    def poll_reports(self, start: int) -> List[Dict[str, Any]]:
        s = self._session
        if s is None:
            return []
        with s.lock:
            return s.reports[start:]

    def host(self) -> Dict[str, Any]:
        """What this worker's host work cost and what held it (``session.host``)."""
        return session_mod.host()

    def ping(self) -> int:
        return self.rank


class WorkerGroup:
    def __init__(
        self,
        num_workers: int,
        resources_per_worker: Dict[str, float],
        placement_group=None,
        coordinator: Optional[Dict[str, Any]] = None,
    ):
        self.num_workers = num_workers
        cpus = resources_per_worker.get("CPU", 1.0)
        tpus = resources_per_worker.get("TPU", 0.0)
        extra = {
            k: v for k, v in resources_per_worker.items() if k not in ("CPU", "TPU")
        }
        self.workers = []
        for rank in range(num_workers):
            cls = TrainWorker.options(
                num_cpus=cpus,
                num_tpus=tpus or None,
                resources=extra or None,
                max_concurrency=4,
                **(
                    {
                        "scheduling_strategy": _pg_strategy(placement_group, rank),
                    }
                    if placement_group is not None
                    else {}
                ),
            )
            self.workers.append(cls.remote(num_workers, rank, coordinator or {}))

    def execute(self, method: str, *args, timeout: Optional[float] = None, **kwargs):
        """Call a method on every worker; returns rank-ordered results."""
        refs = [getattr(w, method).remote(*args, **kwargs) for w in self.workers]
        return ray_tpu.get(refs, timeout=timeout)

    def execute_async(self, method: str, *args, **kwargs):
        return [getattr(w, method).remote(*args, **kwargs) for w in self.workers]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []


def _pg_strategy(pg, rank: int):
    from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

    return PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=rank
    )
