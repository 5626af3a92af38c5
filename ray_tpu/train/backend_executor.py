"""BackendExecutor: drives a WorkerGroup through one training run.

(reference: python/ray/train/_internal/backend_executor.py:44 — start:103
creates the worker group and calls the backend's on_start; start_training:341
launches the user loop on every rank.) The TPU backend replaces
``dist.init_process_group`` (reference train/torch/config.py:113) with
jax.distributed coordinator env vars + a host collective group.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)


class JaxConfig:
    """Backend config for jax SPMD bring-up.

    On a real multi-host slice each rank runs ``jax.distributed.initialize``
    against rank 0's coordinator; here the executor exports the standard env
    vars so the user loop (or flax utilities) can do so. A host collective
    group named ``train`` is always available for CPU-tensor sync.
    """

    def __init__(
        self,
        init_jax_distributed: bool = False,
        local_device_count: Optional[int] = None,
    ):
        self.init_jax_distributed = init_jax_distributed
        # force an n-device virtual CPU platform per rank BEFORE the
        # distributed bring-up: how multi-chip-per-host sharding logic
        # (pp x fsdp x tp meshes) is exercised without TPU hardware
        # (SURVEY.md §4 takeaway: fake topology on CPU devices)
        self.local_device_count = local_device_count


class TorchConfig:
    """Backend config for torch.distributed gangs (reference:
    python/ray/train/torch/config.py TorchConfig): every rank joins a
    process group over the gang coordinator before the user loop runs.
    ``backend="gloo"`` for CPU workers (nccl on GPU hosts)."""

    def __init__(self, backend: str = "gloo"):
        self.torch_backend = backend


class TensorflowConfig:
    """Backend config for tf.distribute MultiWorkerMirroredStrategy gangs
    (reference: python/ray/train/tensorflow/config.py): every rank gets a
    TF_CONFIG naming all ranks' addresses and its own index; the user loop
    then constructs the strategy."""

    def __init__(self):
        self.tf_config = True


class TrainingFailedError(RuntimeError):
    pass


class BackendExecutor:
    def __init__(
        self,
        scaling: ScalingConfig,
        backend: Optional[JaxConfig] = None,
        collective_group: str = "train",
        sharded_update: bool = False,
        collective_backend: Optional[str] = None,
    ):
        self.scaling = scaling
        self.backend = backend or JaxConfig()
        self.collective_group = collective_group
        self.sharded_update = sharded_update
        # sharded updates want the ring plane (shard-chunk RS/AG beats the
        # star actor on exactly the large flat tensors they move)
        self.collective_backend = collective_backend or (
            "ring" if sharded_update else "host"
        )
        self.group: Optional[WorkerGroup] = None
        self._pg = None

    def start(self):
        num = self.scaling.num_workers
        resources = self.scaling.worker_resources()
        if self.scaling.use_tpu and self.scaling.tpu_per_worker:
            # gang-reserve one bundle per slice host (atomic; the slice is
            # the failure domain)
            from ray_tpu.util.placement_group import placement_group

            self._pg = placement_group(
                [dict(resources) for _ in range(num)],
                strategy="STRICT_SPREAD",
                label_equal="tpu_slice_id",
            )
            if not self._pg.ready(timeout=120.0):
                raise TrainingFailedError(
                    f"could not gang-reserve {num}x{resources} on one TPU slice"
                )
        elif self.scaling.placement_strategy and num > 1:
            from ray_tpu.util.placement_group import placement_group

            self._pg = placement_group(
                [dict(resources) for _ in range(num)],
                strategy=self.scaling.placement_strategy,
            )
            if not self._pg.ready(timeout=120.0):
                raise TrainingFailedError(f"could not reserve {num}x{resources}")
        self.group = WorkerGroup(num, resources, placement_group=self._pg)
        # rank 0 picks a coordinator address on its own host; every rank gets
        # it before the loop starts (the jax.distributed bring-up point)
        coord = ray_tpu.get(
            self.group.workers[0].make_coordinator.remote(), timeout=120.0
        )
        self.group.execute("set_coordinator", coord, timeout=120.0)
        # join every rank to the host collective group (unique per run so
        # restarts don't collide with a stale rendezvous actor)
        group_name = f"{self.collective_group}-{time.monotonic_ns()}"
        self.group.execute(
            "setup_collective", group_name, self.collective_backend,
            self.sharded_update, timeout=120.0,
        )
        self.active_collective_group = group_name
        if getattr(self.backend, "tf_config", False):
            # every rank needs its OWN serving address (tf multi-worker),
            # gathered with the rank-ordered parallel fan-out
            addrs = self.group.execute("make_coordinator", timeout=120.0)
            self.group.execute("set_tf_config", addrs, timeout=120.0)
        if getattr(self.backend, "torch_backend", None):
            # the dist.init_process_group moment for torch gangs
            self.group.execute(
                "init_torch_distributed", self.backend.torch_backend,
                timeout=300.0,
            )
        if getattr(self.backend, "init_jax_distributed", False):
            # every rank joins the jax.distributed world NOW (before any
            # other jax call in the worker) — the init_process_group moment
            self.group.execute(
                "init_jax_distributed",
                getattr(self.backend, "local_device_count", None),
                timeout=300.0,
            )

    def start_training(
        self,
        train_fn: Callable,
        config: Dict[str, Any],
        checkpoint: Optional[Checkpoint],
        dataset_shards: Optional[List[Dict[str, Any]]] = None,
        experiment_name: str = "",
    ) -> List:
        """Launch the loop on every rank; returns the per-rank run refs."""
        assert self.group is not None, "call start() first"
        refs = []
        for rank, worker in enumerate(self.group.workers):
            shard = dataset_shards[rank] if dataset_shards else None
            refs.append(
                worker.run.remote(train_fn, config, checkpoint, shard, experiment_name)
            )
        return refs

    def poll_reports(self, rank: int, start: int) -> List[Dict[str, Any]]:
        return ray_tpu.get(
            self.group.workers[rank].poll_reports.remote(start), timeout=60.0
        )

    def host(self, rank: int) -> Dict[str, Any]:
        """Rank ``rank``'s record of its host: what its loop's steps cost its
        thread, and the steps that stood still (``session.host``)."""
        return ray_tpu.get(self.group.workers[rank].host.remote(), timeout=60.0)

    def shutdown(self):
        if self.group is not None:
            self.group.shutdown()
            self.group = None
        # reap the per-run rendezvous actor (a fault-tolerant run would
        # otherwise leak one per restart)
        group_name = getattr(self, "active_collective_group", None)
        if group_name is not None:
            try:
                store = ray_tpu.get_actor(f"__collective_store__{group_name}")
                ray_tpu.kill(store)
            except Exception:
                pass
            self.active_collective_group = None
        if self._pg is not None:
            try:
                from ray_tpu.util.placement_group import remove_placement_group

                remove_placement_group(self._pg)
            except Exception:
                pass
            self._pg = None
