"""Trainers: BaseTrainer → DataParallelTrainer → JaxTrainer.

(reference: python/ray/train/base_trainer.py:556 fit,
train/data_parallel_trainer.py:387 training_loop. The reference runs fit()
as a Tune trial; here fit() drives the BackendExecutor directly and the Tune
integration wraps a trainer the same way, ray_tpu/tune.)

The TPU replacement for TorchTrainer: the user's ``train_loop_per_worker``
runs once per slice host, uses ``ray_tpu.train.session`` for
report/checkpoint, and builds its SPMD mesh with ray_tpu.parallel over the
host's chips (single-host) or jax.distributed (multi-host).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.backend_executor import BackendExecutor, JaxConfig, TrainingFailedError
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.checkpoint_manager import CheckpointManager
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.result import Result

logger = logging.getLogger(__name__)


class BaseTrainer:
    def __init__(
        self,
        *,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        raise NotImplementedError

    def as_trainable(self) -> Callable:
        """Adapter for ``ray_tpu.tune.Tuner``: returns ``fn(config)`` that
        runs a per-trial fit with ``config`` merged into the train loop
        config, forwarding every report (metrics + checkpoints) to the
        trial session (reference: train/base_trainer.py wrapping trainers
        as Tune trainables)."""
        import copy
        import dataclasses as _dc

        base = self

        def _trial_fn(config):
            from ray_tpu.train import session as session_mod

            sess = session_mod._get_session()
            trainer = copy.copy(base)
            if getattr(trainer, "train_loop_config", None) is not None:
                trainer.train_loop_config = {**trainer.train_loop_config, **config}
            trainer.run_config = _dc.replace(
                base.run_config,
                name=None,
                storage_path=sess.trial_dir
                or os.path.join(
                    base.run_config.resolved_storage_path(), sess.trial_id or "trial"
                ),
            )
            trainer._report_callback = session_mod.report
            result = trainer.fit()
            if result.error is not None:
                raise result.error

        return _trial_fn


class DataParallelTrainer(BaseTrainer):
    """Runs one copy of ``train_loop_per_worker`` per worker; data is split
    across workers; gradients sync inside the loop (host collectives for CPU
    tensors, in-program XLA collectives for device state)."""

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        backend_config: Optional[JaxConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        sharded_update: bool = False,
        **kwargs,
    ):
        super().__init__(**kwargs)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config
        self.datasets = datasets or {}
        # opt-in cross-replica sharding of the weight update: workers get
        # a ring collective group + env defaults so ShardedUpdate shards
        # optimizer state 1/N per rank (see train/sharded_update.py)
        self.sharded_update = sharded_update

    # -- dataset sharding -------------------------------------------------

    def _shard_datasets(self, num_workers: int) -> Optional[List[Dict[str, Any]]]:
        if not self.datasets:
            return None
        shards: List[Dict[str, Any]] = [dict() for _ in range(num_workers)]
        for name, ds in self.datasets.items():
            split = getattr(ds, "split", None)
            if callable(split):
                parts = split(num_workers, equal=True)
            elif isinstance(ds, (list, tuple)):
                parts = [list(ds[i::num_workers]) for i in range(num_workers)]
            else:
                parts = [ds] * num_workers  # replicate opaque objects
            for i in range(num_workers):
                shards[i][name] = parts[i]
        return shards

    # -- the fit loop -----------------------------------------------------

    def fit(self) -> Result:
        failures_allowed = self.run_config.failure_config.max_failures
        ckpt_manager = CheckpointManager(
            self.run_config.resolved_storage_path(),
            self.run_config.checkpoint_config,
        )
        resume = self.resume_from_checkpoint
        history: List[Dict[str, Any]] = []
        attempt = 0
        while True:
            attempt += 1
            executor = BackendExecutor(
                self.scaling_config,
                self.backend_config,
                sharded_update=self.sharded_update,
            )
            error: Optional[BaseException] = None
            host: Dict[str, Any] = {}
            try:
                executor.start()
                run_refs = executor.start_training(
                    self.train_loop_per_worker,
                    self.train_loop_config,
                    resume,
                    self._shard_datasets(self.scaling_config.num_workers),
                    experiment_name=self.run_config.name or "",
                )
                self._drive(executor, run_refs, ckpt_manager, history)
                host = executor.host(0)
            except Exception as e:  # noqa: BLE001
                error = e
            finally:
                executor.shutdown()
            if error is None:
                return Result(
                    metrics=history[-1] if history else {},
                    checkpoint=ckpt_manager.latest,
                    metrics_history=history,
                    path=ckpt_manager.storage_path,
                    host=host,
                )
            if failures_allowed != 0 and (
                failures_allowed < 0 or attempt <= failures_allowed
            ):
                logger.warning(
                    "training attempt %d failed (%r); restarting from %s",
                    attempt,
                    error,
                    "latest checkpoint" if ckpt_manager.latest else "scratch",
                )
                resume = ckpt_manager.latest or self.resume_from_checkpoint
                continue
            return Result(
                metrics=history[-1] if history else {},
                checkpoint=ckpt_manager.latest,
                error=error,
                metrics_history=history,
                path=ckpt_manager.storage_path,
            )

    def _drive(
        self,
        executor: BackendExecutor,
        run_refs: List,
        ckpt_manager: CheckpointManager,
        history: List[Dict[str, Any]],
    ):
        """Poll every rank's reports until every rank's loop returns.

        Rank 0's metrics and checkpoints are canonical: SPMD ranks hold
        identical state, so persisting every rank's copy would write
        num_workers duplicates per step and churn num_to_keep retention.
        Reports from other ranks are drained (so their queues empty and
        their errors surface) but their checkpoints are NOT persisted —
        save checkpoints from rank 0, as in the reference's default
        (train/_internal/checkpoint.py rank-0 convention)."""
        num_workers = len(run_refs)
        seen = [0] * num_workers
        callback = getattr(self, "_report_callback", None)

        def _poll_all():
            for rank in range(num_workers):
                for entry in executor.poll_reports(rank, seen[rank]):
                    seen[rank] += 1
                    metrics = entry["metrics"]
                    if rank == 0:
                        history.append(metrics)
                        if callback is not None:
                            callback(metrics, checkpoint=entry.get("checkpoint"))
                        if "checkpoint" in entry:
                            ckpt_manager.register(entry["checkpoint"], metrics)
                    elif "checkpoint" in entry:
                        if not getattr(self, "_warned_nonzero_ckpt", False):
                            self._warned_nonzero_ckpt = True
                            logger.warning(
                                "dropping checkpoint reported by rank %d: only "
                                "rank-0 checkpoints are persisted (report "
                                "checkpoints from rank 0)", rank,
                            )

        pending = list(run_refs)
        while pending:
            done, pending = ray_tpu.wait(
                pending, num_returns=len(pending), timeout=0.2
            )
            _poll_all()
            if done:
                ray_tpu.get(done)  # surface worker exceptions
        _poll_all()  # drain reports that landed after the last wait


class JaxTrainer(DataParallelTrainer):
    """Alias with jax backend defaults (the TorchTrainer counterpart)."""

    def __init__(self, train_loop_per_worker: Callable, **kwargs):
        kwargs.setdefault("backend_config", JaxConfig())
        super().__init__(train_loop_per_worker, **kwargs)
