"""Per-worker training session: the in-loop API.

User training loops call ``report(metrics, checkpoint=...)`` and the rank
accessors (reference: python/ray/air/session.py:43 report, :359
get_dataset_shard; impl train/_internal/session.py:427). The session is a
process-global set up by the train worker actor before the user loop runs.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from ray_tpu._private import accelerator
from ray_tpu.train.checkpoint import Checkpoint


class _Session:
    def __init__(
        self,
        world_size: int,
        world_rank: int,
        local_rank: int,
        checkpoint: Optional[Checkpoint],
        dataset_shards: Optional[Dict[str, Any]] = None,
        experiment_name: str = "",
        trial_id: str = "",
        trial_dir: str = "",
    ):
        self.world_size = world_size
        self.world_rank = world_rank
        self.local_rank = local_rank
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        self.experiment_name = experiment_name
        self.trial_id = trial_id
        self.trial_dir = trial_dir
        self.reports: List[Dict[str, Any]] = []
        self.lock = threading.Lock()
        self.finished = threading.Event()
        #: the step of the user's loop under way, as ``accelerator.HostWatch``
        #: brackets it: from one ``report`` to the next
        self.unit = None

    def end(self) -> None:
        """The loop has returned: what follows its last report is nobody's step."""
        self.finished.set()
        unit, self.unit = self.unit, None
        if unit is not None:
            accelerator.host_watch().drop(unit)


_session: Optional[_Session] = None
_session_lock = threading.Lock()


def _init_session(**kwargs) -> _Session:
    global _session
    with _session_lock:
        _session = _Session(**kwargs)
        return _session


def _shutdown_session():
    global _session
    with _session_lock:
        if _session is not None:
            _session.end()
        _session = None


def _get_session() -> _Session:
    if _session is None:
        raise RuntimeError(
            "not inside a training session (call this from a train loop "
            "launched by a Trainer)"
        )
    return _session


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (and optionally a checkpoint) to the driver.

    From one report to the next is a step of the user's loop, which is all the
    program sees of it: a unit ``train.report`` of this process's
    ``accelerator.HostWatch`` (what the step cost the loop's thread; a step that
    stood still over its usual time keeps its cause and its stack: ``host()``),
    and the call itself a span ``train.report`` on the profiler's clock."""
    s = _get_session()
    watch = accelerator.host_watch()
    now = watch.read()              # ends the step that reports, begins the next
    if s.unit is not None:
        watch.close(s.unit, now, "the loop, from its last report to this one")
    with accelerator.quiet_span("train.report"):
        entry: Dict[str, Any] = {"metrics": dict(metrics)}
        if checkpoint is not None:
            entry["checkpoint"] = checkpoint
        with s.lock:
            s.reports.append(entry)
    s.unit = watch.open("train.report", at=now, usual=True)


def host() -> Dict[str, Any]:
    """What this process's host work cost and what held it: the totals of the
    loop's steps (``host["train.report"]``), the collector's (``gc``), how many
    steps were held and by which cause (``held``) and the last 32 of them with
    their stacks (``held_steps``)."""
    return accelerator.host_watch().stats()


def get_checkpoint() -> Optional[Checkpoint]:
    """The checkpoint to resume from (set on restart/resume)."""
    return _get_session().loaded_checkpoint


def get_world_size() -> int:
    return _get_session().world_size


def get_world_rank() -> int:
    return _get_session().world_rank


def get_local_rank() -> int:
    return _get_session().local_rank


class DataShard:
    """Per-worker view of a dataset: per-epoch streaming iteration with
    host-side prefetch and double-buffered device transfer (reference:
    air/session.py:359 get_dataset_shard streams Ray Data splits; the
    device path is TPU-first — batches are device_put one step ahead so
    host→HBM transfer overlaps the previous step's compute)."""

    def __init__(self, ds: Any):
        self._ds = ds

    def __getattr__(self, name: str):
        return getattr(self._ds, name)

    def iter_batches(self, **kw):
        return self._ds.iter_batches(**kw)

    def iter_epochs(self, epochs: Optional[int] = None, **kw):
        """Yield a fresh streaming batch iterator per epoch (the blocks
        re-stream through the executor each time; nothing is cached)."""
        n = 0
        while epochs is None or n < epochs:
            yield self._ds.iter_batches(**kw)
            n += 1

    def iter_device_batches(
        self,
        *,
        sharding: Any = None,
        prefetch: int = 2,
        **kw,
    ):
        """Stream batches as device arrays, keeping ``prefetch`` transfers
        in flight: device_put is async under JAX, so batch k+1 uploads
        while batch k computes (double buffering)."""
        import collections

        import jax

        def _put(batch):
            if sharding is not None:
                return jax.tree.map(
                    lambda a: jax.device_put(a, sharding), batch
                )
            return jax.tree.map(jax.device_put, batch)

        pending: "collections.deque" = collections.deque()
        for batch in self._ds.iter_batches(**kw):
            pending.append(_put(batch))
            if len(pending) > prefetch:
                yield pending.popleft()
        while pending:
            yield pending.popleft()


def get_dataset_shard(dataset_name: str = "train"):
    shard = _get_session().dataset_shards.get(dataset_name)
    if shard is None:
        return None
    if hasattr(shard, "iter_batches") and not isinstance(shard, DataShard):
        return DataShard(shard)
    return shard


def get_experiment_name() -> str:
    return _get_session().experiment_name


def get_trial_id() -> str:
    return _get_session().trial_id
