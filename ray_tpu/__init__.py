"""ray_tpu: a TPU-native distributed runtime and ML library stack.

Core primitives (tasks, actors, objects) mirror the reference's contract
(reference: python/ray/__init__.py) while the compute path is JAX/XLA/Pallas
and collectives ride ICI/DCN via jax.sharding meshes.
"""

__version__ = "0.1.0"

import os as _os

# pyarrow's bundled mimalloc pool segfaults in mi_thread_init under heavy
# thread churn (observed: NULL+0x18 deref when many short-lived rpc threads
# make their first arrow allocation concurrently). The system allocator is
# immune; set it before pyarrow is first imported.
_os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")

from ray_tpu._private.worker import init, shutdown, is_initialized
from ray_tpu.api import (
    ActorClass,
    ActorDiedError,
    ActorHandle,
    GetTimeoutError,
    ObjectLostError,
    ObjectRef,
    ObjectRefGenerator,
    ObjectStoreFullError,
    RayTpuError,
    RemoteFunction,
    RuntimeContext,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
    cancel,
    drain_node,
    get,
    get_actor,
    get_runtime_context,
    kill,
    nodes,
    put,
    remote,
    wait,
)

# deterministic fault injection (ray_tpu.chaos.apply/clear/report);
# plain import — chaos.py itself lazy-imports the RPC layer on first call
from ray_tpu import chaos

# perf plane (ray_tpu.perf.profile/record/summarize_rpcs); also a plain
# import — perf.py lazy-imports the RPC layer on first call
from ray_tpu import perf
from ray_tpu import slo
from ray_tpu import trace


def timeline(filename=None, *, address=None):
    """Chrome-tracing dump of all task execution — always on, no opt-in
    (reference: ray.timeline). Lazy import: util.state pulls the RPC layer,
    which drivers that only ``import ray_tpu`` must not pay for."""
    from ray_tpu.util.state import timeline as _timeline

    return _timeline(filename, address=address)


__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "timeline",
    "chaos",
    "perf",
    "slo",
    "trace",
    "remote",
    "get",
    "put",
    "wait",
    "kill",
    "cancel",
    "drain_node",
    "get_runtime_context",
    "RuntimeContext",
    "get_actor",
    "nodes",
    "ObjectRef",
    "ObjectRefGenerator",
    "ActorHandle",
    "ActorClass",
    "RemoteFunction",
    "RayTpuError",
    "TaskError",
    "TaskCancelledError",
    "ActorDiedError",
    "GetTimeoutError",
    "ObjectLostError",
    "ObjectStoreFullError",
    "WorkerCrashedError",
    "__version__",
]
