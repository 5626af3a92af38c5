"""Where a run meets the machine: the cluster it starts, the chips it insists
on, and the clock both sides of a run share.

The benchmark's own process never initializes a jax backend: the chip belongs
to the worker ``JaxTrainer`` or ``serve.run`` starts, and a parent that held
it would make that worker fail. A run that finds no accelerator stops here
with no result; there is no fall back to the CPU. The tests rehearse the
flows on CPU workers by setting ``PLATFORM`` to ``"cpu"``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator

PLATFORM = "tpu"


class NoChip(Exception):
    """The machine does not hold the chips the cell asks for."""


def now() -> float:
    """Wall clock, the one clock the parent and its workers can compare."""
    return time.time()


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parent_holds_no_backend() -> None:
    from ray_tpu._private import virtual_mesh

    if PLATFORM == "tpu" and virtual_mesh.backends_initialized():
        raise NoChip("the benchmark's own process initialized a jax backend")


@contextlib.contextmanager
def cluster(chips: int) -> Iterator[Any]:
    """One cluster per run, shut down at its end, on a machine whose device
    nodes show at least ``chips`` TPU chips."""
    import ray_tpu
    from ray_tpu import serve

    parent_holds_no_backend()
    worker = ray_tpu.init(log_level="WARNING")
    try:
        (node,) = ray_tpu.nodes()
        found = node["resources"].get("TPU", 0)
        say(f"cluster up: resources {node['resources']}, session {worker.session_dir}")
        if PLATFORM == "tpu" and found < chips:
            raise NoChip(
                f"the cell asks for {chips} TPU chip(s) and ray_tpu.init() found "
                f"{found:g} (no /dev/accel* or /dev/vfio/* TPU node, and "
                f"RAYTPU_TPU_TOPOLOGY is unset)"
            )
        yield worker
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def check_device(device: Dict[str, Any], chips: int, who: str) -> None:
    """``device`` is what the process that ran the model saw."""
    from benchmark import yardstick

    if device["platform"] != PLATFORM:
        raise NoChip(f"{who} ran on platform {device['platform']!r}, not {PLATFORM!r}")
    if PLATFORM == "tpu":
        yardstick.peak(device["kind"], "bf16_flops")      # an unknown kind is an error
        if device["count"] != chips:
            raise NoChip(f"{who} saw {device['count']} chip(s), the cell asks for {chips}")
