"""Training result (reference: python/ray/air/result.py)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.train.checkpoint import Checkpoint


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    path: Optional[str] = None
    #: worker 0's record of its host (``session.host``): what the loop's steps
    #: cost its thread (``host["train.report"]``), the collector (``gc``), how
    #: many steps stood still and by which cause (``held``), and the last 32 of
    #: them with their stacks (``held_steps``); empty where the run failed
    host: Dict[str, Any] = field(default_factory=dict)

    @property
    def best_checkpoint(self) -> Optional[Checkpoint]:
        return self.checkpoint
