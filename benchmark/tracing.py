"""Tracing a sub-window in the process that holds the chip.

Only that process can trace the device, so both the train loop and the serve
replica use this: start the profiler, wrap each unit of work (a train step, an
engine step) in a ``TraceAnnotation``, stop after a whole number of units, and
reduce the trace there. A trace of the whole window would be too large; a few
seconds are traced and reported as such.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Any, Dict, Optional

from benchmark import trace_reduce


class SubWindowTrace:
    """``start()``, then ``unit()`` around each unit of work, then ``stop()``;
    ``result()`` reduces what was written and removes it."""

    def __init__(self, annotation: str):
        self.annotation = annotation
        self.dir: Optional[str] = None
        self.started_at: Optional[float] = None   # chip.now() clocks
        self.stopped_at: Optional[float] = None
        self.overhead_s = 0.0                     # spent starting and stopping
        self.units = 0

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def start(self) -> None:
        import jax

        t0 = time.perf_counter()
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0           # spans come from annotations
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.overhead_s += time.perf_counter() - t0
        self.started_at = time.time()

    def unit(self):
        import jax

        self.units += 1
        return jax.profiler.TraceAnnotation(self.annotation)

    def stop(self) -> None:
        import jax

        self.stopped_at = time.time()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.overhead_s += time.perf_counter() - t0

    def result(self) -> Optional[Dict[str, Any]]:
        """The reduced trace, or None where nothing was traced."""
        if self.dir is None or self.stopped_at is None:
            return None
        try:
            found = glob.glob(
                os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            if not found:
                return None
            t0 = time.perf_counter()
            reduced = trace_reduce.reduce(
                trace_reduce.load_xplane(found[0]), self.annotation,
                scopes=trace_reduce.load_scopes(found[0]),
            )
            if reduced is not None:
                reduced.update(
                    units=self.units, started_at=self.started_at,
                    stopped_at=self.stopped_at, overhead_s=self.overhead_s,
                    xplane_bytes=os.path.getsize(found[0]),
                    reduce_s=time.perf_counter() - t0,
                )
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
