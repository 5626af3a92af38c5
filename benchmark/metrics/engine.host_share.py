"""The share of a recorded engine step in which the host does something other
than wait for the device: 1 - ``phase_s["fetch"]`` / ``phase_s["step"]`` of the
engine's ``traced`` counters (the steps a profiler session recorded), as
deltas over the run. A program without the group: nothing."""


def read(run):
    spent = ((run.get("counters") or {}).get("traced") or {}).get("phase_s") or {}
    if not spent.get("step"):
        return None
    return 100.0 * (1.0 - spent["fetch"] / spent["step"])
