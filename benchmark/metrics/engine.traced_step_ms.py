"""Mean wall time of an engine step over the steps the profiler session
recorded, by the program's own clock: ``phase_s["step"]`` / ``steps`` of the
engine's ``traced`` counters (``kv_stats()["traced"]``, kept over exactly the
steps that begin and end in a session), as deltas over the run. What
``engine.step_ms`` times from outside. A program without the group: nothing."""


def read(run):
    traced = (run.get("counters") or {}).get("traced") or {}
    if not traced.get("steps"):
        return None
    return 1e3 * traced["phase_s"]["step"] / traced["steps"]
