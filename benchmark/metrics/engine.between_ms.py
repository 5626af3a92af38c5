"""What the batcher's loop costs between two steps of a loaded engine: 1e3 x
``host["llm.between"].wall_s`` / ``steps``, deltas of ``kv_stats``: from a step's
return to the next step while a sequence is active (the wake-up of finished
callers, the admission of queued ones), which the reducer files as ``between
bench.engine_step spans``. A program without the group, or no step: nothing."""


def read(run):
    counters = run.get("counters") or {}
    between = (counters.get("host") or {}).get("llm.between")
    if between is None or not counters.get("steps"):
        return None
    return 1e3 * between["wall_s"] / counters["steps"]
