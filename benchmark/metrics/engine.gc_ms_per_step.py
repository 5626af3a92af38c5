"""What the collector cost an engine step: 1e3 x ``gc.s`` / ``steps``, deltas of
``kv_stats``: the seconds of every collection of the replica's process (one
``gc.callbacks`` hook; a collection holds the interpreter lock, whichever
thread it runs on) over the engine's steps. A program without the group, or
no step: nothing."""


def read(run):
    counters = run.get("counters") or {}
    collected = counters.get("gc")
    if collected is None or not counters.get("steps"):
        return None
    return 1e3 * collected["s"] / counters["steps"]
