"""Of the cache slots the engine gathered for sliding-window layers, the share
holding a token too old for any query of the call to see: what a window-aware
allocator would neither keep nor gather. From the deltas of the engine's
``window_slots_outside`` and ``window_slots`` counters over the run's load."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("window_slots"):
        return None
    return 100.0 * c["window_slots_outside"] / c["window_slots"]
