"""Live tokens of the padded K/V pairs the engine gathered / their slots, from
the deltas of the engine's ``cache_tokens`` and ``cache_slots`` counters."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("cache_slots"):
        return None
    return 100.0 * c["cache_tokens"] / c["cache_slots"]
