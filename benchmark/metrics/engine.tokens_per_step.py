"""Batch occupancy: decode tokens per engine step, from the deltas of the
engine's ``decode_tokens`` and ``steps`` over the run's load."""


def read(run):
    c = run.get("counters")
    if not c or not c.get("steps") or not c.get("decode_tokens"):
        return None
    return c["decode_tokens"] / c["steps"]
