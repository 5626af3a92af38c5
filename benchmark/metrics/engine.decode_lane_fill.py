"""The lane bucket's padding: the real lanes of the engine's decode calls /
their lane buckets (``calls["decode"]["lanes_used"]`` / ``["lane_slots"]``),
over the run's load: a third decoding request takes a bucket of four. A
program that keeps no record per call: nothing."""


def read(run):
    calls = ((run.get("counters") or {}).get("calls") or {}).get("decode") or {}
    if not calls.get("lane_slots"):
        return None
    return 100.0 * calls["lanes_used"] / calls["lane_slots"]
