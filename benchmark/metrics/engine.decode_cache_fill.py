"""The cache bucket's padding: the live tokens the engine gathered for its
decode calls / their lanes x cache bucket (``calls["decode"]["cache_tokens"]``
/ ``["cache_slots"]``), over the run's load: every lane of a call is padded to
the longest one's bucket. A program that keeps no record per call: nothing."""


def read(run):
    calls = ((run.get("counters") or {}).get("calls") or {}).get("decode") or {}
    if not calls.get("cache_slots"):
        return None
    return 100.0 * calls["cache_tokens"] / calls["cache_slots"]
