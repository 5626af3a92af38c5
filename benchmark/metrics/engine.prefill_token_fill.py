"""What of a padded prefill chunk is prompt: the tokens the engine fed its
prefill calls / their lanes x token bucket (``calls["prefill"]["tokens"]`` /
``["token_slots"]``), over the run's load. A program that keeps no record per
call: nothing."""


def read(run):
    calls = ((run.get("counters") or {}).get("calls") or {}).get("prefill") or {}
    if not calls.get("token_slots"):
        return None
    return 100.0 * calls["tokens"] / calls["token_slots"]
