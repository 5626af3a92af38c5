"""What the device spends on a prefill call, as the host sees it: the engine's
``calls["prefill"]["busy_s"]`` / ``["n"]`` over the run's load, where a call's
``busy_s`` is its landing less the later of its own launch's return and the
landing before it (exact while the device is never idle between calls, an
upper bound otherwise). A program that keeps no record per call: nothing."""


def read(run):
    calls = ((run.get("counters") or {}).get("calls") or {}).get("prefill") or {}
    if not calls.get("n"):
        return None
    return 1e3 * calls["busy_s"] / calls["n"]
