"""Programs the engine first called inside traffic: ``programs_cold`` of
``kv_stats`` as a delta over lead-in, window and drain, the shapes of
``extend`` that ``warm()`` did not run (each traced and compiled, or loaded
from the compile cache, while requests waited; its ``llm.dispatch`` span
carries ``cold=1`` and its name). 0 is a warm-up that covers the load. A
program that keeps no such count: nothing."""


def read(run):
    cold = (run.get("counters") or {}).get("programs_cold")
    return None if cold is None else float(cold)
