"""Share of the traced sub-window in which no operation ran on the chip,
from the profiler trace taken in the serve replica."""


def read(run):
    t = run.get("trace")
    if run.get("kind") != "serve" or not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
