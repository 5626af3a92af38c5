"""Share of the traced sub-window in which no operation ran on the busiest
chip, from the profiler trace taken in the train worker."""


def read(run):
    t = run.get("trace")
    if run.get("kind") != "train" or not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busiest_busy_s"] / t["window_s"])
