"""Share of the traced sub-window spent inside all-gather / all-reduce /
reduce-scatter / collective-permute / all-to-all operations while no other
operation ran on that chip (the chip where that is longest)."""


def read(run):
    t = run.get("trace")
    if run.get("kind") != "train" or not t or not t.get("window_s"):
        return None
    exposed = t["collective_exposed_s"]
    return 100.0 * exposed / t["window_s"] if exposed > 0 else None
