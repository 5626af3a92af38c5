"""Mean wall time of an ``engine.step`` call in the traced sub-window: the
replica's clock around each call / the engine's own ``steps`` counter."""


def read(run):
    engine = (run.get("trace") or {}).get("engine")
    if not engine or not engine["steps"]:
        return None
    return 1e3 * engine["in_step_s"] / engine["steps"]
