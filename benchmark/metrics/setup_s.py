"""Process start to the first instant of the measured window: loading,
warming up and, in a run that compiles, compilation."""


def read(run):
    return run.get("setup_s")
