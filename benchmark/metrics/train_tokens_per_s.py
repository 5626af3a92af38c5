"""Tokens of the global batch x whole steps completed in the window / the
window, for the whole job (not per chip), by the worker's clock around steps
that end in a fetched loss."""


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    return run["steps"] * run["tokens_per_step"] / run["window_s"]
