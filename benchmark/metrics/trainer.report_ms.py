"""Mean wall time of ``session.report`` per step, in the worker."""


def read(run):
    r = run.get("report_s")
    return 1e3 * sum(r) / len(r) if r else None
