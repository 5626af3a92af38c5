"""Share of the traced steps' device time spent in the expert layers: the self
time of every operation whose innermost scope is ``train.moe.*`` (``route``,
``experts``; the backward's operations inherit the forward's scope) / the
device's busy time. A program whose train step names no such scope: nothing."""


def read(run):
    trace = run.get("trace") or {}
    moe = sum(t for scope, t in trace.get("ops_by_scope") or [] if scope.startswith("train.moe."))
    return 100.0 * moe / trace["busy_s"] if moe and trace.get("busy_s") else None
