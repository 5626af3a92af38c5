"""Share of the traced steps' device time spent in the Mamba-2 mixers: the self time
of every operation whose innermost scope is ``train.ssm.*`` (``proj``, ``conv``,
``scan``, ``norm``; the backward's operations and the remat's replay inherit the
forward's scope) / the device's busy time. A program whose train step names no such
scope: nothing."""


def read(run):
    trace = run.get("trace") or {}
    ssm = sum(t for scope, t in trace.get("ops_by_scope") or [] if scope.startswith("train.ssm."))
    return 100.0 * ssm / trace["busy_s"] if ssm and trace.get("busy_s") else None
