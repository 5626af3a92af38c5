"""Share of the traced sub-window's device time spent in the expert layer:
the self time of every operation whose innermost scope is ``extend.moe.*``
(``route``, ``experts``, ``shared``) / the device's busy time."""


def read(run):
    trace = run.get("trace") or {}
    moe = sum(t for scope, t in trace.get("ops_by_scope") or [] if scope.startswith("extend.moe."))
    return 100.0 * moe / trace["busy_s"] if moe and trace.get("busy_s") else None
