"""Share of the traced sub-window's device time spent in the Mamba-2 mixers:
the self time of every operation whose innermost scope is ``extend.ssm`` (the
projections, the convolution, the gate, the norm) or lies under it
(``extend.ssm.scan``: the recurrence itself) / the device's busy time. A
program without a recurrent layer has no such scope: nothing."""

SCOPE = "extend.ssm"


def read(run):
    trace = run.get("trace") or {}
    under = sum(
        seconds for scope, seconds in map(tuple, trace.get("ops_by_scope") or [])
        if scope == SCOPE or scope.startswith(SCOPE + "."))
    return 100.0 * under / trace["busy_s"] if under and trace.get("busy_s") else None
