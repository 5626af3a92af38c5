"""Share of the traced sub-window's device time spent in the gated-delta-rule mixers:
the self time of every operation whose innermost scope is ``extend.delta`` (the
projections, the convolution, the norms, the gate, the output) or lies under it
(``extend.delta.scan``: the rule itself in either form, with its state's read and
writes) / the device's busy time. A program without such a layer has no such scope:
nothing."""

SCOPE = "extend.delta"


def read(run):
    trace = run.get("trace") or {}
    under = sum(
        seconds for scope, seconds in map(tuple, trace.get("ops_by_scope") or [])
        if scope == SCOPE or scope.startswith(SCOPE + "."))
    return 100.0 * under / trace["busy_s"] if under and trace.get("busy_s") else None
