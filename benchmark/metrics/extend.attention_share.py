"""Share of the traced sub-window's device time spent under the scope
``extend.attention`` (projections, cache update, scores over the padded pair,
softmax) / the device's busy time."""


def read(run):
    trace = run.get("trace") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    attention = scopes.get("extend.attention")
    return 100.0 * attention / trace["busy_s"] if attention and trace.get("busy_s") else None
