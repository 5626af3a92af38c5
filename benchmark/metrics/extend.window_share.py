"""Share of the traced sub-window's device time spent in the layers that see a
window only: the self time of every operation whose innermost scope is
``extend.attention.window`` (a sliding layer's projections, rotation, the read of the
lanes' slots in the window store, the attend under the sink, the slots' write and the
snapshot's) or lies under it / the device's busy time. A program whose windows are
per-token rows under a mask has no such scope: nothing."""

SCOPE = "extend.attention.window"


def read(run):
    trace = run.get("trace") or {}
    under = sum(
        seconds for scope, seconds in map(tuple, trace.get("ops_by_scope") or [])
        if scope == SCOPE or scope.startswith(SCOPE + "."))
    return 100.0 * under / trace["busy_s"] if under and trace.get("busy_s") else None
