"""Share of the traced sub-window's device time spent deciding which keys a
query reads: the self time of every operation whose innermost scope is
``extend.attention.index`` (the indexer's projections and scores) or
``extend.attention.select`` (top-k or threshold, row gather or mask) / the
device's busy time. A program without an indexer has neither scope: nothing."""

SCOPES = ("extend.attention.index", "extend.attention.select")


def read(run):
    trace = run.get("trace") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    deciding = sum(scopes.get(s, 0.0) for s in SCOPES)
    return 100.0 * deciding / trace["busy_s"] if deciding and trace.get("busy_s") else None
