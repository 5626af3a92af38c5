"""Share of the traced sub-window's device time that is filed under no scope:
the self time of every operation whose ``op_name`` holds no dotted
``jax.named_scope`` (``ops_by_scope``'s ``(no scope)``: what a scan does
between its scopes, a copy the compiler adds at a program's edge, and whatever
slipped out of the scope it belongs to) / the device's busy time. 0 where
scopes were filed and none of the time is outside them.

An instruction's scope is looked up in the table of the module that ran it,
by the module's **name** (``trace_reduce.load_scopes``): where two programs of
a window share a name, one's table names the other's ``fusion.N`` and seconds
move between the scopes and this row. So on identical files the share reads
the same from run to run only where every program has a name of its own."""

NO_SCOPE = "(no scope)"


def read(run):
    trace = run.get("trace") or {}
    scopes = trace.get("ops_by_scope")
    if not scopes or not trace.get("busy_s"):
        return None
    return 100.0 * dict(map(tuple, scopes)).get(NO_SCOPE, 0.0) / trace["busy_s"]
