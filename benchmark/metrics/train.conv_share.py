"""Share of the traced steps' device time spent in the gated short
convolutions: the self time of every operation whose innermost scope is
``train.conv`` (both projections, the gates, the taps, and their backward) /
the device's busy time. A program whose train step names no such scope: nothing."""

SCOPE = "train.conv"


def read(run):
    trace = run.get("trace") or {}
    conv = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    return 100.0 * conv / trace["busy_s"] if conv and trace.get("busy_s") else None
