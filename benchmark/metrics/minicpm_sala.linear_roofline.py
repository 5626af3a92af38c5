"""MiniCPM-SALA's linear recurrence as a share of its roofline in the traced
sub-window: the time the chip needs at its peaks for what the recurrence itself had
to do (``models/minicpm_sala.py`` ``linear_work``: 4 operations a state element a
token and layer, whatever form computes them; a layer's state read and written once
a lane, layer and call, and a token's q, k, v and o) over the device seconds under
``extend.linear.scan``. The counts are ``counters.traced``'s, **not scaled**. A program
that keeps no such record, or has no such layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "minicpm-sala-serve-pp2.json",
)
SCOPE = "extend.linear.scan"


def read(run):
    from benchmark import yardstick
    from benchmark.models import minicpm_sala

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("linear_tokens"):
        return None
    with open(CONFIG) as f:
        work = minicpm_sala.linear_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
