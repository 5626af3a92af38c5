"""The Mamba-2 recurrence as a share of its roofline in the traced sub-window:
the time the chip needs at its peaks for what the recurrence itself had to do
(``models/granitemoehybrid.py`` ``scan_work``: 4 operations a state element a
token and Mamba layer, whatever form computes it; a layer's state read and
written once a lane and call, and a token's ``x``, ``B``, ``C``, ``D_t`` and ``y``)
over the device seconds under ``extend.ssm.scan``.

The counts are ``counters.traced``'s: what ``extend`` counted in exactly the
engine steps the profiler session recorded, **not scaled** from the whole load.
A program that keeps no such record, or has no recurrent layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "granite-4.0-h-micro-serve.json",
)
SCOPE = "extend.ssm.scan"


def read(run):
    from benchmark import yardstick
    from benchmark.models import granitemoehybrid

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("ssm_tokens"):
        return None
    with open(CONFIG) as f:
        work = granitemoehybrid.scan_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
