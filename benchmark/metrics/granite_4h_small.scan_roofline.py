"""granite-4.0-h-small's Mamba-2 recurrence as a share of its roofline in the
traced sub-window, as ``ssm.scan_roofline`` reads micro's but at **this**
configuration's widths (128 heads: a state of 4 MB a layer where micro's is 2):
the time the chip needs at its peaks for what the recurrence itself had to do
(``models/granitemoehybrid.py`` ``scan_work``) over the device seconds under
``extend.ssm.scan``. The counts are ``counters.traced``'s, **not scaled**. A
program that keeps no such record, or has no recurrent layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "granite-4.0-h-small-serve-ep2.json",
)
SCOPE = "extend.ssm.scan"


def read(run):
    from benchmark import yardstick
    from benchmark.models import granitemoehybrid_moe

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("ssm_tokens"):
        return None
    with open(CONFIG) as f:
        work = granitemoehybrid_moe.scan_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
