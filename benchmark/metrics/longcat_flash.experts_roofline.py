"""LongCat-Flash-Chat's held experts as a share of their roofline in the traced
sub-window, as ``glm_moe_dsa.experts_roofline`` reads GLM-5's, without a shared expert:
the time the chip needs at its peaks for what the held experts had to do
(``models/longcat_flash.py`` ``experts_work``: 2 operations a parameter of an expert per
token-expert pair computed here, ``moe_assignments``; the weights of every held expert
that had a token, read once per call and layer, ``moe_experts_hit``; a pair that fell on
a zero-compute expert is no work and under another scope, ``extend.moe.zero``;
activations, the sort and the combine not counted: **lower bounds**) over the device
seconds under ``extend.moe.experts``.

The counts are ``counters.traced``'s: what ``extend`` counted in exactly the engine steps
the profiler session recorded, **not scaled** from the whole load: a 512-token chunk hits
all 16 held experts and a decode call of a few lanes one or two. A program that keeps no
such record, or has no such scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "longcat-flash-chat-serve-ep32.json",
)
SCOPE = "extend.moe.experts"


def read(run):
    from benchmark import yardstick
    from benchmark.models import longcat_flash

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("moe_assignments") or "moe_zero_assignments" not in counted:
        return None
    with open(CONFIG) as f:
        work = longcat_flash.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
