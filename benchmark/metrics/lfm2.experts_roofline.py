"""The trained expert layers' share of their roofline in the traced steps: the
time the chip needs at its peaks for what the held experts had to do
(``models/lfm2_moe.py`` ``experts_work``: forward, the input's gradient and the
weights' gradient, 2 operations a parameter of an expert a pair each; a hit
expert's weights read once a pass and their gradient written once; a pair's
row in and out once a pass; the remat's replay not counted) over the device
seconds under ``train.moe.experts`` (sort, gather, both grouped matmuls,
scatter, their transposes, and the replay).

The pairs and the hit experts are **the steps' own counts** (``step_metrics``'
``moe_assignments`` and ``moe_experts_hit``): their mean a step of the window
times the traced steps. On a fixed batch one step's counts differ from the
next's only by the pairs the last update moved. A train step that reports no
such counters, or names no such scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "lfm2-24b-a2b-train-ep2.json",
)
SCOPE = "train.moe.experts"
COUNTED = ("moe_assignments", "moe_experts_hit")


def read(run):
    from benchmark import yardstick
    from benchmark.models import lfm2_moe

    trace = run.get("trace") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    steps = [m for m in run.get("step_metrics") or [] if m.get("moe_assignments")]
    if not seconds or not steps or not trace.get("units"):
        return None
    counted = {k: trace["units"] * sum(m[k] for m in steps) / len(steps) for k in COUNTED}
    with open(CONFIG) as f:
        work = lfm2_moe.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
