"""Nemotron-3-Nano's trained routed experts' share of their roofline in the traced
steps, as ``lfm2.experts_roofline`` reads LFM2's but at **this** configuration's
widths and form (two matrices an expert, no gate): the time the chip needs at its
peaks for what the held experts had to do (``models/nemotron_h.py`` ``experts_work``)
over the device seconds under ``train.moe.experts`` (sort, gather, both grouped
matmuls, scatter, their transposes; the shared expert has a scope of its own).

The pairs and the hit experts are **the steps' own counts** (``step_metrics``'
``moe_assignments`` and ``moe_experts_hit``): their mean a step of the window times
the traced steps. A train step that reports no such counters, or names no such
scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "nemotron-3-nano-30b-a3b-train-ep8.json",
)
SCOPE = "train.moe.experts"
COUNTED = ("moe_assignments", "moe_experts_hit")


def read(run):
    from benchmark import yardstick
    from benchmark.models import nemotron_h

    trace = run.get("trace") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    steps = [m for m in run.get("step_metrics") or [] if m.get("moe_assignments")]
    if not seconds or not steps or not trace.get("units"):
        return None
    counted = {k: trace["units"] * sum(m[k] for m in steps) / len(steps) for k in COUNTED}
    with open(CONFIG) as f:
        work = nemotron_h.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
