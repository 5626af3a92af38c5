"""This configuration's expert layers as a share of their roofline in the traced
sub-window, as ``moe.experts_roofline`` reads Command A+'s: the time the chip
needs at its peaks for what the routed and shared experts had to do
(``models/kimi_k2.py`` ``experts_work``: 2 operations a parameter of an expert
per token-expert pair computed here and per token through the shared expert;
the weights of every held expert that had a token, and of the shared expert,
read once per call and expert layer; activations not counted: **lower bounds**)
over the device seconds under ``extend.moe.experts`` + ``extend.moe.shared``.

The counts are the whole load's, scaled by the share of its time inside engine
steps that the traced steps took, as ``mla.attend_roofline`` scales its own: an
**estimate**, which assumes the traced seconds carry the load's own mix of
calls (PERF.md, section 7, S7b (4)). A sub-window of decode calls alone would
read high: a 512-token chunk hits all 12 held experts, a decode call few."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "kimi-k2-instruct-serve-ep32.json",
)
SCOPES = ("extend.moe.experts", "extend.moe.shared")


def read(run):
    from benchmark import yardstick
    from benchmark.models import kimi_k2

    trace, counters = run.get("trace") or {}, run.get("counters") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    in_steps = (counters.get("phase_s") or {}).get("step")
    if not seconds or not in_steps or not counters.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = kimi_k2.experts_work(json.load(f), counters)
    traced = trace["engine"]["in_step_s"] / in_steps
    return yardstick.roofline_share(
        traced * work["flops"], traced * work["bytes"], seconds, run["device"]["kind"])
