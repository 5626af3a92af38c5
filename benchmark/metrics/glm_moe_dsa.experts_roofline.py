"""This configuration's expert layers as a share of their roofline in the traced
sub-window, as ``kimi_k2.experts_roofline`` reads Kimi K2's, at GLM-5's widths: the
time the chip needs at its peaks for what the routed and shared experts had to do
(``models/glm_moe_dsa.py`` ``experts_work``: 2 operations a parameter of an expert per
token-expert pair computed here and per token through the shared expert; the weights
of every held expert that had a token, and of the shared expert, read once per call
and expert layer; activations not counted: **lower bounds**) over the device seconds
under ``extend.moe.experts`` + ``extend.moe.shared``.

The counts are ``counters.traced``'s: what ``extend`` counted, and the calls the
engine dispatched (``phase_n.dispatch``: the shared expert's reads a call), in exactly
the engine steps the profiler session recorded, **not scaled** from the whole load: a
512-token chunk hits all 16 held experts and a decode call few. A program that keeps
no such record: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "glm-5-serve-ep16.json",
)
SCOPES = ("extend.moe.experts", "extend.moe.shared")


def read(run):
    from benchmark import yardstick
    from benchmark.models import glm_moe_dsa

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    if not seconds or not counted.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = glm_moe_dsa.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
