"""The expert layer's share of its roofline in the traced sub-window: the time
the chip needs at its peaks for what the routed and shared experts had to do
(``models/cohere2_moe.py`` ``experts_work``: 2 operations a parameter of an
expert per token-expert pair computed and per token through a shared expert;
the weights of every held expert that had a token, and of the shared experts,
read once per call and layer; activations not counted) over the device seconds
under ``extend.moe.experts`` + ``extend.moe.shared``.

The counts are ``counters.traced``'s: what ``extend`` counted, and the calls the
engine dispatched (``phase_n.dispatch``: the shared experts' reads a call), in
exactly the engine steps the profiler session recorded, **not scaled** from the
whole load: a chunk hits every held expert and a decode call few, so the
reading follows the recorded calls and not the load's mix of them. A program
that keeps no such record: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "command-a-plus-serve-ep8.json",
)
SCOPES = ("extend.moe.experts", "extend.moe.shared")


def read(run):
    from benchmark import yardstick
    from benchmark.models import cohere2_moe

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    if not seconds or not counted.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = cohere2_moe.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
