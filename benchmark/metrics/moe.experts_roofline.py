"""The expert layer's share of its roofline in the traced sub-window: the time
the chip needs at its peaks for what the routed and shared experts had to do
(``models/cohere2_moe.py`` ``experts_work``: 2 operations a parameter of an
expert per token-expert pair computed and per token through a shared expert;
the weights of every held expert that had a token, and of the shared experts,
read once per call and layer; activations not counted) over the device seconds
under ``extend.moe.experts`` + ``extend.moe.shared``.

The engine's counters cover the whole load and the trace a few seconds of it,
and the harness keeps no counter per sub-window (``benchmark/server.py``
``_probe`` holds ``steps`` and ``decode_tokens`` alone), so the counts are
scaled by the share of the load's time inside engine steps that the traced
steps took (``trace.engine.in_step_s`` / ``counters.phase_s.step``): an
estimate, which assumes the traced seconds carry the load's own mix of calls."""

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

    trace, counters = run.get("trace") or {}, run.get("counters") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    in_steps = (counters.get("phase_s") or {}).get("step")
    if not seconds or not in_steps or not counters.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = cohere2_moe.experts_work(json.load(f), counters)
    traced = trace["engine"]["in_step_s"] / in_steps
    return yardstick.roofline_share(
        traced * work["flops"], traced * work["bytes"], seconds, run["device"]["kind"])
