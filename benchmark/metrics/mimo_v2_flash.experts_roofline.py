"""This configuration's expert layers as a share of their roofline in the traced
sub-window, as ``kimi_k2.experts_roofline`` reads Kimi's: the time the chip needs at
its peaks for what the routed experts had to do (``models/mimo_v2_flash.py``
``experts_work``: 2 operations a parameter of an expert per token-expert pair computed
here; the weights of every held expert that had a token, read once per call and
expert layer; no shared expert; activations not counted: **lower bounds**) over the
device seconds under ``extend.moe.experts``.

The counts are ``counters.traced``'s: what ``extend`` counted in exactly the engine
steps the profiler session recorded, **not scaled** from the whole load: a 512-token
chunk hits all 16 held experts and a decode call few. A program that keeps no such
record: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "mimo-v2-flash-serve-ep16.json",
)
SCOPE = "extend.moe.experts"


def read(run):
    from benchmark import yardstick
    from benchmark.models import mimo_v2_flash

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = mimo_v2_flash.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
