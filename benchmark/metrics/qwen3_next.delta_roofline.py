"""Qwen3-Next's gated delta rule as a share of its roofline in the traced sub-window:
the time the chip needs at its peaks for what the rule itself had to do
(``models/qwen3_next.py`` ``delta_work``: the definition's 7 operations a state element
a token and layer, whatever form computes them, so the chunk form's triangular inverse
is credited nothing; a layer's state read and written once a lane, layer and call, and
a token's q, k, v, alpha and beta in and o out) over the device seconds under
``extend.delta.scan``: a lower bound of work over an upper bound of time, as
``minicpm_sala.linear_roofline`` argues. The counts are ``counters.traced``'s, **not
scaled**. A program that keeps no such record, or has no such layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "qwen3-next-80b-a3b-serve-ep4.json",
)
SCOPE = "extend.delta.scan"


def read(run):
    from benchmark import yardstick
    from benchmark.models import qwen3_next

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not counted.get("delta_tokens"):
        return None
    with open(CONFIG) as f:
        work = qwen3_next.delta_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
