"""The indexer's share of its roofline in the traced sub-window of the GLM-5
configuration: the time the chip needs at its peaks for what the indexer had to do
(``models/glm_moe_dsa.py`` ``index_work``: 2 x 32 x 128 operations a live causal
query-key pair scored, from ``sparse_keys_scored``; the 256 B indexer key of every
live slot, read once a call and layer, from ``cache_tokens``; its three projections,
the norm, the rotation, the weighting and the selection itself not counted: **lower
bounds**) over the device seconds under ``extend.attention.index`` +
``extend.attention.select`` (the scores, and the top-k or the bisection and the row
gather or the mask beside them: more seconds than the counted work took, never fewer).

The counts are ``counters.traced``'s: what ``extend`` counted and the live slots the
engine gathered in exactly the engine steps the profiler session recorded, **not
scaled** from the whole load. A program that keeps no such record, or has neither
scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "glm-5-serve-ep16.json",
)
SCOPES = ("extend.attention.index", "extend.attention.select")


def read(run):
    from benchmark import yardstick
    from benchmark.models import glm_moe_dsa

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not all(scopes.get(s) for s in SCOPES) or not counted.get("sparse_keys_scored"):
        return None
    with open(CONFIG) as f:
        work = glm_moe_dsa.index_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], sum(scopes[s] for s in SCOPES), run["device"]["kind"])
