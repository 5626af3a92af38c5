"""The indexer's and the sparse attention's share of their roofline in the
traced sub-window: the time the chip needs at its peaks for what they had to do
(``models/keye_vl2.py`` ``sparse_work``: 2 operations a feature of an indexer
head per live causal query-key pair scored, 4 a feature of a query head per key
attended; the indexer keys of the live slots and the K and V rows of the slots
some query selected, read once per call and layer; projections, activations
and writes not counted: **lower bounds**) over the device seconds under
``extend.attention.index`` + ``extend.attention.select`` + ``extend.attention``
(which holds the attend, and the projections, norms, rotary and cache update
beside it: more seconds than the counted work took, never fewer).

The counts are ``counters.traced``'s: the pairs and slots ``extend`` counted and
the live slots the engine gathered (``cache_tokens``) in exactly the engine
steps the profiler session recorded, **not scaled** from the whole load. A
program that keeps no such record: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "keye-vl2-30b-a3b-serve.json",
)
SCOPES = ("extend.attention.index", "extend.attention.select", "extend.attention")


def read(run):
    from benchmark import yardstick
    from benchmark.models import keye_vl2

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not all(scopes.get(s) for s in SCOPES[:2]) or not counted.get("sparse_keys_scored"):
        return None
    with open(CONFIG) as f:
        work = keye_vl2.sparse_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"],
        sum(scopes.get(s, 0.0) for s in SCOPES), run["device"]["kind"])
