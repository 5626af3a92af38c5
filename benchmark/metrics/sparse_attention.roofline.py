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

The engine's counters cover the whole load and the trace a second or two of
it, and the harness keeps no counter per sub-window (``benchmark/server.py``
``_probe`` holds ``steps`` and ``decode_tokens`` alone), so the counts are
scaled by the share of the load's time inside engine steps that the traced
steps took (``trace.engine.in_step_s`` / ``counters.phase_s.step``), as
``moe.experts_roofline`` scales its own: an **estimate**, which assumes the
traced seconds carry the load's own mix of calls. The share is far enough from
100 that a misestimate of half again does not reach it (PERF.md, section 5)."""

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

    trace, counters = run.get("trace") or {}, run.get("counters") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    in_steps = (counters.get("phase_s") or {}).get("step")
    if not all(scopes.get(s) for s in SCOPES[:2]) or not in_steps or not counters.get(
            "sparse_keys_scored"):
        return None
    with open(CONFIG) as f:
        work = keye_vl2.sparse_work(json.load(f), counters)
    traced = trace["engine"]["in_step_s"] / in_steps
    return yardstick.roofline_share(
        traced * work["flops"], traced * work["bytes"],
        sum(scopes.get(s, 0.0) for s in SCOPES), run["device"]["kind"])
