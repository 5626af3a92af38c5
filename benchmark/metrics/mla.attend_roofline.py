"""The latent attention's attend as a share of its roofline in the traced
sub-window: the time the chip needs at its peaks for what the attend had to do
(``models/kimi_k2.py`` ``latent_work``: for every live causal query-key pair
and query head 2 operations a feature of the row scored and of the latent
summed, 2 x 64 x (576 + 512) a pair in the absorbed form and 2 x 64 x (192 +
128) in the expanded; the rows of the live slots, read once a call and layer;
projections, activations and writes not counted: **lower bounds**) over the
device seconds under ``extend.attention`` (which holds the attend, and the
cache update and ``W_o`` beside it: more seconds than the counted work took,
never fewer).

The engine's counters cover the whole load and the trace a second or two of
it, and the harness keeps no counter per sub-window, so the counts are scaled
by the share of the load's time inside engine steps that the traced steps took
(``trace.engine.in_step_s`` / ``counters.phase_s.step``), as
``moe.experts_roofline`` and ``sparse_attention.roofline`` scale their own: an
**estimate**, which assumes the traced seconds carry the load's own mix of
calls (PERF.md, section 7, S7b (4))."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "kimi-k2-instruct-serve-ep32.json",
)
SCOPE = "extend.attention"


def read(run):
    from benchmark import yardstick
    from benchmark.models import kimi_k2

    trace, counters = run.get("trace") or {}, run.get("counters") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    in_steps = (counters.get("phase_s") or {}).get("step")
    if not seconds or not in_steps or not (
            counters.get("mla_pairs_absorbed") or counters.get("mla_pairs_expanded")):
        return None
    with open(CONFIG) as f:
        work = kimi_k2.latent_work(json.load(f), counters)
    traced = trace["engine"]["in_step_s"] / in_steps
    return yardstick.roofline_share(
        traced * work["flops"], traced * work["bytes"], seconds, run["device"]["kind"])
