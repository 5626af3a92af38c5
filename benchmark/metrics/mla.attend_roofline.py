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

The counts are ``counters.traced``'s: the pairs ``extend`` counted and the live
slots the engine gathered (``cache_tokens``) in exactly the engine steps the
profiler session recorded, **not scaled** from the whole load: a program that
attends a chunk in another form changes the recorded calls' pairs and seconds
together, and the load's mix of calls does not enter. A program that keeps no
such record: nothing."""

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

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not (
            counted.get("mla_pairs_absorbed") or counted.get("mla_pairs_expanded")):
        return None
    with open(CONFIG) as f:
        work = kimi_k2.latent_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
