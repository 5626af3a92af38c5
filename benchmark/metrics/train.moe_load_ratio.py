"""The busiest held expert's pairs over the mean held expert's, over the
window's steps: ``moe_load_max`` (the layers' maxima, summed) x the held
experts / ``moe_assignments`` (the layers' pairs, summed). 1 is an even load; it
is what the bias's spread sets and what a layer that drops nothing has to
absorb. A train step that reports no such counters: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "lfm2-24b-a2b-train-ep2.json",
)


def read(run):
    steps = [m for m in run.get("step_metrics") or [] if m.get("moe_assignments")]
    if not steps:
        return None
    with open(CONFIG) as f:
        held = json.load(f)["num_experts"]
    return held * sum(m["moe_load_max"] for m in steps) / sum(m["moe_assignments"] for m in steps)
