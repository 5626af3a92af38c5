"""The sorted rows the trained expert layers' backward walks over the pairs they
hold, over the window's steps: ``moe_rows_visited`` (each layer's blocks of sorted
rows that hold a pair, in rows, summed) / ``moe_assignments`` (the layers' pairs,
summed). 1 is a backward whose passes over sorted rows touch held pairs alone; a
step that reports no ``moe_rows_visited`` walks every pair of every token,
``moe_tokens`` x the configuration's ``num_experts_per_tok``. A train step that
reports no ``moe_assignments``: nothing."""

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
        choices = json.load(f)["num_experts_per_tok"]
    visited = sum(m.get("moe_rows_visited", m["moe_tokens"] * choices) for m in steps)
    return visited / sum(m["moe_assignments"] for m in steps)
