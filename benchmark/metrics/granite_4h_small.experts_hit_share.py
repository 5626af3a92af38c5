"""The share of granite-4.0-h-small's held experts' weights that a device call
reads, over the traced sub-window's calls: held experts with at least one token
(``moe_experts_hit``, summed over the expert layers and the calls) / (experts
held x expert layers x calls), in percent, from ``counters.traced``. A decode
call is bound by the weights it reads, and this is the part of them that moves
with its lanes: 1 - (1 - k / R)^lanes of the held experts in expectation. The
held experts and the layers are **this** configuration's file's, which is why
the reader carries the configuration's name and is no ``engine.`` reader: on
another cell it would divide by the wrong count (PERF.md, section 7, S7b (20)).
A program that keeps no such record, or has no expert layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "granite-4.0-h-small-serve-ep2.json",
)


def read(run):
    counted = (run.get("counters") or {}).get("traced") or {}
    calls = (counted.get("phase_n") or {}).get("dispatch")
    if not calls or not counted.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        keys = json.load(f)
    held = keys["num_local_experts"] * keys["num_hidden_layers"]
    return 100.0 * counted["moe_experts_hit"] / (held * calls)
