"""The share of a token's picks that fell on a zero-compute expert, over the whole load:
``moe_zero_assignments`` (token-expert pairs of real tokens whose expert has no weights,
summed over the layers and the calls) / (``moe_topk`` x ``moe_tokens``: all the pairs of
real tokens), in percent, from the engine's counters (deltas of ``kv_stats``). It says how
much of the routed work a token skipped: 256 of the router's 768 outputs are such experts,
so about a third at random weights, which is the published average (27 B active of 18.6
to 31.3). ``moe_topk`` is **this** configuration's file's, which is why the reader carries
the configuration's name. A program that counts no such pairs: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "longcat-flash-chat-serve-ep32.json",
)


def read(run):
    counted = run.get("counters") or {}
    if not counted.get("moe_tokens") or "moe_zero_assignments" not in counted:
        return None
    with open(CONFIG) as f:
        picks = json.load(f)["moe_topk"]
    return 100.0 * counted["moe_zero_assignments"] / (picks * counted["moe_tokens"])
