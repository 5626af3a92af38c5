"""MiMo-V2-Flash's two kinds of attend as a share of their roofline in the traced
sub-window: the time the chip needs at its peaks for what they had to do
(``models/mimo_v2_flash.py`` ``attend_work``: 2 x (192 + 128) operations a query head
and query-key pair inside the mask, from ``full_keys`` and ``window_keys``; the K and V
rows read once a call, layer and K/V head: a full layer's live slots, a decode lane's
window, a chunk's own rows; projections, rotation, writes and activations not counted:
**lower bounds**) over the device seconds under ``extend.attention`` +
``extend.attention.window`` (which hold the attends, and the projections, the
rotation, the cache's and the slots' updates beside them: more seconds than the
counted work took, never fewer: ``minicpm_sala.sparse_roofline`` argues the same).

The counts are ``counters.traced``'s, **not scaled**. A program that keeps no such
record or counts no such pairs: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "mimo-v2-flash-serve-ep16.json",
)
SCOPES = ("extend.attention", "extend.attention.window")


def read(run):
    from benchmark import yardstick
    from benchmark.models import mimo_v2_flash

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not all(scopes.get(s) for s in SCOPES) or not counted.get("window_keys"):
        return None
    with open(CONFIG) as f:
        work = mimo_v2_flash.attend_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], sum(scopes[s] for s in SCOPES), run["device"]["kind"])
