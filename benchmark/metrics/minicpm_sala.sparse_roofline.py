"""MiniCPM-SALA's selection and block-sparse attention as a share of their roofline
in the traced sub-window: the time the chip needs at its peaks for what they had to do
(``models/minicpm_sala.py`` ``sparse_work``: 2 operations a feature of a K/V head's
query heads for every visible compressed key scored, 4 for every key attended; the
compressed keys of the live slots and the K and V rows of the slots some query read,
once a call and layer; projections, the gate, activations and writes not counted:
**lower bounds**) over the device seconds under ``extend.attention.index`` +
``extend.attention.select`` + ``extend.attention`` (which holds the attend, and the
projections, norms, gate and cache update beside it: more seconds than the counted
work took, never fewer).

The counts are ``counters.traced``'s, **not scaled**. A program that keeps no such
record, or whose traced steps held no query past ``dense_len``: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "minicpm-sala-serve-pp2.json",
)
SCOPES = ("extend.attention.index", "extend.attention.select", "extend.attention")


def read(run):
    from benchmark import yardstick
    from benchmark.models import minicpm_sala

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not all(scopes.get(s) for s in SCOPES[:2]) or not counted.get("sparse_keys_causal"):
        return None
    with open(CONFIG) as f:
        work = minicpm_sala.sparse_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"],
        sum(scopes.get(s, 0.0) for s in SCOPES), run["device"]["kind"])
