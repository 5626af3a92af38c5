"""The attend over the selected latent rows as a share of its roofline in the traced
sub-window of the GLM-5 configuration: the time the chip needs at its peaks for what
the attend had to do (``models/glm_moe_dsa.py`` ``attend_work``: for every pair
**attended**, at most 2,048 a query, 2 x 64 x (576 + 512) operations in the absorbed
form and 2 x 64 x (256 + 256) in the expanded, from ``mla_pairs_absorbed`` /
``mla_pairs_expanded``; in the expanded form ``W_kvb`` over every live slot, which
the selection does not spare, 2 x 512 x 64 x 448 a slot from ``mla_rows_expanded``;
the 1,280 B rows of the slots some query of the call selected, from
``sparse_slots_read``) over the device seconds under ``extend.attention`` less the
indexer's and the selection's: the attend, the cache update and ``W_o``
(``extend.attention`` itself) and the projections into and out of the latent's space
(``extend.attention.latent``). **A lower bound of work over an upper bound of time**,
as ``mla.attend_roofline`` argues: the pairs a chunk's kernel scores and masks away
are not work, so a later kernel that skips the tiles no query of a block selected
reads higher, and not over 100 %.

The counts are ``counters.traced``'s, **not scaled** from the whole load. A program
that keeps no such record, or has no such scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "glm-5-serve-ep16.json",
)
SCOPES = ("extend.attention", "extend.attention.latent")


def read(run):
    from benchmark import yardstick
    from benchmark.models import glm_moe_dsa

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not scopes.get(SCOPES[0]) or not counted.get("sparse_slots_read") or not (
            counted.get("mla_pairs_absorbed") or counted.get("mla_pairs_expanded")):
        return None
    with open(CONFIG) as f:
        work = glm_moe_dsa.attend_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], sum(scopes.get(s, 0.0) for s in SCOPES),
        run["device"]["kind"])
