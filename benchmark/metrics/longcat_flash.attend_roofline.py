"""The attend over the cached latent rows as a share of its roofline in the traced
sub-window of the LongCat-Flash-Chat configuration, both forms and both sub-blocks of
every layer: the time the chip needs at its peaks for what the attend had to do
(``models/longcat_flash.py`` ``latent_work``: for every live causal pair and head 2 x 64
x (576 + 512) operations in the absorbed form and 2 x 64 x (192 + 128) in the expanded,
from ``mla_pairs_absorbed`` / ``mla_pairs_expanded``; in the expanded form ``W_kvb`` over
every live slot, 2 x 512 x 64 x 256 a slot from ``mla_rows_expanded``; the 1,280 B rows of
the live slots, read once a call and sub-block, from ``cache_tokens``) over the device
seconds of **everything that implements it today**: ``extend.attention`` (the attend, the
cache update and ``W_o``), ``extend.attention.latent`` (the projections into and out of
the latent's space) and ``paging.gather`` (the program that hands a chunk its padded
caches: a decode call reads the pool's pages where they lie and runs none).

**The work required, whatever implements it, over an upper bound of its time.**
``mla.attend_roofline`` left the gather's seconds out, and fell (10.93 -> 10.31, PERF.md
section 7) when PR 66 took the decode calls' gather away and put a kernel under the
scope: less was done and the share read lower. Here a change that stops gathering, or
that writes a chunk's rows without copying a slab, shortens the seconds it is read over
and reads higher; the work counted does not move with it.

The counts are ``counters.traced``'s, **not scaled** from the whole load. A program that
keeps no such record, or has no such scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "longcat-flash-chat-serve-ep32.json",
)
SCOPES = ("extend.attention", "extend.attention.latent", "paging.gather")


def read(run):
    from benchmark import yardstick
    from benchmark.models import longcat_flash

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    scopes = dict(map(tuple, trace.get("ops_by_scope") or []))
    if not scopes.get(SCOPES[0]) or "moe_zero_assignments" not in counted or not (
            counted.get("mla_pairs_absorbed") or counted.get("mla_pairs_expanded")):
        return None
    with open(CONFIG) as f:
        work = longcat_flash.latent_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], sum(scopes.get(s, 0.0) for s in SCOPES),
        run["device"]["kind"])
