"""granite-4.0-h-small's grouped matmuls over the routed experts held here as a
share of their roofline in the traced sub-window: the time the chip needs at its
peaks for what they had to do (``models/granitemoehybrid_moe.py``
``experts_work``: 2 operations a parameter of an expert per token-expert pair
computed here; the weights of every held expert that had a token, read once per
call and layer; activations not counted: **lower bounds**) over the device
seconds of the kernel ``gmm``, **by the kernel's name** (``ops_by_kernel``): both
grouped matmuls of every expert layer, and nothing else runs under that name in
this program.

Not the seconds under the scope ``extend.moe.experts``: a fusion takes its
root's scope, and the compiler filed the tied head's 0.06 s fusion under that
scope in one of two runs of the same files, which moved the share from 77 to
54 % (PERF.md, PR 47). The sort, the gather of the pairs' rows, the un-sort and
the combine round the kernel are XLA fusions with no name of their own: they are
left out here and stand in ``extend.moe_share``. The shared MLP is left out on
both sides: its weights reach VMEM by the scan's prefetch, under no scope.

The counts are ``counters.traced``'s: what ``extend`` counted in exactly the
engine steps the profiler session recorded, **not scaled** from the whole load
(as ``ssm.scan_roofline`` reads its own; not the whole-load estimate of
``moe.experts_roofline``). A program that keeps no such record, or has no
expert layer: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "granite-4.0-h-small-serve-ep2.json",
)
KERNEL = "gmm"


def read(run):
    from benchmark import yardstick
    from benchmark.models import granitemoehybrid_moe

    trace = run.get("trace") or {}
    counted = (run.get("counters") or {}).get("traced") or {}
    seconds = dict(map(tuple, trace.get("ops_by_kernel") or [])).get(KERNEL)
    calls = (counted.get("phase_n") or {}).get("dispatch")
    if not seconds or not calls or not counted.get("moe_tokens"):
        return None
    with open(CONFIG) as f:
        work = granitemoehybrid_moe.experts_work(json.load(f), counted)
    return yardstick.roofline_share(
        work["flops"], work["bytes"], seconds, run["device"]["kind"])
