"""Nemotron-3-Nano's chunked Mamba-2 recurrence, forward and backward, as a share of
its roofline in the traced steps: the time the chip needs at its peaks for what the
recurrences of a step require (``models/nemotron_h.py`` ``scan_work``: three passes of
the C.B pairs, the masked product and the state's read-out and feed at the published
``chunk_size``; ``x``, ``dt``, ``B``, ``C`` and ``y`` once a pass and the states between
sub-chunks written and read once; the remat's replay not counted; the same count
whatever implements the scan) times the traced steps, over the device seconds under
``train.ssm.scan``. A train step that names no such scope: nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "nemotron-3-nano-30b-a3b-train-ep8.json",
)
SCOPE = "train.ssm.scan"


def read(run):
    from benchmark import yardstick
    from benchmark.models import nemotron_h

    trace = run.get("trace") or {}
    seconds = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    if not seconds or not trace.get("units"):
        return None
    with open(CONFIG) as f:
        file = json.load(f)
    work = nemotron_h.scan_work(file, *file["job"]["batch"])
    return yardstick.roofline_share(
        trace["units"] * work["flops"], trace["units"] * work["bytes"], seconds,
        run["device"]["kind"])
