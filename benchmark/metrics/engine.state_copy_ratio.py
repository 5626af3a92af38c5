"""What the engine's state store costs beside what the recurrence must move:
the bytes of per-sequence state copied by anything but the model itself (into a
call's lanes, back into their slots, into a snapshot's slot, from one to a
sequence's: ``state_bytes_moved``) over the bytes the recurrence reads and
writes (``scan_work``'s ``state_bytes``: twice a layer's state a lane, Mamba
layer and call, ``ssm_state_passes``), over the run's whole load. 0 for a store
that works in place. A program without per-sequence state counts neither:
nothing."""

import json
import os

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs",
    "granite-4.0-h-micro-serve.json",
)


def read(run):
    from benchmark.models import granitemoehybrid

    c = run.get("counters") or {}
    if "state_bytes_moved" not in c or not c.get("ssm_state_passes"):
        return None
    with open(CONFIG) as f:
        must = granitemoehybrid.scan_work(json.load(f), c)["state_bytes"]
    return c["state_bytes_moved"] / must
