"""95th percentile, over the requests due in the window that completed, of the
time from the replica's enqueue to admission with its blocks: ``queue_s``, which
the engine stamps on every result (``ttft_s - queue_s`` is the prefill the
request itself needed). A failed request has none and is left out; no record
with one: nothing."""

from benchmark import samples
from benchmark.yardstick import percentile


def read(run):
    if not samples.serve_records(run):
        return None
    waits = [r["queue_s"] for r in run["records"] if r.get("ok") and r.get("queue_s") is not None]
    return percentile(waits, 0.95) if waits else None
