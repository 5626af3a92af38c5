"""95th percentile, over the requests due in the window, of the time to the
first token counted from when the request was due (``samples.ttft_from_due``).
With ten requests a window it is their maximum."""

from benchmark import samples
from benchmark.yardstick import percentile


def read(run):
    return percentile(samples.ttft_from_due(run), 0.95) if samples.serve_records(run) else None
