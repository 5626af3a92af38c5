"""95th percentile, over the requests due in the window, of the mean gap
between a request's tokens as its caller sees it (``samples.token_gaps``)."""

from benchmark import samples
from benchmark.yardstick import percentile


def read(run):
    v = samples.token_gaps(run) if samples.serve_records(run) else []
    return percentile(v, 0.95) if v else None
