"""Mean, over the requests due in the window, of the time from when a request
was due to its last token: what a caller waits for a whole chat turn
(``samples.latencies``; a failed request counts as the drain's limit)."""

from benchmark import samples


def read(run):
    if not samples.serve_records(run):
        return None
    v = samples.latencies(run)
    return sum(v) / len(v)
