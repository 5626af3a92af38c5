"""How late the generator sent: 95th percentile of (sent - due), so that a
starved generator is not read as a fast server."""

from benchmark.yardstick import percentile


def read(run):
    if run.get("kind") != "serve" or not run.get("records"):
        return None
    return 1e3 * percentile([r["sent"] - r["due"] for r in run["records"]], 0.95)
