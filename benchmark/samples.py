"""The per-request samples the serving metrics are statistics of, taken from a
serve run's client records (``due``, ``sent``, ``done`` in seconds from the
window's start; ``ttft_s`` the replica's own clock from its enqueue to the
first token). A request that failed, was shed or did not finish counts as the
drain's limit in each."""

from __future__ import annotations

from typing import Any, Dict, List


def ttft_from_due(run: Dict[str, Any]) -> List[float]:
    """Time to first token from when the request was due: how late it was
    sent plus the replica's ``ttft_s`` (the handle's transit is in neither)."""
    return [
        (r["sent"] - r["due"]) + r["ttft_s"] if r["ok"] else run["drain_limit_s"]
        for r in run["records"]
    ]


def token_gaps(run: Dict[str, Any]) -> List[float]:
    """Mean gap between a request's tokens as its caller sees it: (done - due -
    time to first token) / (output tokens - 1); one token has no gap."""
    out = []
    for r in run["records"]:
        if not r["ok"]:
            out.append(run["drain_limit_s"])
        elif r["n_out"] > 1:
            out.append((r["done"] - r["sent"] - r["ttft_s"]) / (r["n_out"] - 1))
    return out


def latencies(run: Dict[str, Any]) -> List[float]:
    """From when the request was due to its last token."""
    return [
        r["done"] - r["due"] if r["ok"] else run["drain_limit_s"] for r in run["records"]
    ]


def serve_records(run: Dict[str, Any]) -> bool:
    return run.get("kind") == "serve" and bool(run.get("records"))
