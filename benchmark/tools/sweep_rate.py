"""Find the knee of a serve cell once, on the chip: the highest rate of a
geometric ladder at which nothing is shed or fails and no more requests are in
flight at the window's end than at its middle.

    python3 benchmark/tools/sweep_rate.py --workload gptj-serve-chat-steady \
        --rates 0.15,0.21,0.3,0.42,0.6 --seconds 40

One replica serves the whole ladder (set-up is paid once); every rung offers
the cell's own mix at its rate for ``--seconds``, then drains. The builder
writes the knee and 0.8 of it into the traffic file by hand, with this table
in PERF.md. No check runs this: a benchmark run never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import chip, manifest, samples, yardstick  # noqa: E402
from benchmark.traffic import serve_open_loop  # noqa: E402


def in_flight(records, t: float) -> int:
    return sum(1 for r in records if r["sent"] <= t and r.get("done", float("inf")) > t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s, rising")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    book = manifest.Manifest(ROOT)
    cell = book.cell(args.workload)
    readers = {n: book.reader(n) for n in ("ttft_p95_s", "tpot_p95_s")}
    rows = []
    with chip.cluster(cell.chips):
        handle, _, _, problems, params = serve_open_loop.deploy(cell, args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            requests = serve_open_loop.schedule(
                {**params, "rate_rps": rate, "lead_in_requests": 0, "lead_out_requests": 0}, args.seed, args.seconds
            )
            stats0 = handle.kv_stats.remote().result(timeout=60.0)
            start = chip.now() + 0.2
            serve_open_loop.offer(
                handle, requests, start, start + args.seconds + params["drain_limit_s"]
            )
            drained_s = chip.now() - start - args.seconds
            stats1 = handle.kv_stats.remote().result(timeout=120.0)
            problems += serve_open_loop.check_completions(requests, params["vocab_size"])
            run = {"kind": "serve", "window_s": args.seconds, "records": requests,
                   "drain_limit_s": params["drain_limit_s"]}
            row = {
                "rate_rps": rate, "sent": len(requests),
                "failed": sum(1 for r in requests if not r["ok"]),
                "shed": sum(1 for r in requests if r.get("shed")),
                "in_flight_mid": in_flight(requests, args.seconds / 2),
                "in_flight_end": in_flight(requests, args.seconds),
                "drained_s": drained_s,
                "steps": stats1["steps"] - stats0["steps"],
                "decode_tokens": stats1["decode_tokens"] - stats0["decode_tokens"],
                "offered_tokens_per_s": sum(r["n_out"] for r in requests) / args.seconds,
                "late_max_ms": 1e3 * max(r["sent"] - r["due"] for r in requests),
                **{n: read(run) for n, read in readers.items()},
                "ttft_median_s": yardstick.median(samples.ttft_from_due(run)),
            }
            row["sustained"] = (
                row["failed"] == 0 and row["in_flight_end"] <= row["in_flight_mid"]
            )
            rows.append(row)
            chip.say("rung " + json.dumps(row))
    for p in problems:
        chip.say(f"NOT CORRECT: {p}")
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rps": max(sustained) if sustained else None, "rungs": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
