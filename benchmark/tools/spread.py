"""Measure a cell's run-to-run spread the way the benchmark's bounds are set:
sets of runs with the same seeds in each set, one new process per run, and for
each metric the quartile spread (third less first quartile of
``statistics.quantiles(values, n=4)``, as a share of the median) of each set.

    python3 benchmark/tools/spread.py --workload <cell> --sets 2 --runs 6 \
        [--seconds <run_seconds>] [--trace 0] [--out chiprun_out/<cell>.jsonl]

Every run's own line of all its metrics ("metrics of this run") and its other
earlier lines are kept, not only the last line, so one batch of untraced runs shows the spread of the
per-layer metrics that need no trace as well. The first run of the first set
is the one that may compile; it is reported and left out of ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = [2147483659, 1103515245, 3141592653, 2718281828, 1618033988, 4242424242]


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    m = statistics.median(values)
    return (q3 - q1) / m if m else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        book = json.load(f)
    seconds = args.seconds or book["run_seconds"]
    out = args.out or os.path.join(ROOT, "chiprun_out", args.workload + ".jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets = []
    with open(out, "a") as log:
        for s in range(args.sets):
            rows = []
            for seed in SEEDS[:args.runs]:
                t0 = time.time()
                proc = subprocess.run(
                    book["command"] + ["--workload", args.workload, "--seed", str(seed),
                                       "--seconds", str(seconds), "--trace", str(args.trace)],
                    cwd=ROOT, capture_output=True, text=True,
                )
                lines = proc.stdout.strip().splitlines()
                every = next(
                    (json.loads(x.split(": ", 1)[1]) for x in lines
                     if x.startswith("[bench] metrics of this run")), {},
                )
                last = None
                if proc.returncode == 0 and lines:
                    last = json.loads(lines[-1])
                row = {"set": s, "seed": seed, "rc": proc.returncode,
                       "wall_s": time.time() - t0, "metrics": every, "last": last,
                       "said": [x for x in lines if x.startswith("[bench]")]}
                if proc.returncode != 0:
                    row["tail"] = (proc.stdout[-1500:] + proc.stderr[-1500:])
                log.write(json.dumps(row) + "\n")
                log.flush()
                print(json.dumps({k: row[k] for k in ("set", "seed", "rc", "wall_s")}
                                 | {"correct": last and last["correct"],
                                    "failed": last and last["failed"]} | every), flush=True)
                rows.append(row)
            sets.append(rows)
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    print(f"{'metric':34s} " + " ".join(f"median{s} spread{s}" for s in range(len(sets))))
    for name in names:
        cells = []
        for s, rows in enumerate(sets):
            # the first run of all may compile: its set-up is recorded apart
            skip = 1 if (name == "setup_s" and s == 0) else 0
            v = [r["metrics"][name] for r in rows[skip:] if name in r["metrics"]]
            sp = spread(v)
            cells.append(
                f"{statistics.median(v):.6g} {'-' if sp is None else f'{100 * sp:.2f}%'}"
                if v else "- -"
            )
        print(f"{name:34s} " + "   ".join(cells))
    return 0


if __name__ == "__main__":
    sys.exit(main())
