"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are files found by the names
in ``BENCHMARK.json`` (see ``manifest.py``); the traffic's generator runs the
system under test on the chip and hands back what it observed; each metric's
reader takes its number from that; ``contract.py`` builds the last line and
refuses to print one that the driver could not read. Without the chips the
cell asks for there is no result line and the exit code is not 0.
"""

from __future__ import annotations

import time

_STARTED = time.time()        # as near to the process's start as python gets

import argparse
import json
import os
import sys
import traceback
from typing import Any, Dict, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import chip, contract, manifest  # noqa: E402


SAID_GROUPS = 24      # kernels and scopes a traced run prints; readers get every one


def run_cell(
    root: str, workload: str, seed: int, seconds: float, traced: bool,
    started: Optional[float] = None,
) -> Tuple[Dict[str, Any], manifest.Cell, Dict[str, Any]]:
    """Run the cell; return its last line (unchecked), the cell, and what the
    generator observed."""
    started = time.time() if started is None else started
    book = manifest.Manifest(root)
    cell = book.cell(workload)
    run = book.generator(cell).run(cell, seed, seconds, traced, started)
    values: Dict[str, Optional[float]] = {
        m["name"]: book.reader(m["name"])(run)
        for group in (cell.end_to_end, cell.per_layer) for m in group
    }
    chip.say(
        "metrics of this run (the last line holds those of this --trace value): "
        + json.dumps({k: v for k, v in values.items() if v is not None})
    )
    if run["kind"] == "serve" and run["records"]:
        _say_samples(run)
    trace = run.get("trace") if traced else None
    device = dict(run["device"])
    breakdown = None
    if trace and "busy_s" in trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        breakdown = {k: trace[k] for k in contract.BREAKDOWN_KEYS}
        for key in ("ops_by_kernel", "ops_by_scope"):
            chip.say(
                f"{key}, device seconds of the traced sub-window's {trace['window_s']:.3f} "
                f"({len(trace[key])} in all, the largest {SAID_GROUPS}): "
                + json.dumps(trace[key][:SAID_GROUPS])
            )
    line = contract.build(
        correct=run["correct"], attempted=run["attempted"], failed=run["failed"],
        values=values, wanted=cell.metrics(traced), device=device, breakdown=breakdown,
    )
    return line, cell, run


def _say_samples(run: Dict[str, Any]) -> None:
    """The sample behind each serving statistic: its size, median and maximum."""
    from benchmark import samples
    from benchmark.yardstick import median

    for name, take in (
        ("time to first token from due", samples.ttft_from_due),
        ("mean gap between tokens", samples.token_gaps),
        ("due to last token", samples.latencies),
    ):
        v = take(run)
        chip.say(f"{name}: {len(v)} samples, median {median(v):.4f} s, max {max(v):.4f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    chip.say(
        f"cell {args.workload}, seed {args.seed}, window {args.seconds}s, trace "
        f"{args.trace}; python {sys.version.split()[0]}; checkout {ROOT}"
    )
    traced = bool(args.trace)
    try:
        line, cell, _ = run_cell(
            ROOT, args.workload, args.seed, args.seconds, traced, _STARTED
        )
        contract.emit(line, cell.metrics(traced), traced, cell.chips)
    except (chip.NoChip, manifest.ManifestError) as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    except contract.ContractViolation as e:
        for p in e.problems:
            chip.say(f"the last line would break the contract: {p}")
        print("[bench] no result: see the lines above", file=sys.stderr, flush=True)
        return 3
    except Exception:  # noqa: BLE001 — a run that broke prints no result line
        traceback.print_exc()
        print("[bench] no result: the run failed", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
