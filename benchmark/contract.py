"""The last line of a run, built and checked in one place.

The driver reads one JSON object from the last line of standard output. This
module holds what that object must be as code: :func:`build` makes it from a
run's numbers, :func:`violations` says everything that is wrong with it, and
:func:`emit` prints it only when nothing is. A run whose line would be
malformed says why on an earlier line and exits non-zero with no object
printed at all.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Any, Dict, List, Optional

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_ROWS = 10


class ContractViolation(Exception):
    def __init__(self, problems: List[str]):
        super().__init__("; ".join(problems))
        self.problems = problems


def _is_number(x: Any) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def build(
    *, correct: bool, attempted: int, failed: int, values: Dict[str, Optional[float]],
    wanted: List[Dict[str, Any]], device: Dict[str, Any],
    breakdown: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """``values`` are the readers' results by metric name; a metric whose
    reader found nothing (None) is left out, which :func:`violations` then
    reports for a metric the manifest lists for this cell."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if values.get(m["name"]) is not None
        },
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = {
            k: [list(row) for row in breakdown.get(k, [])][:BREAKDOWN_ROWS]
            for k in BREAKDOWN_KEYS
        }
    return line


def violations(
    line: Any, wanted: List[Dict[str, Any]], traced: bool, chips: Optional[int] = None
) -> List[str]:
    """Every way ``line`` falls short of the contract for a cell whose
    manifest lists ``wanted`` for this ``--trace`` value; empty when sound."""
    if not isinstance(line, dict):
        return [f"the line is a {type(line).__name__}, not an object"]
    bad = [f"key {k!r} is missing" for k in LINE_KEYS if k not in line]
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) or line[k] < 0:
            bad.append(f"{k!r} is not a count: {line[k]!r}")
    if not bad and line["failed"] > line["attempted"]:
        bad.append(f"failed {line['failed']} > attempted {line['attempted']}")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        bad.append("'metrics' is not an object")
        metrics = {}
    for m in wanted:
        got = metrics.get(m["name"])
        if got is None:
            bad.append(f"metric {m['name']!r} is missing")
        elif not isinstance(got, dict) or not _is_number(got.get("value")):
            bad.append(f"metric {m['name']!r} has no finite value: {got!r}")
        elif got.get("unit") != m["unit"]:
            bad.append(
                f"metric {m['name']!r} has unit {got.get('unit')!r}, the manifest "
                f"says {m['unit']!r}"
            )
    extra = sorted(set(metrics) - {m["name"] for m in wanted})
    if extra:
        bad.append(f"metrics the manifest does not list for this run: {extra}")
    device = line["device"]
    if not isinstance(device, dict):
        return bad + ["'device' is not an object"]
    for k in DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ()):
        if k not in device or device[k] is None:
            bad.append(f"device.{k} is missing")
    for k in ("platform", "kind"):
        if k in device and not (isinstance(device[k], str) and device[k]):
            bad.append(f"device.{k} is not a name: {device[k]!r}")
    if isinstance(device.get("count"), int) and chips and device["count"] != chips:
        bad.append(f"device.count is {device['count']}, the cell asks for {chips}")
    peak = device.get("memory_peak_bytes")
    if peak is not None and not (_is_number(peak) and peak > 0):
        bad.append(f"device.memory_peak_bytes is not above 0: {peak!r}")
    if traced and all(device.get(k) is not None for k in TRACED_DEVICE_KEYS):
        busy, window = device["busy_s"], device["window_s"]
        if not (_is_number(busy) and _is_number(window)):
            bad.append(f"device.busy_s / window_s are not numbers: {busy!r} / {window!r}")
        elif not 0 < busy <= window:
            bad.append(
                f"device.busy_s {busy} is not above 0 and at most window_s {window}: "
                f"no operation was traced on the device, or the window is wrong"
            )
    if "breakdown" in line:
        b = line["breakdown"]
        if not isinstance(b, dict):
            bad.append("'breakdown' is not an object")
        else:
            for k in BREAKDOWN_KEYS:
                rows = b.get(k)
                if not isinstance(rows, list) or len(rows) > BREAKDOWN_ROWS or not all(
                    isinstance(r, list) and len(r) == 2 and isinstance(r[0], str)
                    and _is_number(r[1]) for r in rows
                ):
                    bad.append(f"breakdown.{k} is not at most {BREAKDOWN_ROWS} [name, seconds] rows")
    try:
        if "\n" in json.dumps(line, allow_nan=False):
            bad.append("the line does not fit on one line")
    except ValueError as e:
        bad.append(f"the line is not JSON: {e}")
    return bad


def emit(
    line: Dict[str, Any], wanted: List[Dict[str, Any]], traced: bool,
    chips: Optional[int] = None, out=None,
) -> None:
    """Print ``line`` as the last line, or raise with no object printed."""
    problems = violations(line, wanted, traced, chips)
    if problems:
        raise ContractViolation(problems)
    out = out or sys.stdout
    out.write(json.dumps(line, allow_nan=False) + "\n")
    out.flush()
