"""Release-test runner: execute release.yaml workloads, judge vs floors.

Reference: the release automation around release/release_tests.yaml —
every workload is a named script with a timeout and declared pass
criteria; the runner executes them, collects metrics, and emits a single
pass/fail verdict (plus a JSON artifact for the round records).

Usage:
  python scripts/release_runner.py --tier smoke
  python scripts/release_runner.py --tier full --artifact RELEASE_r05.json
  python scripts/release_runner.py --only shuffle_memory_ceiling
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_workload(name: str, spec: dict) -> dict:
    script = os.path.join(REPO, spec["script"])
    argv = [sys.executable, script, *spec.get("args", [])]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # host workloads: none may claim a chip
    env.update({k: str(v) for k, v in (spec.get("env") or {}).items()})
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            argv,
            env=env,
            capture_output=True,
            text=True,
            timeout=spec.get("timeout_s", 600),
            cwd=REPO,
        )
        out = proc.stdout
        err_tail = "\n".join((proc.stderr or "").splitlines()[-12:])
        rc = proc.returncode
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err_tail = ""
        rc = -1
    duration = time.perf_counter() - t0

    metrics: dict = {}
    for line in out.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "metric" in rec and "value" in rec:
            metrics[rec["metric"]] = rec["value"]
            if rec.get("vs_baseline") is not None:
                metrics.setdefault("vs_baseline", rec["vs_baseline"])

    failures = []
    if rc != 0:
        failures.append(f"exit code {rc}" if rc != -1 else "TIMEOUT")
        if err_tail:
            failures.append(f"stderr tail:\n{err_tail}")
    for metric, bounds in (spec.get("criteria") or {}).items():
        value = metrics.get(metric)
        if value is None:
            failures.append(f"{metric}: MISSING")
            continue
        if "min" in bounds and value < bounds["min"]:
            failures.append(f"{metric}: {value} < floor {bounds['min']}")
        if "max" in bounds and value > bounds["max"]:
            failures.append(f"{metric}: {value} > ceiling {bounds['max']}")
    return {
        "name": name,
        "passed": not failures,
        "failures": failures,
        "metrics": metrics,
        "duration_s": round(duration, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="smoke")
    ap.add_argument("--only", default=None, help="run a single workload")
    ap.add_argument("--artifact", default=None)
    args = ap.parse_args()

    with open(os.path.join(REPO, "release.yaml")) as f:
        cfg = yaml.safe_load(f)
    if args.only:
        names = [args.only]
    else:
        names = cfg["tiers"].get(args.tier)
        if names is None:
            sys.exit(f"unknown tier {args.tier!r}; have {list(cfg['tiers'])}")

    results = []
    for name in names:
        spec = cfg["workloads"][name]
        print(f"=== {name} ({spec['script']}) ...", flush=True)
        res = run_workload(name, spec)
        status = "PASS" if res["passed"] else "FAIL"
        print(f"=== {name}: {status} in {res['duration_s']}s")
        for metric, value in res["metrics"].items():
            print(f"      {metric} = {value}")
        for failure in res["failures"]:
            print(f"   !! {failure}")
        results.append(res)

    passed = sum(r["passed"] for r in results)
    print(f"\n{passed}/{len(results)} workloads passed")
    if args.artifact:
        with open(os.path.join(REPO, args.artifact), "w") as f:
            json.dump(
                {"tier": args.tier, "results": results, "ts": time.time()},
                f,
                indent=2,
            )
    sys.exit(0 if passed == len(results) else 1)


if __name__ == "__main__":
    main()
