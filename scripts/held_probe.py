"""What held the host, run by run: the benchmark's own command for each ``cell:seed``
asked for, one after another (this process stays off jax: each run is the
benchmark's process and leaves the chip to the next), its whole output kept under
``chiprun_out/held/`` with the ``host held: ...`` lines that the run's replica or
train worker logged (``accelerator.HostWatch``: cause, phase and stack of every
step that stood still), and one line a run in ``summary.jsonl``: the result line's
metrics beside the engine's ``held``, ``host`` and ``gc`` over lead-in, window
and drain.

    python3 scripts/held_probe.py --tag change --runs qwen3-next-serve-concurrent-turns:2147483700 ...
    python3 scripts/held_probe.py --tag parent --root _archive/parent --runs ...

``--root``: another checkout to run (the parent's, unpacked under a directory
that ``.gitignore`` lists); ``--trace 1`` for traced runs; ``--summary`` reads
``summary.jsonl`` back as the table of ``PERF.md`` section 6 (``--reparse``: from the
kept outputs, anew)."""

import argparse
import glob
import json
import os
import re
import statistics
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "held")
ENGINE_LINE = re.compile(r"\[bench\] engine over lead-in, window, lead-out and drain: (\{.*?\}); kv blocks")


def held_lines_of(stdout: str) -> list:
    """The ``host held`` lines in the logs of the cluster a run started: the run says
    where it keeps them ("[bench] cluster up: ..., session <dir>")."""
    lines = []
    for session in set(re.findall(r"cluster up: .*session (\S+)", stdout)):
        for log in glob.glob(os.path.join(session, "logs", "**"), recursive=True):
            if os.path.isfile(log):
                with open(log, errors="replace") as f:
                    lines += [f"{os.path.basename(log)}: {line}" for line in f if "host held" in line]
    return lines


def summary_of(name: str, stdout: str, rc: int, held_lines: int, took_s: float) -> dict:
    tag, cell, seed, trace = name.split(".")
    summary = {"tag": tag, "cell": cell, "seed": int(seed), "trace": int(trace[1:]), "rc": rc,
               "took_s": took_s, "held_lines": held_lines}
    lines = stdout.strip().splitlines()
    if rc == 0 and lines:
        result = json.loads(lines[-1])
        summary["correct"], summary["failed"] = result.get("correct"), result.get("failed")
        summary["metrics"] = {k: v["value"] for k, v in result.get("metrics", {}).items()}
    found = ENGINE_LINE.search(stdout)
    if found:
        counters = json.loads(found.group(1))
        summary["engine"] = {
            k: counters.get(k) for k in ("steps", "held", "host", "gc") if k in counters}
        summary["engine"]["phase_s"] = {
            k: counters["phase_s"][k] for k in ("step", "fetch") if k in counters.get("phase_s", {})}
    return summary


def ticks(until: threading.Event, late: list) -> None:
    """This process does nothing while the run lasts: a thread of it that sleeps 5 ms
    at a time and notes every wake-up that comes 20 ms late or later, by the wall
    clock the replica's log lines carry, is a second witness in another process. A
    hold both saw is the machine's, not the replica's."""
    before = time.perf_counter()
    while not until.wait(0.005):
        now = time.perf_counter()
        if now - before > 0.025:
            late.append(f"{time.strftime('%H:%M:%S', time.localtime())},{int(time.time() % 1 * 1e3):03d} "
                        f"the probe's own thread woke {now - before - 0.005:.3f} s late\n")
        before = now


def run_one(root: str, tag: str, cell: str, seed: int, seconds: float, trace: int) -> dict:
    name = f"{tag}.{cell}.{seed}.t{trace}"
    started = time.time()
    over, late = threading.Event(), []
    witness = threading.Thread(target=ticks, args=(over, late), daemon=True)
    witness.start()
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, capture_output=True, text=True)
    over.set()
    witness.join()
    with open(os.path.join(OUT, name + ".ticks.log"), "w") as f:
        f.writelines(late)
    with open(os.path.join(OUT, name + ".out"), "w") as f:
        f.write(done.stdout + "\n--- stderr ---\n" + done.stderr[-20000:])
    held_lines = held_lines_of(done.stdout)
    with open(os.path.join(OUT, name + ".held.log"), "w") as f:
        f.writelines(held_lines)
    return summary_of(name, done.stdout, done.returncode, len(held_lines), round(time.time() - started, 1))


def reparse() -> None:
    """``summary.jsonl`` anew from the outputs kept under ``chiprun_out/held/``."""
    with open(os.path.join(OUT, "summary.jsonl"), "w") as out:
        for path in sorted(glob.glob(os.path.join(OUT, "*.out"))):
            name = os.path.basename(path)[:-4]
            with open(path) as f:
                stdout = f.read().split("\n--- stderr ---\n")[0]
            held = os.path.join(OUT, name + ".held.log")
            n = sum(1 for _ in open(held)) if os.path.exists(held) else 0
            rc = 0 if stdout.strip().splitlines()[-1:] and stdout.strip().splitlines()[-1].startswith("{") else 1
            out.write(json.dumps(summary_of(name, stdout, rc, n, 0.0)) + "\n")


def table(path: str) -> None:
    """A cell's runs side by side: each run's latency against the cell's median,
    and what the engine found held in it."""
    with open(path) as f:
        runs = [json.loads(line) for line in f]
    for cell in sorted({r["cell"] for r in runs}):
        mine = [r for r in runs if r["cell"] == cell and r.get("metrics") and not r["trace"]]
        for key in ("request_latency_mean_s", "train_tokens_per_s"):
            values = [r["metrics"][key] for r in mine if key in r["metrics"]]
            if not values:
                continue
            middle = statistics.median(values)
            print(f"{cell}: {key} median {middle} over {len(values)} runs")
            for r in mine:
                engine = r.get("engine") or {}
                held = engine.get("held") or {}
                by = {c: held[c] for c in ("gc", "python", "threads", "machine") if held.get(c, {}).get("n")}
                step = (engine.get("host") or {}).get("llm.step")
                per_step = "" if not step else (
                    f"; a step {1e3 * engine['phase_s']['step'] / engine['steps']:.4f} ms, its own part "
                    f"{1e3 * (engine['phase_s']['step'] - engine['phase_s']['fetch']) / engine['steps']:.4f}, "
                    f"its thread's CPU {1e3 * step['cpu_s'] / engine['steps']:.4f}, others' "
                    f"{1e3 * step['others_cpu_s'] / engine['steps']:.4f}, switched {step['switched']}")
                print(
                    f"  {r['tag']} seed {r['seed']}: {r['metrics'][key]} ({r['metrics'][key] - middle:+.6g}) "
                    f"held {held.get('n')} excess_s {held.get('excess_s')} {by} gc_s "
                    f"{(engine.get('gc') or {}).get('s')} log lines {r['held_lines']}{per_step}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", default="change")
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--runs", nargs="*", default=[], help="cell:seed ...")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--summary", action="store_true")
    ap.add_argument("--reparse", action="store_true", help="summary.jsonl anew from the kept outputs")
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "summary.jsonl")
    for run in args.runs:
        cell, seed = run.rsplit(":", 1)
        summary = run_one(os.path.abspath(args.root), args.tag, cell, int(seed), args.seconds, args.trace)
        print(json.dumps(summary), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(summary) + "\n")
    if args.reparse:
        reparse()
    if args.summary:
        table(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
