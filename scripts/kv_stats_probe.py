"""One run of a serve cell through the benchmark's own ``run_cell``, keeping
what the benchmark drops: the two ``kv_stats`` reads around the run (lead-in,
window, lead-out, drain), a poll of ``kv_stats`` beside it, each result's
``queue_s``, and the reduced trace. Prints the split of an engine step by
``phase_s``, the bytes, fills and queue time, the two forms of device call
(how many, the device's time on each, what of their padded shapes was real)
and the same split over the steps the profiler session recorded, and
``breakdown.idle_gaps`` beside them: the numbers of PERF.md section 5's serve
paragraph.

    chiprun -- python3 scripts/kv_stats_probe.py --seed 2147484227 --trace 1 --poll 0.5

Reads a parent without the counters as far as it goes. ``--root`` names
another checkout to run (it must hold ``benchmark/`` and ``ray_tpu/``; run the
script **from** that checkout, so that its workers import that tree too).

``--fixed`` runs no cell: it builds the cell's engine in this process and
steps it through one cycle of the cell's requests, request ``i`` admitted
after ``4 i`` device-calling steps, so that every checkout makes the same
device calls in the same order whatever its speed, and keeps the ids each
request was given: two checkouts that serve the same tokens print the same
``ids sha256`` (give each its own ``--out``).
"""

import argparse
import contextlib
import hashlib
import json
import os
import statistics
import sys
import threading
import time

LEAF = ("admit", "upload", "kv_gather", "dispatch", "kv_scatter", "fetch", "sample")
NOT_COUNTERS = ("device", "compile_cache", "adapters_resident")


class _Kept:
    """A response whose answer is also handed to ``keep``."""

    def __init__(self, response, keep):
        self._response, self._keep = response, keep

    def result(self, *args, **kwargs):
        got = self._response.result(*args, **kwargs)
        self._keep(got)
        return got


class _KvStats:
    def __init__(self, method, snaps):
        self._method, self._snaps = method, snaps

    def remote(self, *args, **kwargs):
        return _Kept(
            self._method.remote(*args, **kwargs),
            lambda got: self._snaps.append({"t": time.time(), "stats": got}),
        )


class _Recorder:
    """The handle, keeping every ``kv_stats`` answer and every result's ``queue_s``."""

    def __init__(self, handle, snaps, queued):
        self._handle, self._snaps, self._queued = handle, snaps, queued

    def remote(self, *args, **kwargs):
        return _Kept(
            self._handle.remote(*args, **kwargs),
            lambda got: self._queued.append(got["queue_s"]) if "queue_s" in got else None,
        )

    def __getattr__(self, name):
        real = getattr(self._handle, name)
        return _KvStats(real, self._snaps) if name == "kv_stats" else real


def probe(root, workload, seed, seconds, traced, poll_s=0.0):
    """Run the cell from ``root``; returns the last line and what was kept."""
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    from benchmark import contract, run as run_mod
    from benchmark.traffic import serve_open_loop

    snaps, polled, queued, stop = [], [], [], threading.Event()
    real_deploy = serve_open_loop.deploy

    def deploy(cell, seed):
        out = real_deploy(cell, seed)
        handle = out[0]

        def poll():
            while not stop.wait(poll_s):
                try:
                    got = handle.kv_stats.remote().result(timeout=30.0)
                except Exception as e:  # noqa: BLE001 — the probe must not end the run
                    polled.append({"t": time.time(), "error": repr(e)})
                    continue
                polled.append({"t": time.time(), "stats": {
                    k: v for k, v in got.items() if k not in NOT_COUNTERS
                }})
        if poll_s > 0:
            threading.Thread(target=poll, daemon=True).start()
        return (_Recorder(handle, snaps, queued),) + tuple(out[1:])

    serve_open_loop.deploy = deploy
    try:
        line, cell, run = run_mod.run_cell(root, workload, seed, seconds, traced, time.time())
    finally:
        stop.set()
        serve_open_loop.deploy = real_deploy
    return {
        "root": root, "workload": workload, "seed": seed, "traced": traced,
        "line": line,
        "problems": contract.violations(line, cell.metrics(traced), traced),
        "trace": run.get("trace"), "setup_s": run.get("setup_s"),
        "records": [{k: v for k, v in r.items() if k != "tokens"} for r in run.get("records", [])],
        "queue_s": queued, "snaps": snaps, "polled": polled,
    }


def cell_engine(root, workload, seed):
    """The cell's engine in this process, warm, with what ``warm`` reported and
    one cycle of the cell's requests as its generator makes them."""
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    from benchmark import manifest
    from benchmark.server import BenchLLMServer
    from benchmark.traffic import serve_open_loop

    cell = manifest.Manifest(root).cell(workload)
    server = BenchLLMServer(
        cell.architecture, cell.reference, cell.config, seed=seed, **cell.config["engine"])
    warm = server.warm()
    params = dict(cell.traffic, vocab_size=warm["vocab_size"])
    turns = len(params["prompt_tokens"])
    cycle = sorted((
        r for r in serve_open_loop.schedule(params, seed, (turns + 1) / params["rate_rps"])
        if 0 <= r["index"] < turns), key=lambda r: r["index"])
    return server._engine, warm, cycle


def step_through(eng, cycle, every=4, around=contextlib.nullcontext):
    """The requests of ``cycle`` through ``eng``, request ``i`` admitted after
    ``every * i`` device-calling steps, each step inside ``around()``; the
    sequences, done."""
    from ray_tpu.serve import batching

    waiting = [
        batching._Sequence({"prompt": r["prompt"], "max_new_tokens": r["n_out"]}) for r in cycle
    ]
    seqs, active, calling_steps = list(waiting), [], 0
    while waiting or active:
        while waiting and (not active or calling_steps >= every * (len(seqs) - len(waiting))):
            active.append(waiting.pop(0))
        # by the count every checkout keeps, a parent of PR 31 too
        calls = eng.phase_n["dispatch"]
        with around():
            eng.step(active)
        calling_steps += eng.phase_n["dispatch"] > calls
        active = [s for s in active if not s.done]
    return seqs


def fixed_schedule(root, workload, seed, every=4):
    """One cycle of the cell's requests through its engine, in this process,
    on a schedule counted in steps; the ids, what the engine counted and the
    device's peak memory."""
    eng, warm, cycle = cell_engine(root, workload, seed)
    before, t0 = eng.stats(), time.perf_counter()
    seqs = step_through(eng, cycle, every)
    wall_s, after = time.perf_counter() - t0, eng.stats()
    errors = [repr(s._error) for s in seqs if s._error is not None]
    ids = [s._result["tokens"] if s._error is None else None for s in seqs]
    return {
        "root": os.path.abspath(root), "workload": workload, "seed": seed, "wall_s": wall_s,
        "errors": errors, "ids": ids,
        "sha256": hashlib.sha256(json.dumps(ids).encode()).hexdigest(),
        "delta": delta(after, before), "device": after["device"], "warm": warm,
    }


def report_fixed(kept, say=print):
    d = kept["delta"]
    say(f"{kept['workload']} seed {kept['seed']} fixed schedule: {len(kept['ids'])} requests, "
        f"{sum(len(t) for t in kept['ids'] if t)} ids, errors {kept['errors']}, "
        f"{kept['wall_s']:.2f} s; device {kept['device']}")
    say_split(d, "the whole schedule", say)
    say(f"  ids sha256 {kept['sha256']}")


def delta(after, before):
    out = {}
    for k, v in after.items():
        if isinstance(v, dict) and isinstance(before.get(k), dict):
            out[k] = delta(v, before[k])        # a group of numbers, or of groups
        elif isinstance(v, (int, float)) and not isinstance(v, bool) and k in before:
            out[k] = v - before[k]
    return out


def _size(n):
    """Bytes in the unit that keeps three figures: the pool's traffic fell from GB to KB."""
    for unit, scale in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= scale:
            return f"{n / scale:.3g} {unit}"
    return f"{n:.0f} B"


def say_split(d, label, say=print):
    """The split of the steps between two ``kv_stats`` reads, from their delta."""
    if "phase_s" not in d:
        say(f"  {label}: no phase_s (a parent of PR 24):",
            {k: d[k] for k in ("steps", "decode_tokens") if k in d})
        return
    ph, n, steps = d["phase_s"], d["phase_n"], max(d["steps"], 1)
    step, calls, decoded = ph["step"], max(n["dispatch"], 1), max(d["decode_tokens"], 1)
    if step <= 0:
        say(f"  {label}: no step")
        return
    say(f"  {label}: {d['steps']} steps, {1e3 * step / steps:.2f} ms a step, "
        f"leaf phases / step {sum(ph[k] for k in LEAF) / step:.4f}")
    for k in ("prefill", "decode") + LEAF:
        say(f"     {k:10s} {ph[k]:8.3f} s {100 * ph[k] / step:6.2f} %  n={n[k]:<5d}"
            f"{1e3 * ph[k] / max(n[k], 1):8.2f} ms each")
    say(f"     h2d {_size(d['h2d_bytes'])}: {_size(d['h2d_bytes'] / calls)} a call, "
        f"{_size(d['h2d_bytes'] / steps)} a step, {_size(d['h2d_bytes'] / decoded)} a decode "
        f"token; d2h {_size(d['d2h_bytes'] / steps)} a step (from array sizes)")
    if "h2d_transfers" in d:            # crossings of the host-device link (PR 29)
        say(f"     transfers a call: {d['h2d_transfers'] / calls:.2f} up, "
            f"{d['d2h_transfers'] / calls:.2f} down; ids-only calls {d['ids_only_calls']}/"
            f"{n['dispatch']} = {d['ids_only_calls'] / calls:.3f}")
    if "calls_ahead" in d:              # calls launched behind a call in flight (PR 31)
        say(f"     run-ahead share {d['calls_ahead']}/{n['dispatch']} = "
            f"{d['calls_ahead'] / calls:.3f} of the calls were launched while another was in "
            f"flight; tokens fed on the device {d['tokens_fed_on_device']}/{d['lanes_used']} lanes")
    say(f"     lane_fill {d['lanes_used']}/{d['lane_slots']} = "
        f"{d['lanes_used'] / max(d['lane_slots'], 1):.3f}; cache_fill {d['cache_tokens']}/"
        f"{d['cache_slots']} = {d['cache_tokens'] / max(d['cache_slots'], 1):.3f}")
    if d.get("moe_tokens"):             # an expert layer's counters (PR 28)
        say(f"     experts: {d['moe_assignments']} pairs of {d['moe_tokens']} token-layers "
            f"computed here ({d['moe_assignments'] / d['moe_tokens']:.3f} a token), "
            f"{d['moe_experts_hit'] / calls:.2f} held experts hit a call (summed over layers), "
            f"busiest expert {d['moe_load_max'] / max(d['moe_assignments'], 1):.3f} of the pairs")
    if d.get("sparse_queries"):         # an indexer's counters (PR 32)
        say(f"     indexer: {d['sparse_keys_scored']} query-key pairs scored for "
            f"{d['sparse_queries']} query-layers, {d['sparse_keys_attended']} attended "
            f"({d['sparse_keys_attended'] / max(d['sparse_keys_scored'], 1):.3f} of the scored); "
            f"slots read {d['sparse_slots_read']}/{d['sparse_slots_gathered']} = "
            f"{d['sparse_slots_read'] / max(d['sparse_slots_gathered'], 1):.3f} of the slots "
            f"gathered, over the layers")
    if d.get("window_slots"):
        say(f"     window: {d['window_slots_outside']}/{d['window_slots']} = "
            f"{d['window_slots_outside'] / d['window_slots']:.3f} of the slots gathered for "
            f"sliding layers hold a token no query of the call could see")
    say(f"     admitted {d['admitted']}, queue_s mean {d['queue_s'] / max(d['admitted'], 1):.4f}; "
        f"prefill {d['prefill_tokens']} tokens in {n['prefill']} calls; decode "
        f"{d['decode_tokens']} tokens in {n['decode']} calls")
    for form, c in d.get("calls", {}).items():      # a record per form of call (PR 37)
        say(f"     {form} calls: n={c['n']}, {1e3 * c['busy_s'] / max(c['n'], 1):.2f} ms each on "
            f"the device ({100 * c['busy_s'] / step:.1f} % of the steps' time); lanes "
            f"{c['lanes_used']}/{c['lane_slots']} = {c['lanes_used'] / max(c['lane_slots'], 1):.3f}, "
            f"tokens {c['tokens']}/{c['token_slots']} = "
            f"{c['tokens'] / max(c['token_slots'], 1):.3f}, cache {c['cache_tokens']}/"
            f"{c['cache_slots']} = {c['cache_tokens'] / max(c['cache_slots'], 1):.3f} of their slots")
    if d.get("traced", {}).get("steps"):            # the same, over recorded steps alone
        say_split(d["traced"], f"of them, the {d['traced']['steps']} steps a profiler session recorded", say)


def report(kept, say=print):
    line, trace = kept["line"], kept["trace"]
    say(f"{kept['workload']} seed {kept['seed']} traced {kept['traced']}: correct "
        f"{line['correct']}, failed {line['failed']} of {line['attempted']}, set-up "
        f"{kept['setup_s']:.1f} s, problems {kept['problems']}")
    say("  metrics", {k: round(v["value"], 4) for k, v in line["metrics"].items()})
    done = [r for r in kept["records"] if "ttft_s" in r]
    if done:
        say(f"  latency mean {statistics.mean(r['done'] - r['due'] for r in done):.3f} s; ttft_s "
            f"median {statistics.median(r['ttft_s'] for r in done):.3f} max "
            f"{max(r['ttft_s'] for r in done):.3f}")
    if kept["queue_s"]:
        q = sorted(kept["queue_s"])
        say(f"  queue_s of {len(q)} results (lead-in and lead-out too): median "
            f"{statistics.median(q):.4f} max {q[-1]:.4f}")
    if trace and "busy_s" in trace:
        w, eng = trace["window_s"], trace["engine"]
        say(f"  traced sub-window {w:.3f} s, busy {trace['busy_s']:.3f} s, idle share "
            f"{1 - trace['busy_s'] / w:.4f}; engine.step_ms "
            f"{1e3 * eng['in_step_s'] / max(eng['steps'], 1):.2f} over {eng['steps']} steps")
        for name, t in trace["idle_gaps"]:
            say(f"     idle gap {name:56s} {t:8.4f} s {100 * t / w:6.2f} % of the sub-window")
        say("  device_ops:", [(n, round(t, 4)) for n, t in trace["device_ops"]])
    snaps = kept["snaps"]
    if len(snaps) >= 2:         # the generator's reads: the last two are stats0 and stats1
        s0, s1 = snaps[-2]["stats"], snaps[-1]["stats"]
        say_split(delta(s1, s0), "stats1 - stats0 (lead-in, window, lead-out, drain)", say)
        say("  compile_cache", s0.get("compile_cache"), "->", s1.get("compile_cache"))
    # a read takes slowest_step with it, so the run's slowest is over every read
    slow = [x["stats"].get("slowest_step") for x in snaps[-1:] + kept["polled"] if "stats" in x]
    slow = [s for s in slow if s]
    if slow:
        say("  slowest_step since stats0:", json.dumps(max(slow, key=lambda s: s["wall_s"])))
    polled = [x for x in kept["polled"] if "stats" in x]
    if trace and polled and "started_at" in trace:
        a = max((x for x in polled if x["t"] <= trace["started_at"]), key=lambda x: x["t"], default=None)
        b = min((x for x in polled if x["t"] >= trace["stopped_at"]), key=lambda x: x["t"], default=None)
        if a and b:
            say_split(
                delta(b["stats"], a["stats"]),
                f"polled, around the traced sub-window ({a['t'] - trace['started_at']:+.2f} s .. "
                f"{b['t'] - trace['stopped_at']:+.2f} s)", say,
            )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("--workload", default="gptj-serve-chat-steady")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--poll", type=float, default=0.0, help="seconds between kv_stats polls; 0: none")
    ap.add_argument("--out", default="chiprun_out", help="where probe_<seed>_t<trace>.json goes")
    ap.add_argument("--fixed", action="store_true", help="the fixed schedule, in this process")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    if args.fixed:
        kept = fixed_schedule(args.root, args.workload, args.seed)
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, f"fixed_{args.workload}_{args.seed}.json")
        with open(path, "w") as f:
            json.dump(kept, f, default=str)
        report_fixed(kept)
        print("[probe] kept in", path)
        return
    kept = probe(args.root, args.workload, args.seed, args.seconds, bool(args.trace), args.poll)
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"probe_{args.seed}_t{args.trace}.json")
    with open(path, "w") as f:
        json.dump(kept, f, default=str)
    report(kept)
    print("[probe] kept in", path)
    print("[probe] LINE " + json.dumps(kept["line"]))


if __name__ == "__main__":
    main()
