"""Serve traffic: an open loop of chat turns through the deployment handle.

One general generator, driven by the mix's parameters: a fixed multiset of
prompt and output lengths sent as one repeating cycle at a fixed rate, before,
through and after the window, so that every seed offers the same work in the
same order at the same times. The seed draws every token id (no two prompts
share a prefix, so the prefix cache is bypassed) and the weights. Requests are timed from when they were *due*, so a stall that
delays later sends counts against the system, and how late the generator
itself ran is reported beside them.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Any, Dict, List

from benchmark import chip, yardstick

WAITERS = 32       # threads blocked on results; more than can be in flight unshed


def schedule(params: Dict[str, Any], seed: int, seconds: float) -> List[Dict[str, Any]]:
    """The requests of a run, ``due`` in seconds from the window's start.

    Requests ``0 .. rate x seconds - 1`` are due in the window and measured.
    ``lead_in_requests`` before it and ``lead_out_requests`` after it keep the
    same rate and cycle and are not measured: a request lives for several
    arrival intervals, so without them the requests near the window's edges
    would meet less traffic than those in its middle.

    The cycle is the file's: request ``i`` takes the prompt length, the output
    length and the offset of its due time from the even grid (``due_offsets``,
    in arrival intervals) at position ``i`` modulo the cycle. ``--seed`` draws the token ids and
    nothing else: a request lives for several arrival intervals, so where in
    the cycle a window starts decides which requests overlap, and a start
    drawn from the seed moved the mean latency by a fifth (PERF.md)."""
    import numpy as np

    prompts, outputs = params["prompt_tokens"], params["output_tokens"]
    offsets = params["due_offsets"]
    if not len(prompts) == len(outputs) == len(offsets):
        raise ValueError("prompt_tokens, output_tokens and due_offsets differ in length")
    cycle = len(prompts)
    interval = 1.0 / params["rate_rps"]
    rng = np.random.default_rng(seed)
    lead_in, in_window = int(params["lead_in_requests"]), int(params["rate_rps"] * seconds)
    out = []
    for i in range(-lead_in, in_window + int(params["lead_out_requests"])):
        k = i % cycle
        due = (i + offsets[k]) * interval
        if 0 <= i < in_window:                  # due inside the window, whatever the offset
            due = min(max(due, 0.0), seconds - 1e-3)
        elif i < 0:
            due = min(max(due, -lead_in * interval), -1e-3)
        else:
            due = max(due, seconds)
        out.append({
            "index": i, "measured": 0 <= i < in_window, "due": due,
            "prompt": [int(t) for t in rng.integers(0, params["vocab_size"], size=prompts[k])],
            "n_out": int(outputs[k]),
        })
    return sorted(out, key=lambda r: r["due"])


def _gate(
    handle, params: Dict[str, Any], seed: int, reference: Dict[str, Any]
) -> List[str]:
    """One seeded prompt asked twice: uncached, then from the prefix cache,
    must give equal tokens and bitwise-equal logits; and the logits must be
    those of the configuration's plain reference (``reference`` is the group
    of that name in its file), which the replica runs in float32 on the
    served weights over the prompt and the tokens it gave."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    n, new = params["gate_prompt_tokens"], params["gate_new_tokens"]
    vocab = params["vocab_size"]
    ask = {
        "prompt": [int(t) for t in rng.integers(0, vocab, size=n)],
        "max_new_tokens": new, "return_logits": True,
    }
    first = handle.remote(ask).result(timeout=600.0)
    again = handle.remote(ask).result(timeout=600.0)
    block = params["block_size"]
    reused = (n - 1) // block * block
    problems = []
    if first["prefix_cached_tokens"] != 0 or again["prefix_cached_tokens"] != reused:
        problems.append(
            f"prefix reuse: first {first['prefix_cached_tokens']}, repeat "
            f"{again['prefix_cached_tokens']}, want 0 then {reused}"
        )
    if again["tokens"] != first["tokens"] or not np.array_equal(
        again["logits"], first["logits"]
    ):
        problems.append(
            f"cached decode differs from uncached: tokens {first['tokens']} vs "
            f"{again['tokens']}, max |dlogit| "
            f"{float(np.abs(again['logits'] - first['logits']).max())}"
        )
    if first["logits"].shape != (new, vocab) or not np.isfinite(
        first["logits"]
    ).all():
        problems.append(f"bad logits {first['logits'].shape}")
        return problems
    t0 = time.perf_counter()
    want = handle.reference_logits.remote(
        ask["prompt"] + first["tokens"][:-1], new
    ).result(timeout=600.0)
    error = yardstick.logits_error(first["logits"], want)
    chip.say(
        f"reference {reference['module']} on the served weights, float32, {n + new - 1} "
        f"tokens in {time.perf_counter() - t0:.2f}s: the server's {new} x "
        f"{vocab} logits differ by {error:.5f} of the reference's standard "
        f"deviation {float(np.std(want)):.4f} (max |d| {float(np.abs(first['logits'] - want).max()):.5f}, "
        f"limit {reference['max_logits_error']}); argmax "
        f"{[int(t) for t in want.argmax(-1)]} vs served tokens {first['tokens']}"
    )
    if not error <= reference["max_logits_error"]:
        problems.append(
            f"the server's logits differ from the reference's by {error:.5f} of "
            f"their standard deviation, over the limit {reference['max_logits_error']}"
        )
    return problems


def deploy(cell, seed: int):
    """Put the replica on the chip, warm every shape, and pass the gate.
    Returns ``(handle, device, warm, problems, params)``, ``params`` the
    traffic file's with the served vocabulary and the engine's block size;
    call inside ``chip.cluster``."""
    from ray_tpu import serve

    from benchmark.server import BenchLLMServer

    config, params = cell.config, dict(cell.traffic)
    engine = config["engine"]
    t0 = time.perf_counter()
    handle = serve.run(
        serve.deployment(
            BenchLLMServer, name="bench",
            ray_actor_options={"num_tpus": 1} if chip.PLATFORM == "tpu" else None,
        ).bind(cell.architecture, cell.reference, config, seed=seed, **engine),
        timeout=600.0,
    )
    # the first call returns once the replica has built its weights
    device = handle.kv_stats.remote().result(timeout=900.0)["device"]
    chip.check_device(device, cell.chips, "the serve replica")
    up_s = time.perf_counter() - t0
    warm = handle.warm.remote().result(timeout=1100.0)
    params.setdefault("vocab_size", warm["vocab_size"])
    params["block_size"] = engine["block_size"]
    chip.say(
        f"serve replica: {device}; {warm['model']}; up in {up_s:.1f}s (weights "
        f"{warm['weights_s']:.1f}s), {warm['shapes']} shapes warmed in "
        f"{warm['warm_s']:.1f}s; engine {engine}"
    )
    problems = _gate(handle, params, seed, config["reference"])
    return handle, device, warm, problems, params


def offer(handle, requests: List[Dict[str, Any]], window_start: float, deadline: float) -> None:
    """Send each request when it is due (``window_start`` + its ``due``, on
    ``chip.now()``'s clock) whatever became of the earlier ones, and wait for
    the answers until ``deadline``. Fills each request's ``sent`` and, when it
    completes, ``done``, ``ttft_s``, ``queue_s`` and ``tokens``; else ``error``."""
    from ray_tpu.serve.handle import BackPressureError

    def finish(rec, response):
        try:
            result = response.result(timeout=max(0.1, deadline - chip.now()))
            rec["done"] = chip.now() - window_start
            rec["ttft_s"] = result["ttft_s"]
            rec["queue_s"] = result.get("queue_s")
            rec["tokens"] = result["tokens"]
        except Exception as e:  # noqa: BLE001 — any failure is a failed request
            rec["error"] = repr(e)

    pool = ThreadPoolExecutor(WAITERS, thread_name_prefix="bench-wait")
    futures = []
    try:
        for rec in requests:
            delay = window_start + rec["due"] - chip.now()
            if delay > 0:
                time.sleep(delay)
            rec["sent"] = chip.now() - window_start
            ask = {"prompt": rec.pop("prompt"), "max_new_tokens": rec["n_out"]}
            try:
                futures.append(pool.submit(finish, rec, handle.remote(ask)))
            except BackPressureError as e:
                rec["error"], rec["shed"] = repr(e), True
        wait(futures, timeout=max(0.1, deadline - chip.now()) + 5.0)
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


def check_completions(requests, vocab: int) -> List[str]:
    """Mark each request ``ok`` or not; a completion of the wrong length or
    outside the vocabulary is a wrong result, not a slow one."""
    problems = []
    for rec in requests:
        rec["ok"] = "error" not in rec and "done" in rec
        if rec["ok"] and not (
            len(rec["tokens"]) == rec["n_out"] and all(0 <= t < vocab for t in rec["tokens"])
        ):
            rec["ok"] = False
            problems.append(f"request {rec['index']}: bad completion {rec['tokens']}")
    return problems


def counter_deltas(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, Any]:
    """What the engine counted between two ``kv_stats`` reads: the difference
    of every number in them, and of every number in a group of numbers
    (``phase_s``, ``phase_n``), under its own name; whatever a later engine
    adds comes with it. What is not a number (the device, a list) is left out."""
    out: Dict[str, Any] = {}
    for k, v in after.items():
        if isinstance(v, dict) and isinstance(before.get(k), dict):
            group = counter_deltas(v, before[k])
            if group:
                out[k] = group
        elif _is_number(v) and _is_number(before.get(k)):
            out[k] = v - before[k]
    return out


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def run(cell, seed: int, seconds: float, traced: bool, started: float) -> Dict[str, Any]:
    with chip.cluster(cell.chips):
        handle, device, warm, problems, params = deploy(cell, seed)
        requests = schedule(params, seed, seconds)
        stats0 = handle.kv_stats.remote().result(timeout=60.0)
        lead = -min(r["due"] for r in requests)
        window_start = chip.now() + 0.2 + lead
        if traced:
            handle.arm_trace.remote(
                window_start + params["trace_from"] * seconds, params["trace_seconds"]
            ).result(timeout=60.0)
        offer(handle, requests, window_start, window_start + seconds + params["drain_limit_s"])
        drained_s = chip.now() - window_start - seconds
        stats1 = handle.kv_stats.remote().result(timeout=120.0)
        trace = handle.trace_result.remote().result(timeout=300.0) if traced else None

    problems += check_completions(requests, params["vocab_size"])
    measured = [r for r in requests if r["measured"]]
    failed = [r for r in measured if not r["ok"]]
    if stats1["kv_blocks_in_use"] != stats1["prefix_cached_blocks"]:
        problems.append(
            f"KV blocks in use {stats1['kv_blocks_in_use']} != prefix-cached "
            f"{stats1['prefix_cached_blocks']}: a leak, or requests still in flight"
        )
    if stats0["compile_cache"] != stats1["compile_cache"]:
        problems.append(
            f"something compiled inside the window: {stats0['compile_cache']} -> "
            f"{stats1['compile_cache']}"
        )
    for p in problems:
        chip.say(f"NOT CORRECT: {p}")
    for r in failed[:5]:
        chip.say(f"failed request {r['index']}: {r.get('error', 'unfinished')}")

    counters = counter_deltas(stats1, stats0)
    late = [r["sent"] - r["due"] for r in measured]
    compiled = warm["compiled"]
    chip.say(
        f"requests: {len(measured)} due in the {seconds}s window (+{len(requests) - len(measured)} "
        f"before and after it, {sum(1 for r in requests if not r['ok']) - len(failed)} of those failed), {len(failed)} failed, {sum(1 for r in measured if r.get('shed'))} shed; "
        f"drained {drained_s:.2f}s after the window (limit {params['drain_limit_s']}s); "
        f"generator lateness median {yardstick.median(late) * 1e3:.2f} ms max {max(late) * 1e3:.2f} ms"
    )
    chip.say(
        f"engine over lead-in, window, lead-out and drain: {json.dumps(counters)}; kv blocks in use "
        f"{stats1['kv_blocks_in_use']}; compile cache {stats1['compile_cache']}"
    )
    chip.say(
        f"memory: peak_bytes_in_use {stats1['device']['peak_bytes_in_use']} of "
        f"{stats1['device']['bytes_limit']}; the compiler sizes the largest extend shape "
        f"(lanes, tokens, cache) {compiled}"
    )
    if trace and "busy_s" in trace:
        chip.say(
            f"traced sub-window: {trace['engine']['steps']} engine steps "
            f"({trace['engine']['in_step_s']:.3f}s inside them), {trace['window_s']:.3f}s "
            f"from {trace['started_at'] - window_start:.2f}s into the window; profiler "
            f"start+stop {trace['overhead_s']:.2f}s, xplane {trace['xplane_bytes']} B, "
            f"reduced in {trace['reduce_s']:.2f}s; per device {trace['per_device']}"
        )
    return {
        "kind": "serve",
        "correct": not problems,
        "attempted": len(measured), "failed": len(failed),
        "setup_s": window_start - started,
        "window_s": float(seconds),
        "drain_limit_s": params["drain_limit_s"],
        "records": measured, "counters": counters,
        "trace": trace,
        "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"],
            "memory_peak_bytes": stats1["device"]["peak_bytes_in_use"],
        },
    }
