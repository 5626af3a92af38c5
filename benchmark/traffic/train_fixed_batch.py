"""Train traffic: one seeded batch, steps back to back for the window,
through ``JaxTrainer`` and ``session.report`` — the train step does the work.

``run`` is the parent's side and never touches jax; ``train_loop`` runs in the
worker that holds the chip(s): it builds the sharded state from the seed in one
jitted call, takes the batch's loss under those weights from the
configuration's plain reference, compiles the step once, warms it (its first
loss is the one the reference gave), measures whole steps for the
window by its own clock, and (``--trace 1``) traces a few of them.

The window keeps the chip fed while the host stands still (PR 49: a host that
stalls for a second between two steps took 2 % of a run's rate with it, and the
check's two sets of runs of one tree then differed by more than the bound): the
mix's ``ahead_s`` seconds of steps (at most ``ahead_steps_at_most``) are sent
ahead of the one whose loss is fetched, so every loss is read and reported that
many steps late. When the time is up nothing more is sent, every step that was
sent is waited for, and the clock is read after that wait: all of that work
counts, over all of that time. The traced steps are taken one at a time, the
queue drained before them, so that a traced span holds one whole step.
"""

from __future__ import annotations

import collections
import importlib
import math
import os
import re
import time
from typing import Any, Dict, List

from benchmark import chip, yardstick
from benchmark.manifest import published_keys
from benchmark.trace_reduce import kernel_of
from benchmark.tracing import SubWindowTrace

ANNOTATION = "bench.train_step"
# a Pallas kernel in a compiled program's text: its instruction is named for the
# kernel (``%flash_fwd.7 = (...) custom-call(...), custom_call_target="tpu_custom_call", ...``)
KERNEL_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?(\S+) = [^\n]*custom_call_target="tpu_custom_call"', re.MULTILINE)


def kernels_of(text: str) -> Dict[str, int]:
    """Every ``tpu_custom_call`` of a compiled step's text, counted under its
    kernel's name: the instruction's name less its ``.N``, as a trace's
    ``ops_by_kernel`` has it (``flash_fwd``, ``flash_bwd_dq``, ``gmm``, ``tgmm``)."""
    return dict(collections.Counter(map(kernel_of, KERNEL_CALL.findall(text))))


def missing_kernels(kernels: Dict[str, int], floors: Dict[str, int]) -> List[str]:
    """What a compiled step lacks of the kernels its configuration's
    ``job.min_kernels`` asks for, one line a kernel. A floor is what one forward
    and one backward pass **require** of that kernel with nothing replayed (the
    rule of ``train_step_flops`` and ``experts_work``: remat's replay is the
    program's choice, not the mathematics'), and it is asked by name: a total
    cannot tell a step whose attention fell back to XLA from one that replays
    fewer grouped matmuls. A file without floors (a tiny one, off the chip) asks
    for nothing."""
    return [
        f"{kernels.get(name, 0)} {name} in the compiled step (its tpu_custom_call by kernel: "
        f"{dict(sorted(kernels.items()))}), want >= {floor}: what one forward and one "
        f"backward pass require of that kernel"
        for name, floor in floors.items() if kernels.get(name, 0) < floor
    ]


def train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``: everything on the device happens here."""
    import jax
    import numpy as np

    from ray_tpu._private import accelerator
    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import session

    file, chips = config["file"], config["chips"]
    job = file["job"]
    cfg = importlib.import_module(config["architecture"]).program_config(
        published_keys(file)
    )
    batch = tuple(job["batch"])
    device = accelerator.device_report()
    devices = jax.devices()[:chips]
    mesh = MeshSpec(**job["mesh"]).build(devices)
    opt = default_optimizer(learning_rate=job["learning_rate"])
    t0 = time.perf_counter()
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(config["seed"]), batch
    )
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 1), batch, 0, cfg.vocab_size
    )
    t0 = time.perf_counter()
    reference_loss = importlib.import_module(config["reference"]).program_loss(
        state.params, tokens, file
    )
    reference_s = time.perf_counter() - t0

    def send():
        """Dispatch one step; its metrics stay on the device."""
        nonlocal state
        t = time.perf_counter()
        state, m = compiled(state, tokens)
        send_s.append(time.perf_counter() - t)
        return m

    def fetch(m) -> Dict[str, float]:
        loss = float(np.asarray(m["loss"]))       # waits for the step
        # every other scalar the step reports, for whichever reader wants it,
        # in one fetch (a fetch apiece took 0.3 ms of a step: PERF.md, PR 26)
        rest = jax.device_get({k: v for k, v in m.items() if k != "loss" and np.ndim(v) == 0})
        return {**{k: float(v) for k, v in rest.items()}, "loss": loss}

    def one_step():
        t = time.perf_counter()
        m = fetch(send())
        return {**m, "step_s": time.perf_counter() - t}

    send_s: List[float] = []
    with mesh:
        t0 = time.perf_counter()
        compiled = step.lower(state, tokens).compile()
        compile_s = time.perf_counter() - t0
        memory = compiled.memory_analysis()
        kernels = kernels_of(compiled.as_text())
        warm = [one_step() for _ in range(job["warmup_steps"])]
        cache0 = accelerator.compile_cache_stats()

        trace = SubWindowTrace(ANNOTATION) if config["trace"] else None
        trace_from = config["seconds"] * job["trace_from"]
        # how many steps are sent ahead of the one that is waited for
        traffic = config.get("traffic") or {}
        warm_step_s = sorted(m["step_s"] for m in warm)[len(warm) // 2]
        ahead = min(
            math.ceil(traffic.get("ahead_s", 0.0) / warm_step_s),
            traffic.get("ahead_steps_at_most", 0),
        )
        steps: List[Dict[str, Any]] = []
        report_s: List[float] = []
        sent: collections.deque = collections.deque()
        del send_s[:]

        def take():
            """Wait for the oldest step that was sent, and report it. Its
            ``step_s`` is the time since the step before it was taken: a step's
            own time while the chip is what is waited for."""
            nonlocal taken_at
            m = fetch(sent.popleft())
            now = time.perf_counter()
            steps.append({**m, "step_s": now - taken_at})
            taken_at = now
            session.report({"step": len(steps), "loss": m["loss"]})
            report_s.append(time.perf_counter() - now)

        window_start = chip.now()
        t_start = taken_at = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t_start
            if elapsed >= config["seconds"] and not (trace and trace.running):
                break
            if trace and trace.started_at is None and elapsed >= trace_from:
                while sent:
                    take()
                trace.start()
                taken_at = time.perf_counter()
            if trace and trace.running:
                with trace.unit():
                    sent.append(send())
                    take()
                if trace.units >= job["trace_steps"]:
                    trace.stop()
                    taken_at = time.perf_counter()
            else:
                sent.append(send())
                if len(sent) > ahead:
                    take()
        while sent:                  # nothing more is sent; all that was sent counts
            take()
        window_s = time.perf_counter() - t_start
        cache1 = accelerator.compile_cache_stats()

    session.report({
        "summary": True, "device": device, "init_s": init_s, "compile_s": compile_s,
        "kernels": kernels, "mesh": dict(mesh.shape),
        "reference_loss": reference_loss, "reference_s": reference_s,
        "warm": warm, "steps": steps, "report_s": report_s,
        "ahead": ahead, "send_s": send_s,
        "window_start": window_start, "window_s": window_s,
        "compile_cache": [cache0, cache1],
        "compiled_bytes": None if memory is None else {
            "argument": memory.argument_size_in_bytes,
            "temp": memory.temp_size_in_bytes,
            "output": memory.output_size_in_bytes,
            "alias": memory.alias_size_in_bytes,
        },
        "peak_bytes_per_device": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
        ],
        "trace": trace.result() if trace else None,
    })


def run(cell, seed: int, seconds: float, traced: bool, started: float) -> Dict[str, Any]:
    """Start the cluster, run the job, and hand back what the readers read."""
    from ray_tpu import train

    config, job = cell.config, cell.config["job"]
    architecture = importlib.import_module(cell.architecture)
    with chip.cluster(cell.chips) as worker:
        on_tpu = chip.PLATFORM == "tpu"
        result = train.JaxTrainer(
            train_loop,
            train_loop_config={
                "file": config, "architecture": cell.architecture,
                "reference": cell.reference, "chips": cell.chips, "seed": seed,
                "seconds": seconds, "trace": traced, "traffic": cell.traffic,
            },
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=on_tpu,
                tpu_per_worker=cell.chips if on_tpu else 0,
            ),
            run_config=train.RunConfig(
                name="bench", storage_path=os.path.join(worker.session_dir, "bench_train"),
            ),
        ).fit()
    if result.error is not None:
        raise result.error
    s = result.metrics
    if not s.get("summary"):
        raise RuntimeError("the train worker ended without its summary")
    chip.check_device(s["device"], cell.chips, "the train worker")

    steps, warm = s["steps"], s["warm"]
    reported = [m for m in result.metrics_history if "step" in m and "summary" not in m]
    losses = [m["loss"] for m in warm + steps]
    finite = all(math.isfinite(v) for m in warm + steps for v in m.values())
    cache0, cache1 = s["compile_cache"]
    problems = []
    if not finite:
        problems.append("a step reported a number that is not finite")
    limit = config["reference"]["max_loss_error"]
    loss_error = abs(warm[0]["loss"] - s["reference_loss"]) / s["reference_loss"]
    if not loss_error <= limit:
        problems.append(
            f"the first step's loss {warm[0]['loss']} differs from the reference's "
            f"{s['reference_loss']} by {loss_error:.2e} of it, over the limit {limit}"
        )
    if not warm[-1]["loss"] < warm[0]["loss"]:
        problems.append(f"loss did not fall over the warm-up steps: {[m['loss'] for m in warm]}")
    problems.extend(missing_kernels(s["kernels"], job.get("min_kernels", {})))
    if cache0 != cache1:
        problems.append(f"something compiled inside the window: {cache0} -> {cache1}")
    if len(reported) != len(steps):
        problems.append(f"{len(reported)} reports came back for {len(steps)} steps")
    for p in problems:
        chip.say(f"NOT CORRECT: {p}")

    batch = job["batch"]
    step_s = [m["step_s"] for m in steps]
    compiled = s["compiled_bytes"]
    chip.say(
        f"train worker: {s['device']}; mesh {s['mesh']}; init {s['init_s']:.2f}s, step "
        f"compile {s['compile_s']:.2f}s, tpu_custom_call x{sum(s['kernels'].values())} "
        f"by kernel {dict(sorted(s['kernels'].items()))}; compile cache {cache1}"
    )
    chip.say(
        f"reference {config['reference']['module']} on the initial weights, float32, "
        f"{s['reference_s']:.2f}s: loss {s['reference_loss']:.6f}; the step's first loss "
        f"{warm[0]['loss']:.6f} differs by {loss_error:.2e} of it (limit {limit})"
    )
    chip.say(
        f"steps taken {len(steps)} in {s['window_s']:.3f}s (asked {seconds}s); step_s "
        f"median {yardstick.median(step_s):.4f} p95 {yardstick.percentile(step_s, 0.95):.4f} "
        f"max {max(step_s):.4f}; losses {losses[0]:.4f} -> {losses[-1]:.4f}"
    )
    chip.say(
        f"steps sent ahead of the one waited for: {s['ahead']} (the mix's ahead_s "
        f"{cell.traffic.get('ahead_s', 0.0)}s of steps, at most "
        f"{cell.traffic.get('ahead_steps_at_most', 0)}); a send took median "
        f"{yardstick.median(s['send_s']):.4f}s max {max(s['send_s']):.4f}s; steps that took "
        f"over 1.25 medians: {sum(1 for v in step_s if v > 1.25 * yardstick.median(step_s))}"
    )
    chip.say(
        f"memory: peak_bytes_in_use per device {s['peak_bytes_per_device']}; the compiler "
        f"sizes the step at {compiled} (arguments + temporaries + outputs - aliased = "
        f"{None if compiled is None else compiled['argument'] + compiled['temp'] + compiled['output'] - compiled['alias']} B per device)"
    )
    trace = s["trace"]
    if trace:
        chip.say(
            f"traced sub-window: {trace['units']} steps, {trace['window_s']:.3f}s from "
            f"{trace['started_at'] - s['window_start']:.2f}s into the window; profiler "
            f"start+stop {trace['overhead_s']:.2f}s, xplane {trace['xplane_bytes']} B, "
            f"reduced in {trace['reduce_s']:.2f}s; per device {trace['per_device']}"
        )
    peaks = [p for p in s["peak_bytes_per_device"] if p]
    return {
        "kind": "train",
        "correct": not problems,
        "attempted": len(steps), "failed": 0 if finite else len(steps),
        "setup_s": s["window_start"] - started,
        "window_s": s["window_s"],
        # tracing's own cost is no part of the step: leave it out of the rate
        "work_s": s["window_s"] - (trace["overhead_s"] if trace else 0.0),
        "steps": len(steps), "step_s": step_s, "report_s": s["report_s"],
        "ahead": s["ahead"],
        "tokens_per_step": batch[0] * batch[1],
        "flops_per_step": architecture.train_step_flops(
            published_keys(config), batch[0], batch[1]
        ),
        "step_metrics": steps,
        "chips": cell.chips, "device_kind": s["device"]["kind"],
        "trace": trace,
        "device": {
            "platform": s["device"]["platform"], "kind": s["device"]["kind"],
            "count": s["device"]["count"],
            "memory_peak_bytes": max(peaks) if peaks else None,
        },
    }
