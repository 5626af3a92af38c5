"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded train step across four chips

One chip: a parent that never initializes a jax backend starts a cluster
through ``ray_tpu.init()``, which must find the chip by itself. A
``JaxTrainer`` leases the chip to a worker process that runs
``init_sharded_state`` + ``make_train_step`` at GPT-J-6B's published widths
(depth cut to what 16 GB holds, bf16 params and moments — with f32 the same
step needs 20.1 GB); then, in the same cluster, ``serve.run`` puts an
``LLMServer`` replica with the same widths at full depth on the chip and
answers requests through the handle and the HTTP proxy, checking the prefix
cache's bitwise gate. Four chips (``--chips 4``, never run by the driver): the
train step on ``MeshSpec(dp=-1, fsdp=2, tp=2)`` against the same step on one
of those chips, in this process.

Every phase checks its own results and any failure exits non-zero. The last
line of standard output is one JSON object naming the device as the process
that ran the model saw it. Without an accelerator there is no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import urllib.request
from typing import Any, Dict, Iterator, List, Tuple

# one bf16 ulp: the compute dtype is bf16 and sharding reorders its sums
LOSS_RTOL = 2.0 ** -7


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one run. ``chip_plan()`` is what runs on the v5e; the tests
    drive the same flow through a tiny plan on the CPU."""

    train_cfg: Any                       # GPTConfig of the train step
    serve_cfg: Any                       # GPTConfig of the server
    cuts: str                            # every cut from the published config
    batch: Tuple[int, int]
    steps: int
    engine: Dict[str, Any]               # LLMEngine keyword arguments
    prompt_len: int                      # the repeated prompt of the bitwise gate
    other_prompt_lens: Tuple[int, ...]   # sent together, through the handle
    max_new_tokens: int
    platform: str = "tpu"                # what the model must run on; "tpu" leases the chip
    min_flash_kernels: int = 3           # forward, dq and dk/dv in the compiled step


def chip_plan() -> Plan:
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_j_6b

    widths = dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return Plan(
        train_cfg=gpt_j_6b(num_layers=6, **widths),
        serve_cfg=gpt_j_6b(**widths),
        cuts=(
            "train: depth 28 -> 6 (bf16 params + bf16 Adam moments + grads are "
            "8 B/param; depth 6 at batch 4x2048 compiles to 14.8 of 15.75 GiB, "
            "depth 7 does not fit); param_dtype f32 -> bf16 (f32 needs 20.1 GB "
            "at depth 4); serve: full depth 28, param_dtype f32 -> bf16 (11.3 "
            "GiB of weights; f32 needs 36.6 GB); widths never cut; weights "
            "random from --seed"
        ),
        batch=(4, 2048),
        steps=6,
        # one cache bucket and two lane buckets keep the compiled shapes to
        # six. The KV pool lives on the chip beside 12.1 GB of weights, and
        # every call builds the padded pair there too (1.88 GB at 4 lanes x
        # 1024): 160 blocks are 1.17 GB, 15.5 of the chip's 16.9 GB in all,
        # where 320 would leave 0.2. The requests below never hold more than
        # 73 blocks at once
        engine=dict(
            num_blocks=160, block_size=16, prefill_chunk=128, prefill_lanes=2,
            lane_buckets=(1, 4), prefill_token_buckets=(32, 128),
            cache_buckets=(1024,),
        ),
        prompt_len=264,
        other_prompt_lens=(200, 330, 600),
        max_new_tokens=8,
    )


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def describe(cfg) -> str:
    return (
        f"embed {cfg.embed_dim} / {cfg.num_heads} heads x {cfg.head_dim} / "
        f"mlp {cfg.mlp_dim} / vocab {cfg.vocab_size} / depth {cfg.num_layers} / "
        f"compute {cfg.dtype.__name__} / params {cfg.param_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.2f}B params"
    )


# ---------------------------------------------------------------------------
# the train step, as the process that holds the chip(s) runs it
# ---------------------------------------------------------------------------


def train_steps(cfg, mesh_spec, devices, batch, steps, seed) -> Iterator[Dict[str, Any]]:
    """Initialize the sharded state on ``devices`` and take ``steps`` steps on
    one seeded batch. Yields one dict per step, then a summary."""
    import jax
    import numpy as np

    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )

    mesh = mesh_spec.build(devices)
    opt = default_optimizer(learning_rate=1e-4)
    t0 = time.perf_counter()
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(seed), batch
    )
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    param_bytes: Dict[int, int] = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(state.params):
        for shard in leaf.addressable_shards:
            param_bytes[shard.device.id] += shard.data.nbytes
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), batch, 0, cfg.vocab_size
    )
    with mesh:
        t0 = time.perf_counter()
        compiled = step.lower(state, tokens).compile()
        compile_s = time.perf_counter() - t0
        flash_kernels = compiled.as_text().count("tpu_custom_call")
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = compiled(state, tokens)
            loss = float(np.asarray(metrics["loss"]))  # waits for the step
            yield {
                "step": i + 1, "loss": loss,
                "grad_norm": float(np.asarray(metrics["grad_norm"])),
                "step_s": time.perf_counter() - t0,
            }
    yield {
        "summary": True, "init_s": init_s, "compile_s": compile_s,
        "flash_kernels": flash_kernels, "mesh": dict(mesh.shape),
        "param_bytes_per_device": param_bytes,
        "peak_bytes_per_device": {
            d.id: (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
        },
    }


def check_training(plan: Plan, history: List[Dict[str, Any]], label: str) -> List[float]:
    import math

    steps, summary = history[:-1], history[-1]
    losses = [m["loss"] for m in steps]
    say(
        f"{label}: init {summary['init_s']:.1f}s, compile {summary['compile_s']:.1f}s, "
        f"steady {sum(m['step_s'] for m in steps[1:]) / max(1, len(steps) - 1):.2f}s/step "
        f"(first step {steps[0]['step_s']:.2f}s), "
        f"tpu_custom_call x{summary['flash_kernels']} in the compiled step"
    )
    say(f"{label}: losses {[round(x, 4) for x in losses]}")
    say(
        f"{label}: param bytes per device {summary['param_bytes_per_device']}, "
        f"peak bytes in use {summary['peak_bytes_per_device']}"
    )
    if len(steps) != plan.steps or not summary.get("summary"):
        raise SystemExit(f"{label}: {len(steps)} step reports came back, want {plan.steps}")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"{label}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"{label}: loss did not fall: {losses}")
    if summary["flash_kernels"] < plan.min_flash_kernels:
        raise SystemExit(
            f"{label}: the compiled step holds {summary['flash_kernels']} "
            f"tpu_custom_call, want >= {plan.min_flash_kernels}: the flash "
            f"kernel was meant to run and did not"
        )
    return losses


def train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker`` of the one-chip phase (runs in the worker)."""
    import jax

    from ray_tpu._private import accelerator
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import session

    for metrics in train_steps(
        config["cfg"], MeshSpec(), jax.devices()[:1], config["batch"],
        config["steps"], config["seed"],
    ):
        if metrics.get("summary"):
            metrics["device"] = accelerator.device_report()
            # enabled at TPU worker start (default_worker)
            metrics["compile_cache"] = accelerator.compile_cache_stats()
        session.report(metrics)


# ---------------------------------------------------------------------------
# one chip: trainer worker, then serve replica, in one cluster
# ---------------------------------------------------------------------------


def assert_parent_off_jax() -> None:
    """A parent that has touched jax holds the chip, and the child that
    needs it then fails or hangs."""
    from ray_tpu._private import virtual_mesh

    if virtual_mesh.backends_initialized():
        raise SystemExit("the parent initialized a jax backend before starting a child")


def check_device(plan: Plan, device: Dict[str, Any], who: str) -> None:
    say(f"{who}: pid {device['pid']} (parent {os.getpid()}) sees {device}")
    if device["pid"] == os.getpid():
        raise SystemExit(f"{who} ran in the parent process")
    if device["platform"] != plan.platform:
        raise SystemExit(
            f"{who} ran on platform {device['platform']!r}, want {plan.platform!r}"
        )


def train_phase(plan: Plan, seed: int, storage: str) -> Dict[str, Any]:
    from ray_tpu import train

    use_tpu = plan.platform == "tpu"

    say(f"trainer model: {describe(plan.train_cfg)}; batch {plan.batch}, {plan.steps} steps")
    assert_parent_off_jax()
    t0 = time.perf_counter()
    result = train.JaxTrainer(
        train_loop,
        train_loop_config={
            "cfg": plan.train_cfg, "batch": plan.batch, "steps": plan.steps,
            "seed": seed,
        },
        scaling_config=train.ScalingConfig(
            num_workers=1, use_tpu=use_tpu, tpu_per_worker=1 if use_tpu else 0
        ),
        run_config=train.RunConfig(name="chip_smoke", storage_path=storage),
    ).fit()
    if result.error is not None:
        raise result.error
    summary = result.metrics
    check_device(plan, summary["device"], "train worker")
    check_training(plan, result.metrics_history, "trainer")
    say(
        f"trainer: phase wall {time.perf_counter() - t0:.1f}s, metrics came back "
        f"through the session ({len(result.metrics_history)} reports); "
        f"compile cache {summary['compile_cache']}"
    )
    return summary["device"]


def make_prompt(rng, length: int, vocab: int) -> List[int]:
    return [int(t) for t in rng.randint(0, vocab, size=length)]


def serve_phase(plan: Plan, seed: int) -> Dict[str, Any]:
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.serve import llm

    cfg, eng, new = plan.serve_cfg, plan.engine, plan.max_new_tokens
    say(f"server model: {describe(cfg)}; engine {eng}")
    assert_parent_off_jax()
    t0 = time.perf_counter()
    handle = serve.run(
        serve.deployment(
            llm.LLMServer, name="gptj",
            ray_actor_options={"num_tpus": 1} if plan.platform == "tpu" else None,
        ).bind(cfg, seed=seed, **eng),
        timeout=600.0,
    )
    # the first call returns once the replica has built its weights on the chip
    stats = handle.kv_stats.remote().result(timeout=900.0)
    check_device(plan, stats["device"], "serve replica")
    say(f"server: replica up with weights in {time.perf_counter() - t0:.1f}s")

    rng = np.random.RandomState(seed)

    def ask(prompt, **kw):
        return {"prompt": prompt, "max_new_tokens": new, **kw}

    timed = []
    for _ in range(2):  # same lengths, so the same compiled shapes: cold, then warm
        prompt = make_prompt(rng, plan.prompt_len, cfg.vocab_size)
        t0 = time.perf_counter()
        first = handle.remote(ask(prompt, return_logits=True)).result(timeout=900.0)
        timed.append(time.perf_counter() - t0)
    say(
        f"server: uncached {plan.prompt_len}-token prompt + {new} tokens through the "
        f"handle: cold {timed[0]:.1f}s (with compilation), warm {timed[1]:.1f}s, "
        f"compile ~{timed[0] - timed[1]:.1f}s; ttft warm {first['ttft_s']:.2f}s"
    )
    # the bitwise gate: the last prompt again, now served from the prefix cache
    again = handle.remote(ask(prompt, return_logits=True)).result(timeout=900.0)
    reused = (plan.prompt_len - 1) // eng["block_size"] * eng["block_size"]
    if first["prefix_cached_tokens"] != 0 or again["prefix_cached_tokens"] != reused:
        raise SystemExit(
            f"prefix reuse wrong: first {first['prefix_cached_tokens']}, "
            f"repeat {again['prefix_cached_tokens']}, want 0 then {reused}"
        )
    if again["tokens"] != first["tokens"] or not np.array_equal(
        again["logits"], first["logits"]
    ):
        raise SystemExit(
            f"cached decode differs from uncached: tokens {first['tokens']} vs "
            f"{again['tokens']}, max |dlogit| "
            f"{np.abs(again['logits'] - first['logits']).max()}"
        )
    if not np.isfinite(first["logits"]).all() or first["logits"].shape != (new, cfg.vocab_size):
        raise SystemExit(f"bad logits {first['logits'].shape}")
    say(
        f"server: repeated prompt reused {reused}/{plan.prompt_len} tokens; tokens "
        f"{first['tokens']} and {new}x{cfg.vocab_size} logits bitwise equal cached and uncached"
    )

    t0 = time.perf_counter()
    pending = [
        handle.remote(ask(make_prompt(rng, n, cfg.vocab_size)))
        for n in plan.other_prompt_lens
    ]
    together = [p.result(timeout=900.0) for p in pending]
    say(
        f"server: {len(together)} requests of {plan.other_prompt_lens} prompt tokens "
        f"together in {time.perf_counter() - t0:.1f}s (cold: new lane shapes)"
    )

    proxy = serve.start_http_proxy()
    try:
        over_http = []
        for p in (prompt, make_prompt(rng, plan.prompt_len, cfg.vocab_size)):
            t0 = time.perf_counter()
            request = urllib.request.Request(
                f"{proxy.address}/gptj", data=json.dumps(ask(p)).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(request, timeout=120) as reply:
                if reply.status != 200:
                    raise SystemExit(f"HTTP proxy answered {reply.status}")
                over_http.append(json.loads(reply.read())["result"])
            say(
                f"server: POST {proxy.address}/gptj -> 200 in "
                f"{time.perf_counter() - t0:.1f}s, tokens {over_http[-1]['tokens']}"
            )
    finally:
        proxy.stop()
    if over_http[0]["tokens"] != first["tokens"]:
        raise SystemExit(
            f"the proxy's tokens {over_http[0]['tokens']} differ from the "
            f"handle's {first['tokens']} for the same prompt"
        )
    for r in together + over_http:
        if len(r["tokens"]) != new or not all(0 <= t < cfg.vocab_size for t in r["tokens"]):
            raise SystemExit(f"bad completion {r['tokens']}")

    stats = handle.kv_stats.remote().result(timeout=60.0)
    say(
        f"server: {stats['steps']} engine steps, {stats['decode_tokens']} decode tokens, "
        f"prefix hits {stats['prefix_hits']}, kv blocks in use {stats['kv_blocks_in_use']}, "
        f"peak bytes in use {stats['device']['peak_bytes_in_use']} of "
        f"{stats['device']['bytes_limit']}; compile cache {stats['compile_cache']}"
    )
    if stats["kv_blocks_in_use"] != stats["prefix_cached_blocks"]:
        raise SystemExit(f"KV blocks leaked: {stats}")
    return stats["device"]


def run_one_chip(plan: Plan, seed: int) -> Dict[str, Any]:
    """Trainer, then server, one after the other in one cluster. Returns the
    device as the processes that ran the model reported it."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import rpc
    from ray_tpu.native import rpc_native

    # a library that does not build fails here, not quietly on the Python transport
    rpc_native.load()
    worker = ray_tpu.init(log_level="WARNING")
    try:
        (node,) = ray_tpu.nodes()
        resources = node["resources"]
        say(
            f"cluster up: resources {resources}, rpc transport {rpc.transport_name()}, "
            f"session {worker.session_dir}"
        )
        if rpc.transport_name() != "native":
            raise SystemExit("the native RPC transport did not load")
        if plan.platform == "tpu" and resources.get("TPU", 0) < 1:
            raise SystemExit(
                "no TPU chip found: ray_tpu.init() reports no TPU resource "
                "(no /dev/accel* or /dev/vfio/* TPU device node on this host, "
                "and RAYTPU_TPU_TOPOLOGY is unset)"
            )
        trained_on = train_phase(
            plan, seed, os.path.join(worker.session_dir, "train_results")
        )
        # the train worker was an actor: its process is gone, and the chip
        # with it, before the replica's worker starts
        served_on = serve_phase(plan, seed)
        same = ("platform", "kind", "count")
        if [trained_on[k] for k in same] != [served_on[k] for k in same]:
            raise SystemExit(f"trainer saw {trained_on}, server saw {served_on}")
        if trained_on["pid"] == served_on["pid"]:
            raise SystemExit("the train worker and the serve replica were one process")
        return served_on
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# four chips: the sharded step against the same step on one of them
# ---------------------------------------------------------------------------


def run_four_chips(plan: Plan, seed: int) -> Dict[str, Any]:
    import jax

    from ray_tpu._private import accelerator
    from ray_tpu.parallel.mesh import MeshSpec

    accelerator.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != plan.platform or len(devices) < 4:
        raise SystemExit(
            f"--chips 4 needs four {plan.platform} devices; jax sees "
            f"{len(devices)} x {devices[0].platform}"
        )
    devices = devices[:4]
    say(f"model: {describe(plan.train_cfg)}; batch {plan.batch}, {plan.steps} steps")

    def run(label, spec, devs):
        history = list(
            train_steps(plan.train_cfg, spec, devs, plan.batch, plan.steps, seed)
        )
        return check_training(plan, history, label), history[-1]

    sharded, summary = run(
        "fsdp2 x tp2 on four chips", MeshSpec(dp=-1, fsdp=2, tp=2), devices
    )
    single, _ = run("one chip", MeshSpec(), devices[:1])
    held = summary["param_bytes_per_device"]
    if not all(0 < b < sum(held.values()) / 2 for b in held.values()):
        raise SystemExit(f"parameters are not spread over the four devices: {held}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    say(
        f"per-step losses, four chips vs one: max relative difference {worst:.2e} "
        f"(tolerance {LOSS_RTOL:.2e}); compile cache {accelerator.compile_cache_stats()}"
    )
    if worst > LOSS_RTOL:
        raise SystemExit(f"losses disagree: {sharded} vs {single}")
    report = accelerator.device_report()
    report["count"] = len(devices)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import flax
    import jax
    import jaxlib
    import optax

    from ray_tpu._private.accelerator import compile_cache_dir

    say(
        f"python {sys.version.split()[0]}, jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, flax {flax.__version__}, optax {optax.__version__}; "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}; compile cache "
        f"{compile_cache_dir()} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})"
    )
    plan = chip_plan()
    say(f"cuts: {plan.cuts}")
    t0 = time.perf_counter()
    device = run_four_chips(plan, args.seed) if args.chips == 4 else run_one_chip(plan, args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {k: device[k] for k in ("platform", "kind", "count")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
