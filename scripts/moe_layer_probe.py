"""One trained expert layer alone, on the chip: ``moe.trained_experts_ffn`` at a train
cell's widths under the remat its model gives it (both grouped matmuls' results kept by
name), value and the gradients of the tokens, the weights and both expert stacks, traced:
the milliseconds a step, and the device's self time **by instruction**, each with the
operation it was traced under. What the cell's own trace sums under one scope
(``train.moe.experts``) is told apart here: the gather, the activation, the combine,
the casts, the loops, the kernels.

    python3 scripts/moe_layer_probe.py [--cell nemotron|lfm2] [--seed 0] [--steps 5] [--top 40]

Runs in any checkout that has ``ray_tpu`` and ``benchmark`` (copy it into a parent's
``scripts/`` to read the parent's layer).
"""

import argparse
import glob
import inspect
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce
from ray_tpu.models import moe

# tokens, choices a token, model width, expert width, experts held, experts routed, gated
CELLS = {
    "nemotron": (16384, 6, 2688, 1856, 16, 128, False),
    "lfm2": (16384, 4, 2048, 1536, 32, 64, True),
    "tiny": (256, 2, 128, 128, 2, 8, False),         # a rehearsal off the chip
}
ANNOTATION = "probe.step"


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", choices=list(CELLS), default="nemotron")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--top", type=int, default=40)
    args = parser.parse_args()
    n, k, d, f, held, routed, gated = CELLS[args.cell]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    x = jax.random.normal(keys[0], (n, d)).astype(jnp.bfloat16)
    scores = jax.random.uniform(keys[1], (n, routed))
    weights, experts = jax.lax.top_k(scores, k)
    wi = (0.02 * jax.random.normal(keys[2], (held, d, (2 if gated else 1) * f))).astype(jnp.bfloat16)
    wo = (0.02 * jax.random.normal(keys[3], (held, f, d))).astype(jnp.bfloat16)
    up = jax.random.normal(keys[4], (n, d))
    activation = moe.gated_silu if gated else moe.relu_squared
    said = {"routed": routed} if "routed" in inspect.signature(moe.trained_experts_ffn).parameters else {}

    def layer(x, weights, wi, wo):
        with jax.named_scope("train.moe.experts"):
            return moe.trained_experts_ffn(
                x, weights, experts.astype(jnp.int32), wi, wo, activation=activation, **said)

    kept = jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(*moe.TRAINED_RESIDUALS))

    def loss(x, weights, wi, wo):
        y, counters = kept(x, weights, wi, wo)
        return (y * up).sum(), counters

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))
    (value, counters), grads = jax.block_until_ready(step(x, weights, wi, wo))
    print(f"device {jax.devices()[0].device_kind}; cell {args.cell}: n {n} k {k} d {d} f {f}, "
          f"{held} of {routed} experts held; counters {dict(zip(moe.TRAINED_COUNTERS, map(int, counters)))}; "
          f"loss {float(value):.6e}; gradients finite "
          f"{all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads)}")
    taken = []
    for _ in range(args.steps):
        start = time.perf_counter()
        jax.block_until_ready(step(x, weights, wi, wo))
        taken.append(time.perf_counter() - start)
    print(f"host clock: median {1e3 * sorted(taken)[len(taken) // 2]:.3f} ms a step")

    where = tempfile.mkdtemp(prefix="moe_layer_probe_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    for _ in range(args.steps):
        with jax.profiler.TraceAnnotation(ANNOTATION):
            jax.block_until_ready(step(x, weights, wi, wo))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(where, "**", "*.xplane.pb"), recursive=True)
    planes, scopes = trace_reduce.load_xplane(path), trace_reduce.load_scopes(path)
    shutil.rmtree(where, ignore_errors=True)
    names = {}
    for module in scopes.values():
        names.update(module)
    totals = {}
    for plane, lines in planes.items():
        if not trace_reduce.DEVICE_PLANE.match(plane):
            continue
        for name, a, b in trace_reduce.self_segments(lines.get(trace_reduce.OPS_LINE, [])):
            totals[name] = totals.get(name, 0.0) + (b - a)
    # every traced step is whole: the steps run one after another and nothing else does
    per_step = {name: t / args.steps / 1e6 for name, t in totals.items()}
    print(f"device self time: {sum(per_step.values()):.3f} ms a step over {len(per_step)} instructions")
    by_kernel = {}
    for name, t in per_step.items():
        by_kernel[trace_reduce.kernel_of(name)] = by_kernel.get(trace_reduce.kernel_of(name), 0.0) + t
    print("by kernel:", ", ".join(
        f"{name} {t:.3f}" for name, t in sorted(by_kernel.items(), key=lambda kv: -kv[1])[:16]))
    for name, t in sorted(per_step.items(), key=lambda kv: -kv[1])[:args.top]:
        print(f"{t:8.3f} ms  {name:34s} {'/'.join(names.get(name, '').split('/')[-3:])}")


if __name__ == "__main__":
    main()
