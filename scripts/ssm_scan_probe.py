"""The trained Mamba-2 scan alone, on the chip: ``granitemoehybrid.ssm_scan`` (the
kernel pair) against ``ssm_chunked`` (XLA's loop, what the kernels replace) at
Nemotron-3-Nano's widths, one layer of ``[lanes, tokens]``: how far the two are apart
(``y``, the last state, the gradient of every input, as a share of the loop's largest)
and the milliseconds a forward and a forward-and-backward of each take.

    python3 scripts/ssm_scan_probe.py [--lanes 2] [--tokens 8192] [--seed 0] [--repeats 10]
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models.granitemoehybrid import ssm_chunked, ssm_scan

HEADS, P, N, GROUPS, CHUNK = 64, 64, 128, 8, 128


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=2)
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=10)
    args = parser.parse_args()
    dtype, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    shape = (args.lanes, args.tokens)
    x = jax.random.normal(keys[0], shape + (HEADS, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], shape + (HEADS,)) - 3.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), f32, 0.0, 2.77))
    b, c = (jax.random.normal(k, shape + (GROUPS, N)).astype(dtype) for k in keys[3:5])
    state = 0.1 * jax.random.normal(keys[5], (args.lanes, HEADS, P, N))
    weigh = jax.random.normal(keys[6], shape + (HEADS, P))
    weigh_last = jax.random.normal(keys[7], state.shape)
    operands = (state, x, dt, a, b, c)

    def loop(*v):
        return ssm_chunked(*v, CHUNK, dtype)[:2]

    def kernels(*v):
        return ssm_scan(*v, CHUNK, dtype)

    def scalar(fn):
        def loss(*v):
            y, last = fn(*v)
            return (y * weigh).sum() + (last * weigh_last).sum()
        return loss

    print(f"device {jax.devices()[0].device_kind}; x {x.shape} {x.dtype}, b {b.shape}")
    results = {}
    for name, fn in (("loop", loop), ("kernels", kernels)):
        forward, both = jax.jit(fn), jax.jit(jax.grad(scalar(fn), argnums=range(6)))
        results[name] = jax.block_until_ready((forward(*operands), both(*operands)))
        for what, call in (("forward", forward), ("forward+backward", both)):
            taken = []
            for _ in range(args.repeats):
                start = time.perf_counter()
                jax.block_until_ready(call(*operands))
                taken.append(time.perf_counter() - start)
            taken.sort()
            print(f"{name:8s} {what:17s} median {1e3 * taken[len(taken) // 2]:8.3f} ms  "
                  f"least {1e3 * taken[0]:8.3f} ms")
    (y0, last0), grads0 = results["loop"]
    (y1, last1), grads1 = results["kernels"]
    names = ("y", "last", "dstate", "dx", "ddt", "da", "db", "dc")
    for name, want, got in zip(names, (y0, last0, *grads0), (y1, last1, *grads1)):
        want, got = want.astype(f32), got.astype(f32)
        print(f"{name:7s} largest {float(jnp.abs(want).max()):.4e}  apart "
              f"{float(jnp.abs(got - want).max() / jnp.abs(want).max()):.3e} of it")


if __name__ == "__main__":
    main()
