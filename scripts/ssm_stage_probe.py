"""The two pointwise stages of a trained Mamba-2 mixer alone, on the chip: the head
(``granitemoehybrid.ssm_conv``: four taps, a bias, ``silu``, the split into ``x``, ``B``,
``C``) and the tail (``ssm_gate_norm``: ``D x``, the gate, the grouped norm) of one layer
at Nemotron-3-Nano's widths, each as its ``jax.numpy`` lines under plain autodiff (what
runs off the TPU, and ran on it before PR 68) against its kernel pair: how far the two are
apart (the value and the gradient of every input, as a share of the ``jax.numpy`` form's
largest) and the milliseconds a forward and a backward of each take: on the host's
clock, the calls in a row, and as the device's self time by instruction from a trace. Both
forms take ``z`` and ``xbc`` out of the in-projection's whole result, as the mixer does:
the ``jax.numpy`` form as slices its fusions read, the kernels as columns they read in
place (``within=``).
(``scripts/ssm_scan_probe.py`` does the same for the scan between them.)

    python3 scripts/ssm_stage_probe.py [--lanes 2] [--tokens 8192] [--seed 0] [--repeats 10]
        [--rows 512] [--sub 16] [--tiny]

``--tiny`` rehearses it off the chip: small widths, the kernels interpreted.
"""

import argparse
import glob
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
from ray_tpu.models.granitemoehybrid import ssm_conv, ssm_gate_norm
from ray_tpu.ops import backend

HEADS, P, N, GROUPS, TAPS, EPS = 64, 64, 128, 8, 4, 1e-5


def device_self_time(run, repeats):
    """Milliseconds a call of the device's self time by instruction, from a trace of
    ``repeats`` calls of ``run`` one after another."""
    where = tempfile.mkdtemp(prefix="ssm_stage_probe_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    for _ in range(repeats):
        run()
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(where, "**", "*.xplane.pb"), recursive=True)
    planes = trace_reduce.load_xplane(paths[0]) if paths else {}
    shutil.rmtree(where, ignore_errors=True)
    totals = {}
    for plane, lines in planes.items():
        if trace_reduce.DEVICE_PLANE.match(plane):
            for name, a, b in trace_reduce.self_segments(lines.get(trace_reduce.OPS_LINE, [])):
                totals[name] = totals.get(name, 0.0) + (b - a) / repeats / 1e6
    return totals


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=2)
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--sub", type=int, default=None)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    heads, p, n, groups = (8, 8, 16, 4) if args.tiny else (HEADS, P, N, GROUPS)
    tokens = 64 if args.tiny else args.tokens
    dtype, f32 = jnp.bfloat16, jnp.float32
    inner, part = heads * p, groups * n
    channels, shape = inner + 2 * part, (args.lanes, tokens)
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 16))
    blocks = {name: getattr(args, name) for name in ("rows", "sub") if getattr(args, name)}
    if args.tiny:
        blocks = {"rows": 32, "sub": 16, **blocks}

    def normal(*dims):
        return jax.random.normal(next(keys), dims)

    proj = normal(*shape, inner + channels + heads).astype(dtype)   # z, xbc, dt: the in-projection's result
    bound = TAPS ** -0.5
    taps = jax.random.uniform(next(keys), (TAPS, channels), f32, -bound, bound)
    bias = jax.random.uniform(next(keys), (channels,), f32, -bound, bound)
    y, x = normal(*shape, inner), normal(*shape, inner).astype(dtype)
    skip, scale = 1.0 + 0.1 * normal(heads), (1.0 + 0.1 * normal(inner)).astype(dtype)
    weigh = [normal(*shape, width) for width in (inner, part, part)]

    def head(kernels):
        def loss(proj, taps, bias):
            xbc = proj[..., inner:inner + channels]
            parts = ssm_conv(xbc, taps, bias, inner, **(
                dict(blocks, interpret=args.tiny, within=(proj, inner)) if kernels else {}))
            return sum((got.astype(f32) * w).sum() for got, w in zip(parts, weigh)), parts
        return loss, (proj, taps, bias), ("x", "b", "c", "dproj", "dtaps", "dbias")

    def tail(kernels):
        def loss(y, x, proj, skip, scale):
            out = ssm_gate_norm(
                y, x, proj[..., :inner], skip, scale, groups, EPS,
                **(dict(blocks, interpret=args.tiny, within=(proj, 0)) if kernels else {}))
            return (out.astype(f32) * weigh[0]).sum(), (out,)
        return loss, (y, x, proj, skip, scale), ("out", "dy", "dx", "dproj", "dD", "dscale")

    print(f"device {jax.devices()[0].device_kind}; proj {proj.shape} {proj.dtype}, blocks {blocks or 'default'}")
    on_tpu = backend.on_tpu
    for stage in (head, tail):
        results = {}
        for form, kernels in (("jnp", False), ("kernels", True)):
            # the jax.numpy form is what the functions run off the TPU: answer for the probe
            backend.on_tpu = (lambda: False) if not kernels else on_tpu
            loss, operands, names = stage(kernels)
            forward = jax.jit(lambda *v: loss(*v)[1])
            # a backward alone: the loss is linear in the results, so it needs no forward
            both = jax.jit(jax.grad(lambda *v: loss(*v)[0], argnums=tuple(range(len(operands)))))
            results[form] = jax.block_until_ready((forward(*operands), both(*operands)))
            for what, call in (("forward", forward), ("backward", both)):
                start = time.perf_counter()
                for _ in range(args.repeats):
                    out = call(*operands)
                jax.block_until_ready(out)
                host = (time.perf_counter() - start) / args.repeats
                device = device_self_time(lambda: jax.block_until_ready(call(*operands)), args.repeats)
                print(f"{stage.__name__} {form:8s} {what:9s} host {1e3 * host:7.3f} ms a call in a row; device "
                      f"{sum(device.values()):7.3f} ms: " + ", ".join(
                          f"{name} {t:.3f}" for name, t in sorted(device.items(), key=lambda kv: -kv[1])[:6]))
        backend.on_tpu = on_tpu
        flat = {form: (*values, *grads) for form, (values, grads) in results.items()}
        for name, want, got in zip(names, flat["jnp"], flat["kernels"]):
            want, got = want.astype(f32), got.astype(f32)
            print(f"{stage.__name__} {name:7s} largest {float(jnp.abs(want).max()):.4e}  apart "
                  f"{float(jnp.abs(got - want).max() / jnp.abs(want).max()):.3e} of it")


if __name__ == "__main__":
    main()
