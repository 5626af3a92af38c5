"""Single-chip training benchmark: GPT tokens/sec and MFU on the real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline normalizes achieved MFU against the 40% north-star from
BASELINE.json (reference's GPT-J fine-tune target: ≥40% MFU on TPU).

Default flagship is the 1B-param config (head_dim=128 → full MXU tiles);
``--model 125m`` benches the small config. The train step runs the Pallas
flash-attention forward+backward kernels (ray_tpu/ops/attention.py) and the
blockwise cross-entropy (ray_tpu/models/gpt.py:blockwise_next_token_loss).

MFU accounting note (r5 sweep): train_step_flops counts attention as the
full 12·L·H·s²·d term (the PaLM-convention), but the Pallas kernel SKIPS
fully-masked causal tiles (attention.py:225), so full-counting overstates
utilization as seq grows — by ~4% at seq 2048 and ~35% at seq 16k (where
this formula would read 0.67 "MFU"). The flagship therefore stays at
seq 2048 / batch 12, where the conventions nearly agree AND the loss
trajectory is bit-comparable with earlier rounds (loss 0.8501 at iter 21).
r5 sweep results at this shape: batch 24 → 0.628; attn blocks 512 → 0.588
(kernel overhead beats the extra causal skip); remat=dots OOMs (saved dot
outputs exceed HBM at 1B/bf16); ce_chunk 1024 neutral. Long-context
throughput (the honest win of the flash kernel) is benched by
``--seq 16384 --batch 2`` explicitly, not by inflating the headline.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

# bf16 peak FLOP/s per chip, keyed by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s). A device that is not in the table
# is an error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 197e12}
TARGET_MFU = 0.40


def chip_peak_flops() -> float:
    """Peak of the chip this process runs on; raises off-chip and for a
    device kind the table does not know."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU and found platform {dev.platform!r} "
            f"({dev.device_kind}); a CPU run is not a device number"
        )
    if dev.device_kind not in PEAK_FLOPS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device kind {dev.device_kind!r}; "
            f"add it to PEAK_FLOPS with its source"
        )
    return PEAK_FLOPS[dev.device_kind]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="1b", choices=["1b", "125m"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument(
        "--remat-policy", default=None, choices=["nothing", "dots", "attn"]
    )
    ap.add_argument(
        "--scan-layers", default=None, choices=["on", "off"],
        help="force lax.scan over layers on/off (1b default: off/unrolled)",
    )
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--attn-block", type=int, default=None)
    args = ap.parse_args()

    from ray_tpu._private.accelerator import enable_compile_cache
    from ray_tpu.models.gpt import gpt_1b, gpt_125m, train_step_flops
    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )
    from ray_tpu.parallel.mesh import MeshSpec

    enable_compile_cache()
    peak = chip_peak_flops()
    extra = {}
    if args.remat_policy:
        extra["remat_policy"] = args.remat_policy
    if args.scan_layers is not None:
        extra["scan_layers"] = args.scan_layers == "on"
    if args.ce_chunk:
        extra["ce_chunk"] = args.ce_chunk
    if args.attn_block:
        extra["attn_block_q"] = args.attn_block
        extra["attn_block_k"] = args.attn_block
    if args.model == "1b":
        # bf16 params+moments so the full Adam state fits one 16G chip; a
        # real multi-chip run keeps f32 master state sharded over fsdp.
        # Tuned on v5e (r4 sweep): batch 12 + 1024x1024 flash tiles +
        # 512-row CE chunks + unrolled layers = 0.622 MFU vs 0.570 before.
        extra.setdefault("attn_block_q", 1024)
        extra.setdefault("attn_block_k", 1024)
        extra.setdefault("ce_chunk", 512)
        extra.setdefault("scan_layers", False)
        cfg = gpt_1b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **extra)
        batch, seq, iters = 12, 2048, 20
    else:
        cfg = gpt_125m(dtype=jnp.bfloat16, **extra)
        batch, seq, iters = 16, 2048, 30
    batch = args.batch or batch
    seq = args.seq or seq
    iters = args.iters or iters

    mesh = MeshSpec().build(jax.devices()[:1])
    opt = default_optimizer(learning_rate=1e-4)
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(0), (batch, seq)
    )
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)

    import numpy as np

    with mesh:
        state, m = step(state, tokens)  # compile + warmup
        float(np.asarray(m["loss"]))  # fetching the loss waits for the step
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, tokens)
        # the final loss depends on every preceding step, so fetching it
        # synchronizes the whole chain
        final_loss = float(np.asarray(m["loss"]))
        dt = time.perf_counter() - t0

    tokens_per_s = batch * seq * iters / dt
    flops = train_step_flops(cfg, batch, seq) * iters / dt
    mfu = flops / peak
    print(
        json.dumps(
            {
                "metric": f"gpt{args.model}_train_tokens_per_sec_chip",
                "value": round(tokens_per_s, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / TARGET_MFU, 4),
                "mfu": round(mfu, 4),
                "platform": "tpu",
                "device_kind": jax.devices()[0].device_kind,
                "loss": round(final_loss, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
