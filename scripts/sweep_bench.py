"""Ad-hoc sweep: model size × batch × flash block sizes on the real chip."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import chip_peak_flops
from ray_tpu.models.gpt import gpt_125m, gpt_1b, train_step_flops
from ray_tpu.models.training import (
    default_optimizer,
    init_sharded_state,
    make_train_step,
)
from ray_tpu.parallel.mesh import MeshSpec


def run(cfg_name, batch, seq, iters=10):
    cfg = {"125m": gpt_125m, "1b": gpt_1b}[cfg_name](
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16
    )
    mesh = MeshSpec().build(jax.devices()[:1])
    opt = default_optimizer(learning_rate=1e-4)
    state, shardings = init_sharded_state(cfg, mesh, opt, jax.random.PRNGKey(0), (batch, seq))
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)
    with mesh:
        state, m = step(state, tokens)
        float(np.asarray(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, tokens)
        float(np.asarray(m["loss"]))
        dt = time.perf_counter() - t0
    flops = train_step_flops(cfg, batch, seq) * iters / dt
    print(f"{cfg_name} b={batch} seq={seq}: {batch*seq*iters/dt:.0f} tok/s  mfu={flops/chip_peak_flops():.4f}", flush=True)


if __name__ == "__main__":
    for name, b in [("1b", 4), ("1b", 8), ("1b", 16)]:
        try:
            run(name, b, 2048)
        except Exception as e:
            print(f"{name} b={b}: FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)
