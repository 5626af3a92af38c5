"""Ring / Ulysses attention exactness on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import _attention_xla
from ray_tpu.ops.ring import mesh_attention


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _mesh(sp, tp=1, dp=1):
    devs = np.array(jax.devices()[: dp * tp * sp]).reshape(dp, 1, tp, sp)
    return Mesh(devs, ("dp", "fsdp", "tp", "sp"))


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("sp", [2, 4])
def test_seq_parallel_matches_dense(impl, sp):
    q, k, v = (_rand((2, 4, 64, 32), s) for s in (0, 1, 2))
    ref = _attention_xla(q, k, v, causal=True)
    mesh = _mesh(sp)
    out = jax.jit(
        lambda q, k, v: mesh_attention(q, k, v, mesh, impl=impl)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_seq_parallel_grads_match_dense(impl):
    q, k, v = (_rand((1, 4, 64, 32), s) for s in (0, 1, 2))
    mesh = _mesh(sp=4)

    def loss_sp(q, k, v):
        o = mesh_attention(q, k, v, mesh, impl=impl)
        return jnp.sum(o * jnp.sin(o))

    def loss_ref(q, k, v):
        o = _attention_xla(q, k, v, causal=True)
        return jnp.sum(o * jnp.sin(o))

    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_sp, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3, err_msg=f"d{name}"
        )


def test_seq_parallel_with_tp_and_dp():
    # combined dp=2, tp=2, sp=2 on 8 devices: batch, heads and seq all sharded
    q, k, v = (_rand((4, 4, 32, 16), s) for s in (0, 1, 2))
    ref = _attention_xla(q, k, v, causal=True)
    mesh = _mesh(sp=2, tp=2, dp=2)
    out = jax.jit(
        lambda q, k, v: mesh_attention(q, k, v, mesh, impl="ring")
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_gpt_with_ring_matches_dense():
    """Full model: sp=2 sharded train-step loss == single-device loss."""
    from ray_tpu.models.gpt import GPT, gpt_nano
    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )
    from ray_tpu.parallel.mesh import MeshSpec

    cfg = gpt_nano(seq_parallel_impl="ring")
    batch, seq = 2, 64
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab_size)
    opt = default_optimizer(learning_rate=1e-3)

    # dense single-device baseline
    mesh1 = MeshSpec().build(jax.devices()[:1])
    state1, sh1 = init_sharded_state(cfg, mesh1, opt, jax.random.PRNGKey(1), (batch, seq))
    step1 = make_train_step(cfg, opt, mesh1, state_shardings_tree=sh1)
    with mesh1:
        _, m1 = step1(state1, tokens)

    # sp=2 ring-attention mesh
    spec = MeshSpec(dp=1, fsdp=1, sp=2, tp=2)
    mesh2 = spec.build(jax.devices()[:4])
    state2, sh2 = init_sharded_state(cfg, mesh2, opt, jax.random.PRNGKey(1), (batch, seq))
    step2 = make_train_step(cfg, opt, mesh2, state_shardings_tree=sh2)
    with mesh2:
        _, m2 = step2(state2, tokens)

    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=2e-4,
        err_msg="sp=2 ring loss diverges from dense loss",
    )
