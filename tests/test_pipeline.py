"""Pipeline parallelism: GPipe microbatching over the pp mesh axis.

Exactness is checked against the non-pipelined scanned-blocks model on the
same parameters (the reference delegates PP to Alpa — release/alpa_tests —
so the parity bar here is numerical agreement with our own dense path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.gpt import GPT, blockwise_next_token_loss, gpt_nano
from ray_tpu.models.training import (
    TrainState,
    default_optimizer,
    init_params,
    make_train_step,
)
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.pipeline import make_pp_train_step, pipeline_apply, stage_split

def _nano():
    # float32 + no remat noise; 4 layers so pp=2 gives 2 layers/stage
    import dataclasses

    return dataclasses.replace(gpt_nano(remat=False), num_layers=4)


def test_stage_split_shapes():
    tree = {"w": jnp.zeros((4, 3, 5))}
    out = stage_split(tree, 2)
    assert out["w"].shape == (2, 2, 3, 5)
    with pytest.raises(ValueError):
        stage_split({"w": jnp.zeros((3, 2))}, 2)


def test_pipeline_apply_matches_sequential():
    """A toy stacked-linear network: pipelined output == sequential scan."""
    mesh = MeshSpec(dp=2, pp=4).build()
    L, D, M, mb = 8, 16, 4, 2
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    def layer_apply(lp, h):
        return jnp.tanh(h @ lp)

    # sequential reference
    def seq(x_flat):
        h = x_flat
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return h

    expected = seq(x.reshape(M * mb, D)).reshape(M, mb, D)
    got = pipeline_apply(mesh, layer_apply, stage_split(w, 4), x, remat=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-5)


def test_pipeline_apply_gradients_match():
    mesh = MeshSpec(dp=2, pp=4).build()
    L, D, M, mb = 4, 8, 4, 2
    w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    def layer_apply(lp, h):
        return jnp.tanh(h @ lp)

    def loss_pp(w_):
        y = pipeline_apply(mesh, layer_apply, stage_split(w_, 4), x, remat=False)
        return (y**2).sum()

    def loss_seq(w_):
        h = x.reshape(M * mb, D)
        for i in range(L):
            h = jnp.tanh(h @ w_[i])
        return (h**2).sum()

    g_pp = jax.grad(loss_pp)(w)
    g_seq = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq), atol=1e-4)


def test_pp_train_step_matches_dense():
    """Full pipelined GPT train step: loss equals the non-pipelined step."""
    cfg = _nano()
    mesh = MeshSpec(dp=2, pp=2, tp=2).build()
    params = init_params(cfg, jax.random.PRNGKey(0), (1, 32))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(4, 32)
    ).astype(np.int32)

    optimizer = default_optimizer(1e-3)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
    )

    # dense loss on the same params (no mesh: plain jit path)
    model = GPT(cfg, return_hidden=True)
    hidden, kernel, bias = model.apply({"params": params}, jnp.asarray(tokens))
    dense_loss = float(blockwise_next_token_loss(hidden, kernel, bias, jnp.asarray(tokens)))

    pp_step = make_pp_train_step(
        cfg, optimizer, mesh, num_microbatches=2, donate=False
    )
    new_state, metrics = pp_step(state, jnp.asarray(tokens))
    assert abs(float(metrics["loss"]) - dense_loss) < 1e-3, (
        float(metrics["loss"]),
        dense_loss,
    )
    assert int(new_state.step) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), params, new_state.params
    )
    assert max(jax.tree.leaves(moved)) > 0.0


def test_multislice_mesh_train_step():
    """num_slices=2 hybrid mesh: dp spans the DCN axis; a dpxfsdp train
    step runs across the slice boundary (SURVEY §2.6 collective-backend
    row; on CPU fixtures the slice split is emulated by reshape)."""
    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )

    cfg = _nano()
    mesh = MeshSpec(dp=2, fsdp=-1, num_slices=2).build()
    assert int(mesh.shape["dp"]) == 2
    opt = default_optimizer(1e-3)
    batch, seq = 8, 32
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(0), (batch, seq)
    )
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    with mesh:
        state, metrics = step(state, tokens)
    assert float(metrics["loss"]) > 0.0


def test_pp_composes_with_fsdp_tp():
    """pp x fsdp x tp on one mesh: state sharded at rest over all three
    axes via shd.pp_rules, loss finite and step runs (VERDICT r2 weak #4)."""
    from ray_tpu.models.training import default_optimizer, init_sharded_state
    from ray_tpu.parallel import sharding as shd

    cfg = _nano()
    mesh = MeshSpec(pp=2, fsdp=2, tp=2).build()
    opt = default_optimizer(1e-3)
    rules = shd.pp_rules()
    batch, seq = 4, 32
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(0), (batch, seq), rules=rules
    )
    # the stacked layer axis must actually be sharded over pp at rest
    qk = state.params["blocks"]["layers"]["attn"]["q"]["kernel"]
    assert "pp" in str(qk.sharding.spec)
    step = make_pp_train_step(
        cfg, opt, mesh, num_microbatches=2, rules=rules,
        state_shardings_tree=shardings,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    with mesh:
        state, metrics = step(state, tokens)
    assert float(metrics["loss"]) > 0.0
