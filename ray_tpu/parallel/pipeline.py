"""Pipeline parallelism: GPipe-style microbatching over the ``pp`` mesh axis.

The reference has no native pipeline parallelism — it delegates inter-op
parallelism to Alpa running inside Ray tasks (reference: release/alpa_tests/
train_opt_2_7b_minimum.py, release/release_tests.yaml:3364-3401). The
TPU-native design makes PP a first-class mesh axis instead: transformer
layers are split into S contiguous stages, the stacked layer parameters are
sharded over ``pp`` (leading axis), and a `shard_map` program streams M
microbatches through the stages with `lax.ppermute` hops between ICI
neighbors. Reverse-mode AD through the scan+ppermute program *is* the
backward pipeline (the transpose of a ppermute is the inverse ppermute), so
one forward definition yields the full fwd+bwd schedule with
(S-1)/(M+S-1) bubble overhead — the GPipe schedule, compiler-scheduled.

Composes with dp/fsdp (microbatch dim sharded over them); tp/sp inside a
stage compose at the XLA level when the stage matmuls carry sharding
constraints — the canonical mesh order (parallel/mesh.py AXIS_ORDER) keeps
pp hops on ICI neighbors.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stage_split(tree: Any, num_stages: int) -> Any:
    """Reshape stacked-layer params [num_layers, ...] → [S, L/S, ...]."""

    def _split(p):
        n = p.shape[0]
        if n % num_stages:
            raise ValueError(
                f"num_layers={n} not divisible by pp={num_stages}"
            )
        return p.reshape((num_stages, n // num_stages) + p.shape[1:])

    return jax.tree.map(_split, tree)


def pipeline_apply(
    mesh: Mesh,
    layer_apply: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_mb: jax.Array,
    *,
    remat: bool = True,
) -> jax.Array:
    """Stream microbatches through pipeline stages on the ``pp`` mesh axis.

    Args:
      mesh: the device mesh; its ``pp`` axis size is the stage count S.
      layer_apply: ``(layer_params, x) -> x`` for ONE layer (leaves of
        ``stage_params`` minus the two leading [S, L] axes).
      stage_params: pytree with leaves ``[S, L, ...]`` (see `stage_split`).
      x_mb: microbatched activations — an array or pytree of arrays, every
        leaf ``[M, mb, ...]``; the microbatch dim is sharded over
        (dp, fsdp), the stream dim M is replicated.
    Returns:
      Same pytree structure, outputs of the final stage (replicated on pp).
    """
    S = int(mesh.shape.get("pp", 1))
    M = jax.tree.leaves(x_mb)[0].shape[0]
    if S == 1:
        def _stack(params, x):
            def body(carry, lp):
                return layer_apply(lp, carry), None
            flat = jax.tree.map(lambda p: p.reshape((-1,) + p.shape[2:]), params)
            out, _ = lax.scan(body, x, flat)
            return out
        return _stack(stage_params, x_mb)

    if remat:
        layer_apply = jax.checkpoint(layer_apply)

    # Partial-manual shard_map: only ``pp`` is a manual axis (the ppermute
    # ring), every other mesh axis stays GSPMD-auto, so the tensor/fsdp/
    # sequence shardings carried by the layer's own constraint annotations
    # compose with the pipeline instead of being erased — specs therefore
    # mention only the pp placement of each operand.
    mb_spec = jax.tree.map(lambda _: P(), x_mb)  # replicated over pp
    param_spec = jax.tree.map(lambda _: P("pp"), stage_params)

    def per_stage(params, x):
        # params leaves [1, L, ...] (this stage's slice); x leaves [M, mb', ...]
        params = jax.tree.map(lambda p: p[0], params)
        stage = lax.axis_index("pp")

        def stage_fn(act):
            def body(carry, lp):
                return layer_apply(lp, carry), None
            out, _ = lax.scan(body, act, params)
            return out

        def tree_index(buf, i):
            return jax.tree.map(
                lambda b: lax.dynamic_index_in_dim(b, i, axis=0, keepdims=False),
                buf,
            )

        def tree_select(pred, a, b):
            return jax.tree.map(lambda u, v: jnp.where(pred, u, v), a, b)

        zero = jax.tree.map(lambda b: jnp.zeros(b.shape[1:], b.dtype), x)
        # stage i sends its output to stage i+1; the last stage's output
        # falls off the end (collected into out_buf instead)
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            prev_out, out_buf = carry
            arriving = jax.tree.map(
                lambda b: lax.ppermute(b, "pp", perm), prev_out
            )
            first_in = tree_index(x, jnp.clip(t, 0, M - 1))
            my_in = tree_select(stage == 0, first_in, arriving)
            y = stage_fn(my_in)
            out_t = t - (S - 1)
            safe = jnp.clip(out_t, 0, M - 1)
            cur = tree_index(out_buf, safe)
            write = jnp.logical_and(out_t >= 0, stage == S - 1)
            new = tree_select(write, y, cur)
            out_buf = jax.tree.map(
                lambda b, v: lax.dynamic_update_index_in_dim(b, v, safe, axis=0),
                out_buf,
                new,
            )
            return (y, out_buf), None

        # the carry becomes pp-varying inside the loop (each stage computes
        # its own activations); mark the zero init accordingly for vma
        def _varying(t):
            return jax.tree.map(
                lambda v: lax.pcast(v, ("pp",), to="varying"), t
            )

        init = (_varying(zero), _varying(jax.tree.map(jnp.zeros_like, x)))
        (_, out_buf), _ = lax.scan(tick, init, jnp.arange(M + S - 1))
        # result lives on the last stage only; replicate it over pp
        return jax.tree.map(
            lambda b: lax.psum(jnp.where(stage == S - 1, b, 0), "pp"), out_buf
        )

    # vma checking must stay ON: with it off, partial-manual mode
    # requires every mesh axis in out_specs (defeating auto sharding)
    return shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(param_spec, mb_spec),
        out_specs=mb_spec,
        axis_names=frozenset({"pp"}),
    )(stage_params, x_mb)


def make_pp_train_step(
    cfg,
    optimizer,
    mesh: Mesh,
    *,
    num_microbatches: int = 4,
    donate: bool = True,
    rules=None,
    state_shardings_tree: Any = None,
) -> Callable:
    """Pipelined GPT train step: embed → pipelined blocks → blockwise loss.

    The embedding/final-norm/lm-head run outside the shard_map (replicated
    over pp, sharded over dp/fsdp/tp via the usual logical rules); only the
    homogeneous transformer stack is pipelined. pp composes with fsdp/tp:
    the shard_map is manual over ``pp`` alone, so the Block's logical-axis
    constraints (heads/mlp → tp, embed → fsdp) shard each stage's compute
    under GSPMD exactly as in the non-pipelined step. Pass
    ``state_shardings_tree`` from ``init_sharded_state(..., rules=
    shd.pp_rules())`` so params/opt-state are pp×fsdp×tp sharded at rest.
    Requires ``num_layers % pp == 0`` (the stacked [num_layers, ...] block
    params are split over the stages).
    """
    import flax.linen as nn
    import optax

    from ray_tpu.models.gpt import Block, blockwise_next_token_loss
    from ray_tpu.models.training import TrainState
    from ray_tpu.parallel import sharding as shd

    S = int(mesh.shape.get("pp", 1))
    block = Block(cfg)
    active_rules = list(rules if rules is not None else shd.pp_rules())

    def layer_apply(layer_params, xp):
        x, positions = xp
        y = block.apply({"params": layer_params}, x, positions)
        return (y, positions)

    def _loss_fn(params, tokens):
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )
        x = params["wte"]["embedding"].astype(cfg.dtype)[tokens]
        b, t, d = x.shape
        M = num_microbatches
        if b % M:
            raise ValueError(f"batch {b} not divisible by microbatches {M}")
        mb = b // M
        stacked = stage_split(params["blocks"]["layers"], S)
        x_mb = x.reshape(M, mb, t, d)
        pos_mb = positions.reshape(M, mb, t)
        y_mb, _ = pipeline_apply(
            mesh,
            layer_apply,
            stacked,
            (x_mb, pos_mb),
            remat=cfg.remat,
        )
        y = y_mb.reshape(b, t, d)
        ln = params["ln_f"]
        mean = y.mean(-1, keepdims=True)
        var = ((y - mean) ** 2).mean(-1, keepdims=True)
        y = (y - mean) * lax.rsqrt(var + 1e-6)
        y = y * ln["scale"].astype(y.dtype) + ln["bias"].astype(y.dtype)
        head = params["lm_head"]
        return blockwise_next_token_loss(y, head["kernel"], head["bias"], tokens)

    def loss_fn(params, tokens):
        # install the logical rule table so Block's with_logical_constraint
        # calls shard stage-internal matmuls over tp/fsdp (silent no-ops
        # without rules — then pp would run unsharded stages)
        with nn.logical_axis_rules(active_rules):
            return _loss_fn(params, tokens)

    def step(state: TrainState, tokens: jax.Array):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "step": state.step + 1}
        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            metrics,
        )

    kwargs = {}
    if state_shardings_tree is not None:
        data_sharding = shd.batch_sharding(mesh, ndim=2, rules=active_rules)
        kwargs["in_shardings"] = (state_shardings_tree, data_sharding)
        kwargs["out_shardings"] = (
            state_shardings_tree,
            NamedSharding(mesh, P()),
        )
    return jax.jit(step, donate_argnums=(0,) if donate else (), **kwargs)
