"""Sharded train/eval step factories for the model family.

This is the TPU-native replacement for the reference's per-framework trainer
backends (reference: python/ray/train/torch/config.py:69 process-group setup
+ train_loop_utils.py:75 DDP wrap): instead of wrapping a module per
strategy, we jit one functional train step whose in/out shardings are derived
from the model's logical axis annotations and a rule table. XLA inserts the
psum/all-gather/reduce-scatter collectives implied by the shardings, so the
same step is DP, FSDP, TP, SP or any mix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu._private import accelerator
from ray_tpu.models.gpt import GPT, GPTConfig, blockwise_next_token_loss
from ray_tpu.parallel import sharding as shd


@dataclasses.dataclass
class TrainState:
    """Minimal functional train state (a pytree)."""

    step: jax.Array
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def default_optimizer(
    learning_rate: float = 1e-4, weight_decay: float = 0.0, grad_clip: float = 1.0
) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def _trained(params: Any, buffers: Tuple[str, ...]) -> Tuple[Any, Any]:
    """``params`` without and with only the top-level entries a model names as
    its buffers (``TrainModel.buffers``): what the optimizer sees, and what a
    step hands on as it is."""
    if not buffers:
        return params, {}
    return (
        {k: v for k, v in params.items() if k not in buffers},
        {k: params[k] for k in buffers},
    )


def abstract_state(
    cfg: Any, optimizer: optax.GradientTransformation, sample_tokens: jax.ShapeDtypeStruct
):
    """Eval-shape the init to get the (boxed) abstract state without FLOPs.
    ``cfg`` is any configuration that answers ``train_model`` (``gpt.TrainModel``)."""
    model = cfg.train_model()

    def _init(rng):
        params = model.init(rng, jnp.zeros(sample_tokens.shape, jnp.int32))
        opt_state = optimizer.init(nn.meta.unbox(_trained(params, model.buffers)[0]))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    return _init, jax.eval_shape(_init, jax.random.PRNGKey(0))


def state_shardings(
    mesh: Mesh, abstract: Any, rules: Optional[shd.Rules] = None
) -> Any:
    """NamedShardings for a TrainState with flax-Partitioned param leaves.

    Optimizer moments mirror the param shardings (ZeRO-style: the fsdp axis
    shards both, cf. the reference's delegation of this to DeepSpeed —
    SURVEY.md §2.6 FSDP row).
    """
    param_shardings = shd.params_shardings(mesh, abstract.params, rules)
    flat_params = jax.tree_util.tree_leaves_with_path(param_shardings)
    by_path = {jax.tree_util.keystr(p): s for p, s in flat_params}

    def _opt_leaf(path, leaf):
        key = jax.tree_util.keystr(path)
        for ppath, s in by_path.items():
            if key.endswith(ppath):
                return s
        return NamedSharding(mesh, PartitionSpec())

    opt_shardings = jax.tree_util.tree_map_with_path(_opt_leaf, abstract.opt_state)
    return TrainState(
        step=NamedSharding(mesh, PartitionSpec()),
        params=param_shardings,
        opt_state=opt_shardings,
    )


def init_sharded_state(
    cfg: Any,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    batch_shape: Tuple[int, int],
    rules: Optional[shd.Rules] = None,
) -> Tuple[TrainState, Any]:
    """Initialize the train state directly into its target shardings (each
    device materializes only its shard — required for >HBM models)."""
    sample = jax.ShapeDtypeStruct(batch_shape, jnp.int32)
    init_fn, abstract = abstract_state(cfg, optimizer, sample)
    shardings = state_shardings(mesh, abstract, rules)
    unboxed_shardings = nn.meta.unbox(shardings)

    @functools.partial(jax.jit, out_shardings=unboxed_shardings)
    def _sharded_init(rng):
        state = init_fn(rng)
        return TrainState(
            step=state.step, params=nn.meta.unbox(state.params), opt_state=state.opt_state
        )

    with mesh:
        state = _sharded_init(rng)
    return state, unboxed_shardings


def make_train_step(
    cfg: Any,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    rules: Optional[shd.Rules] = None,
    state_shardings_tree: Any = None,
    donate: bool = True,
) -> Callable:
    """Build `step(state, tokens) -> (state, metrics)`, jitted with shardings.

    ``cfg`` is any configuration that answers ``train_model(mesh)``
    (``gpt.TrainModel``): its model's forward up to the head, its auxiliary loss
    and the scalars it counts, which the step reports beside ``loss``,
    ``grad_norm`` and ``step``; what it names as buffers is handed on untouched, or as
    its ``update_buffers`` leaves it.

    Its parts carry the scopes ``train.forward``, ``train.loss`` and
    ``train.optimizer`` (the backward pass inherits the forward's; a model may
    name its layers' own inside ``train.forward``): names in the compiled
    program's metadata that a device trace can be grouped by."""
    model = cfg.train_model(mesh)
    active_rules = list(rules if rules is not None else shd.DEFAULT_RULES)
    _apply = jax.named_scope("train.forward")(model.apply)

    def loss_fn(trained, buffers, tokens):
        params = {**trained, **buffers} if model.buffers else trained
        if mesh is not None:
            # Install the logical-axis rule table so the model's
            # with_logical_constraint calls reach XLA (they are silent
            # no-ops when no rules are set).
            with nn.logical_axis_rules(active_rules):
                (hidden, kernel, bias), aux, scalars = _apply(params, tokens)
                # the head's kernel as the loss reads it: gathered over fsdp
                # once a step and its gradient reduce-scattered once, where
                # every chunk of the loss gathered it (twice, with the replay)
                # and reduced its share of the gradient
                kernel = nn.with_logical_constraint(kernel, ("act_embed", "vocab"), mesh=mesh)
        else:
            (hidden, kernel, bias), aux, scalars = _apply(params, tokens)
        # Blockwise xent: never materializes the [b, t, vocab] logits.
        with jax.named_scope("train.loss"):
            loss = blockwise_next_token_loss(hidden, kernel, bias, tokens)
        return loss + model.aux_weight * aux, scalars

    def step(state: TrainState, tokens: jax.Array):
        trained, buffers = _trained(state.params, model.buffers)
        (loss, scalars), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            trained, buffers, tokens)
        with jax.named_scope("train.optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, trained)
            new_params = optax.apply_updates(trained, updates)
            if model.update_buffers is not None:
                buffers, scalars = model.update_buffers(buffers, scalars)
            if model.buffers:
                new_params = {**new_params, **buffers}
        metrics = {
            **scalars,
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            "step": state.step + 1,
        }
        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            metrics,
        )

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    data_sharding = shd.batch_sharding(mesh, ndim=2, rules=rules)
    kwargs = {}
    if state_shardings_tree is not None:
        kwargs["in_shardings"] = (state_shardings_tree, data_sharding)
        kwargs["out_shardings"] = (
            state_shardings_tree,
            NamedSharding(mesh, PartitionSpec()),
        )

    # where the collectives sit in the schedule is the compiler's; what it is
    # told follows from the mesh (nothing on one device or off the TPU) and from
    # what the model does with a sequence of the tokens' length
    @functools.lru_cache(maxsize=None)
    def jitted(scattered: bool):
        # where a layer's products are taken token half by token half round tp
        # (``gpt.Block.scattered``) a weight's gather in chunks would join both
        # halves' products in one loop: a half's partial sum could not leave before
        # the other half is multiplied, and the backward would gather each weight
        # twice. Whole, the gathers start early and the hops travel beside the
        # products (0.7053 against 0.6865 s a four-chip GPT-J step: PERF.md
        # section 6, PR 44). Every other step keeps the mesh's options.
        options = {} if scattered else accelerator.compiler_options(mesh)
        return jax.jit(
            step, donate_argnums=(0,) if donate else (), compiler_options=options, **kwargs)

    if not accelerator.compiler_options(mesh):
        return jitted(False)
    return _StepBySequence(jitted, lambda seq: bool(model.scatters(active_rules, seq)))


class _StepBySequence:
    """``step(state, tokens)`` on a mesh whose compiler options wait for the
    tokens' shape: the call, ``lower`` and ``trace`` of the jit built for what
    the model does with a sequence of that length (``TrainModel.scatters``)."""

    def __init__(self, jitted: Callable, scatters: Callable):
        self._jitted, self._scatters = jitted, scatters

    def _for(self, tokens):
        return self._jitted(self._scatters(tokens.shape[1]))

    def __call__(self, state, tokens):
        return self._for(tokens)(state, tokens)

    def lower(self, state, tokens):
        return self._for(tokens).lower(state, tokens)

    def trace(self, state, tokens):
        return self._for(tokens).trace(state, tokens)


def make_eval_step(cfg: Any, mesh: Optional[Mesh] = None) -> Callable:
    """Pass the training mesh so eval shards attention the same way (with
    sp>1, dense attention would all-gather full K/V and OOM at the context
    lengths the sp axis exists for)."""
    model = cfg.train_model(mesh)

    @jax.jit
    def eval_step(params, tokens):
        (hidden, kernel, bias), _, _ = model.apply(params, tokens)
        return blockwise_next_token_loss(hidden, kernel, bias, tokens)

    return eval_step


def make_forward(cfg: GPTConfig, mesh: Optional[Mesh] = None) -> Callable:
    """Jittable pure forward (logits) — used by __graft_entry__.entry()."""
    model = GPT(cfg, mesh=mesh)

    def forward(params, tokens):
        return model.apply({"params": params}, tokens)

    return forward


def init_params(cfg: GPTConfig, rng: jax.Array, batch_shape=(1, 128)) -> Any:
    model = GPT(cfg)
    variables = model.init(rng, jnp.zeros(batch_shape, jnp.int32))
    return nn.meta.unbox(variables["params"])
