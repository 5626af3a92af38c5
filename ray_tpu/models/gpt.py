"""Decoder-only transformer family (the framework's flagship model).

Fills the slot the reference fills with external torch models (GPT-J-6B
DeepSpeed fine-tune, reference: doc/source/ray-air/examples/
gptj_deepspeed_fine_tuning.ipynb; release/train_tests) — but TPU-first:

- flax.linen modules whose every parameter carries *logical* axis names
  (see ray_tpu.parallel.sharding), so one model definition runs DP, FSDP,
  TP, SP and any mix by switching rule tables;
- bfloat16 activations/compute; params and optimizer state in
  ``param_dtype`` (float32 unless the configuration says otherwise);
- `nn.scan` over layers (one XLA While loop, compiles O(1) in depth) with
  `nn.remat` so long-context activations are rematerialized;
- fused attention from ray_tpu.ops (Pallas flash kernel on TPU).

``GPTConfig`` says what the model is; how a step runs (kernel or XLA, tile
sizes, what the remat saves, the loss's chunk) is the code's to decide. It has
decided to keep what is cheap to hold and dear to make again: a layer's remat
saves its input and the attention kernel's output and logsumexp (the backward
kernels' own residuals) and replays the rest; the loss keeps no logits and
makes its gradients from each chunk's logits while it has them.
`gpt_j_6b()` matches the reference benchmark model's shape (28 layers,
d_model 4096, 16 heads × 256, rotary_dim 64, vocab 50400, one LayerNorm
feeding attention and MLP in parallel); `gpt_nano` is for tests, `gpt_1b`
for the multichip dry run. ``make_extend_fn`` is the serving engine's
KV-cache forward of the same block.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers
from ray_tpu.ops.attention import FLASH_RESIDUALS
from ray_tpu.ops.ring import mesh_attention
from ray_tpu.parallel import ring_dense


class TrainModel(NamedTuple):
    """What ``models/training.py`` asks of a configuration (``cfg.train_model(mesh)``),
    as ``serve/llm.py`` asks one for ``make_extend_fn``, ``cache_arrays`` and
    ``init_params``: the model as the train step needs it, whatever it is built of.

    ``init(rng, tokens)`` gives the parameters (flax ``Partitioned`` boxes where the
    model names logical axes, plain arrays where it does not); ``apply(params,
    tokens)`` gives ``((hidden, head_kernel, head_bias), aux, scalars)``: what
    :func:`blockwise_next_token_loss` takes, an auxiliary loss that enters the step's
    loss ``aux_weight`` times, and the scalars the step reports beside its own
    (a dict, empty where the model counts nothing). ``buffers`` names the top-level
    entries of the parameters that are no parameters: they get no gradient and no
    optimizer state, and a step hands them on as they are, or as ``update_buffers``
    leaves them: ``update_buffers(buffers, scalars)`` gives ``(buffers, scalars)`` after
    the step, from what ``apply`` counted in this step's forward (a rule that is no
    gradient's: an expert layer's correction bias follows its experts' loads; what the
    rule alone reads, it takes out of the scalars). ``scatters(rules, seq)``
    says whether ``apply`` on ``seq`` tokens a sequence, under the logical axis
    ``rules``, takes its layers' products apart round the mesh's tp axis
    (``GPTConfig.scatter_axis``): what the step is compiled with follows from it."""

    init: Callable
    apply: Callable
    aux_weight: float = 0.0
    buffers: Tuple[str, ...] = ()
    scatters: Callable = lambda rules, seq: False
    update_buffers: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50400
    num_layers: int = 28
    num_heads: int = 16
    head_dim: int = 256
    embed_dim: int = 4096
    mlp_dim: int = 16384
    max_seq_len: int = 2048
    rotary_dim: int = 64
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # master parameter dtype
    tie_embeddings: bool = False
    remat: bool = True                 # rematerialize each layer in the backward pass
    seq_parallel_impl: str = "ring"         # "ring" | "ulysses" (used when sp>1)
    # mixture-of-experts (0 = dense MLP); experts shard over the ep axis
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    @property
    def qkv_dim(self) -> int:
        return self.num_heads * self.head_dim

    # -- what the serving engine asks of an architecture's configuration
    # (``serve/llm.py``): the heads its cache stores, its ``extend`` and its
    # seeded weights; ``models/cohere2_moe.py`` and ``models/keye_vl2.py``
    # answer the same ------------------------------------------------------

    @property
    def kv_heads(self) -> int:
        """K/V heads a cache stores: one per query head."""
        return self.num_heads

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V."""
        return ((self.kv_heads, self.head_dim),) * 2

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        """Deterministically initialized, unboxed params: every replica builds
        bitwise-identical base weights from the same seed."""
        variables = GPT(self).init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
        return unboxed_params(variables)

    def train_model(self, mesh=None) -> TrainModel:
        """The flax :class:`GPT` as the train step takes a model; with experts
        (``MoeMlp``) the layers' load-balancing losses, sown into ``losses``,
        are the auxiliary loss."""
        model = GPT(self, return_hidden=True, mesh=mesh)

        def init(rng, tokens):
            return GPT(self).init(rng, tokens)["params"]

        def apply(params, tokens):
            if self.moe_num_experts > 0:
                out, mut = model.apply({"params": params}, tokens, mutable=["losses"])
                aux = sum(jnp.sum(v) for v in jax.tree.leaves(mut["losses"]))
                return out, aux / self.num_layers, {}
            return model.apply({"params": params}, tokens), jnp.zeros((), jnp.float32), {}

        def scatters(rules, seq):
            return self.scatter_axis(mesh, rules, seq) is not None

        return TrainModel(init, apply, self.moe_aux_weight, scatters=scatters)

    def scatter_axis(self, mesh, rules, seq: int) -> Optional[str]:
        """The mesh axis over which the stream between blocks lies scattered along
        a sequence of ``seq`` tokens (``ring_dense.scatter_axis``: tp, of more than
        one chip, where it divides ``seq``), or None: ``Block`` runs plain then, as
        it does with experts, whose output is no partial sum of two products. The
        one place that decides it: ``Block`` asks here, and so does the train step
        for what it is compiled with."""
        if self.moe_num_experts > 0:
            return None
        return ring_dense.scatter_axis(mesh, rules, seq)

    def num_params(self) -> int:
        """Exact parameter count (for MFU math)."""
        d, h, hd, f, v = (
            self.embed_dim,
            self.num_heads,
            self.head_dim,
            self.mlp_dim,
            self.vocab_size,
        )
        if self.moe_num_experts:
            mlp_params = self.moe_num_experts * 2 * d * f + d * self.moe_num_experts
        else:
            mlp_params = 2 * d * f + f + d
        per_layer = (
            4 * d * h * hd          # q,k,v,o
            + mlp_params
            + 2 * d                 # ln scale+bias
        )
        head = 0 if self.tie_embeddings else d * v + v
        return v * d + self.num_layers * per_layer + 2 * d + head


def gpt_nano(**kw) -> GPTConfig:
    return GPTConfig(
        vocab_size=256, num_layers=2, num_heads=4, head_dim=16, embed_dim=64,
        mlp_dim=256, max_seq_len=128, rotary_dim=16, dtype=jnp.float32, **kw
    )


def gpt_1b(**kw) -> GPTConfig:
    return GPTConfig(
        vocab_size=50304, num_layers=16, num_heads=16, head_dim=128,
        embed_dim=2048, mlp_dim=8192, max_seq_len=2048, rotary_dim=64, **kw
    )


def gpt_j_6b(**kw) -> GPTConfig:
    return GPTConfig(**kw)


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------


# the logical axes of a block's kernels
_QKV_AXES, _O_AXES = ("embed", "heads", "kv"), ("heads", "kv", "embed")
_WI_AXES, _WO_AXES = ("embed", "mlp"), ("mlp", "embed")


class _DenseND(nn.Module):
    """DenseGeneral equivalent that initializes the kernel at its FULL
    shape. flax's DenseGeneral initializes a flattened 2-D kernel and
    reshapes afterwards, which breaks logical partitioning metadata inside
    manual-mesh regions (the rank-2 flat kernel gets constrained with the
    rank-N spec during scope.param's eval_shape revalidation) — the
    pipeline stages run exactly there. Same param names/shapes/math as
    DenseGeneral contracting the trailing input dims."""

    features: Tuple[int, ...]
    logical_axes: Tuple[str, ...]
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None  # the step's device mesh, when it has one
    bias_axes: Optional[Tuple[str, ...]] = None  # the bias's own, where not the output's

    @nn.compact
    def __call__(self, x: jax.Array, beside: Optional[jax.Array] = None) -> jax.Array:
        """``beside``, of the output's shape, is added to the product before
        the bias is."""
        n_in = len(self.logical_axes) - len(self.features)
        in_shape = x.shape[-n_in:]
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), self.logical_axes
            ),
            in_shape + tuple(self.features),
            self.param_dtype,
        )
        # a plain dot_general, but for the kernel's gradient on a mesh whose
        # fsdp axis shards it: that is reduced a shard at a time round the axis
        y = ring_dense.dense(
            x.astype(self.dtype), kernel.astype(self.dtype), n_in,
            self.mesh, self.logical_axes, nn.get_logical_axis_rules(),
        )
        if beside is not None:
            y = y + beside
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), self.bias_axes or self.logical_axes[n_in:]
                ),
                tuple(self.features),
                self.param_dtype,
            )
            y = y + bias.astype(self.dtype)
        return y


def _dense(features: Tuple[int, ...], logical_axes: Tuple[str, ...], cfg: GPTConfig,
           name: str, use_bias: bool = True, mesh: Any = None,
           bias_axes: Optional[Tuple[str, ...]] = None):
    return _DenseND(
        features=tuple(features) if isinstance(features, tuple) else (features,),
        logical_axes=logical_axes,
        use_bias=use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        mesh=mesh,
        bias_axes=bias_axes,
        name=name,
    )


def _attend(cfg: GPTConfig, mesh: Any, q: jax.Array, k: jax.Array, v: jax.Array,
            positions: jax.Array) -> jax.Array:
    """Causal attention of [b, t, h, d] projections, rotated here."""
    q = layers.rotary(q, positions, cfg.rotary_dim)
    k = layers.rotary(k, positions, cfg.rotary_dim)
    # [b, t, h, d] → [b, h, t, d] for the fused kernel
    qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    # the fused kernel, per shard under shard_map when the mesh has more
    # than one device (batch on dp/fsdp, heads on tp); with sp > 1
    # context parallelism: ring/ulysses over the sp axis (first-class
    # long-context support — SURVEY.md §5)
    return mesh_attention(
        qh, kh, vh, mesh, impl=cfg.seq_parallel_impl, causal=True
    ).transpose(0, 2, 1, 3)


class Attention(nn.Module):
    cfg: GPTConfig
    mesh: Any = None  # the step's device mesh, when it has one

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        h, hd = cfg.num_heads, cfg.head_dim
        q = _dense((h, hd), _QKV_AXES, cfg, "q", use_bias=False, mesh=self.mesh)(x)
        k = _dense((h, hd), _QKV_AXES, cfg, "k", use_bias=False, mesh=self.mesh)(x)
        v = _dense((h, hd), _QKV_AXES, cfg, "v", use_bias=False, mesh=self.mesh)(x)
        out = _attend(cfg, self.mesh, q, k, v, positions)
        return _dense(
            (cfg.embed_dim,), _O_AXES, cfg, "o", use_bias=False, mesh=self.mesh
        )(out)


class Mlp(nn.Module):
    cfg: GPTConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, beside: Optional[jax.Array] = None) -> jax.Array:
        """The MLP of ``x``; ``beside`` joins ``wo``'s product under its bias."""
        cfg = self.cfg
        x = _dense((cfg.mlp_dim,), _WI_AXES, cfg, "wi", mesh=self.mesh)(x)
        x = nn.gelu(x)
        # ``wo``'s bias is as long as the embedding: every chip holds it whole, as it
        # does a LayerNorm's scale and bias (``sharding.DEFAULT_RULES``, "embed_vector")
        return _dense(
            (cfg.embed_dim,), _WO_AXES, cfg, "wo", mesh=self.mesh, bias_axes=("embed_vector",)
        )(x, beside)


def _layer_norm(cfg: GPTConfig, name: str):
    return nn.LayerNorm(
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed_vector",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed_vector",)),
        name=name,
    )


class Block(nn.Module):
    """GPT-J's block: one LayerNorm feeds attention and MLP side by side.

    Attention's output and the MLP's last product are summed before anything
    else is added to either: ``x + ((attn + mlp's product) + mlp's bias)``.
    Under tp both are partial sums of a matmul whose contraction is sharded,
    so their sum is reduced once a layer, where ``x + attn + mlp`` with a bias
    between the two took two all-reduces. An expert layer's output is no such
    product and keeps the plain sum.

    Where ``GPTConfig.scatter_axis`` names an axis (tp, of more than one chip)
    that sum is never all-reduced: the stream between blocks lies scattered
    along the sequence over the axis, and the block is :meth:`scattered`.

    The constraints name the step's mesh: flax applies one without a mesh only
    under ``jax.set_mesh``, and the one on the output is what keeps the
    compiler from laying the sum out along ``wo``'s bias (embed over fsdp)."""

    cfg: GPTConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        axis = cfg.scatter_axis(self.mesh, nn.get_logical_axis_rules(), x.shape[1])
        if axis is not None and not self.is_initializing():
            return self.scattered(x, positions, axis)
        axes = ("batch", "seq", "act_embed")
        x = nn.with_logical_constraint(x, axes, mesh=self.mesh)
        hidden = _layer_norm(cfg, "ln")(x)
        attn = Attention(cfg, self.mesh, name="attn")(hidden, positions)
        if cfg.moe_num_experts > 0:
            from ray_tpu.models.moe import MoeMlp

            x = x + attn + MoeMlp(cfg, name="mlp")(hidden)
        else:
            x = x + Mlp(cfg, self.mesh, name="mlp")(hidden, beside=attn)
        return nn.with_logical_constraint(x, axes, mesh=self.mesh)

    def scattered(self, x: jax.Array, positions: jax.Array, axis: str) -> jax.Array:
        """The same sums with the layer's all-reduce over ``axis`` taken apart
        (``parallel/ring_dense.py``). A chip normalises its own tokens; q, k, v
        and ``wi`` multiply them while the next chip's arrive, and those as the
        ones after them do (``train.tp.gather``); attention sees the whole
        sequence and the chip's heads, as before; ``o`` and ``wo`` multiply the
        farthest chip's tokens first and send the partial sum on while they
        multiply the next chip's, this chip's own last
        (``train.tp.scatter``); bias and residual are added to those alone.
        The MLP is token by token, so its activations are never laid out in the
        sequence's order. The parameters are read where the plain block made
        them (a block is always initialized plain)."""
        cfg, mesh, rules = self.cfg, self.mesh, nn.get_logical_axis_rules()
        axes = ("batch", "act_seq", "act_embed")
        x = nn.with_logical_constraint(x, axes, mesh=mesh)
        params = nn.meta.unbox(self.variables["params"])
        attn, mlp = params["attn"], params["mlp"]
        hidden = _layer_norm(cfg, "ln")(x)
        kernels = [
            (attn["q"]["kernel"], _QKV_AXES), (attn["k"]["kernel"], _QKV_AXES),
            (attn["v"]["kernel"], _QKV_AXES), (attn["o"]["kernel"], _O_AXES),
            (mlp["wi"]["kernel"], _WI_AXES), (mlp["wi"]["bias"], _WI_AXES[1:]),
            (mlp["wo"]["kernel"], _WO_AXES),
        ]

        def layer(hidden, positions, q, k, v, o, wi, wi_bias, wo):
            def times(xs, kernel, kernel_axes, n_in=1):
                return ring_dense.products(xs, kernel, n_in, mesh, kernel_axes, rules)

            # every half of the stream laid out as the stream is (its batch over
            # the batch's axes), on its way in and, with its gradient, on its way
            # out, or the compiler may lay one out as ``wo``'s bias
            own = lambda half: ring_dense.laid_out(half, ("batch", None, "act_embed"), rules, mesh)  # noqa: E731
            with jax.named_scope("train.tp.gather"):
                held = [own(half) for half in ring_dense.arriving(hidden, axis)]
                q, k, v = (
                    ring_dense.in_order(times(held, each, _QKV_AXES), axis) for each in (q, k, v))
                inner = tuple(nn.gelu(each) for each in ring_dense.biased(
                    times(held, wi, _WI_AXES), wi_bias, mesh, _WI_AXES[1:], rules))
            out = _attend(cfg, mesh, q, k, v, positions)
            with jax.named_scope("train.tp.scatter"):
                return ring_dense.home([
                    own(of_mlp + of_attn) for of_attn, of_mlp in zip(
                        times(ring_dense.by_hop(out, axis), o, _O_AXES, 2),
                        times(inner, wo, _WO_AXES))
                ], axis)

        of = lambda logical: ring_dense.spec_over(axis, logical, rules, mesh)  # noqa: E731
        out = jax.shard_map(
            layer, mesh=mesh, out_specs=of(axes), axis_names={axis}, check_vma=False,
            in_specs=(of(axes), of(("batch", "seq")), *(of(logical) for _, logical in kernels)),
        )(hidden, positions, *(each.astype(cfg.dtype) for each, _ in kernels))
        x = x + (out + mlp["wo"]["bias"].astype(cfg.dtype))
        return nn.with_logical_constraint(x, axes, mesh=mesh)


class ScannedBlocks(nn.Module):
    cfg: GPTConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        # remat keeps a layer's input and the attention kernel's own residuals
        # (its output, the size of the input, and its logsumexp), so the
        # backward runs dq and dk/dv without running the forward kernel again;
        # everything else is replayed. Under a scan the loop already keeps XLA
        # from merging the replay into the forward
        keep = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)
        block = nn.remat(Block, prevent_cse=False, policy=keep) if cfg.remat else Block
        x, _ = nn.scan(
            lambda mdl, carry, _: (mdl(carry, positions), None),
            variable_axes={"params": 0, "losses": 0},
            split_rngs={"params": True},
            length=cfg.num_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(block(cfg, self.mesh, name="layers"), x, None)
        if cfg.scatter_axis(self.mesh, nn.get_logical_axis_rules(), x.shape[1]) is not None:
            # what the blocks left scattered along the sequence is gathered here,
            # once: the loss cuts the sequence into chunks of its own
            x = nn.with_logical_constraint(x, ("batch", "seq", "act_embed"), mesh=self.mesh)
        return x


class GPT(nn.Module):
    """Returns logits [batch, seq, vocab] — or, with ``return_hidden=True``,
    ``(hidden, head_kernel, head_bias)`` so callers can run a blockwise
    cross-entropy that never materializes the full [b, t, vocab] logits
    (the dominant HBM cost of the train step at GPT-J vocab sizes)."""

    cfg: GPTConfig
    return_hidden: bool = False
    mesh: Any = None  # the step's device mesh: attention is shard_mapped over it

    @nn.compact
    def __call__(self, tokens: jax.Array, positions: Optional[jax.Array] = None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.embed_dim,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            name="wte",
        )
        x = embed(tokens)
        x = ScannedBlocks(cfg, self.mesh, name="blocks")(x, positions)
        x = _layer_norm(cfg, "ln_f")(x)
        if cfg.tie_embeddings:
            kernel = embed.embedding.T  # [d, vocab]
            bias = None
        else:
            kernel, bias = LMHead(cfg, name="lm_head")()
        if self.return_hidden:
            return x, kernel, bias
        logits = x.astype(cfg.dtype) @ kernel.astype(cfg.dtype)
        if bias is not None:
            logits = logits + bias
        return nn.with_logical_constraint(
            logits.astype(jnp.float32), ("batch", "seq", "act_vocab")
        )


class LMHead(nn.Module):
    """Owns the untied lm_head params (same tree as the former DenseGeneral:
    lm_head/{kernel,bias}) and returns them as arrays."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", "vocab")
            ),
            (cfg.embed_dim, cfg.vocab_size),
            cfg.param_dtype,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("vocab",)),
            (cfg.vocab_size,),
            cfg.param_dtype,
        )
        return kernel, bias


# ---------------------------------------------------------------------------
# KV-cache decode path (serve/llm.py)
# ---------------------------------------------------------------------------
#
# The training modules above never materialize a KV cache — they recompute
# attention over the whole sequence every call, which is the right shape
# for teacher forcing and the wrong shape for serving. The inference
# engine instead runs `make_extend_fn(cfg)`: one jitted "extend" step that
# appends `tc` new tokens per lane to a per-lane cache of `lengths` tokens
# and attends the new queries over the full (padded) cache. Prefill is an
# extend with tc = prompt-chunk length; decode is an extend with tc = 1 —
# the same compiled family, bucketed on (batch, tc, cache capacity) so XLA
# only ever sees the configured shapes.


def unboxed_params(variables):
    """The raw ``params`` subtree with flax partitioning metadata stripped
    — the form :func:`make_extend_fn` consumes."""
    tree = variables["params"] if "params" in variables else variables
    return nn.meta.unbox(tree)


def make_extend_fn(cfg: GPTConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, *, last=None)``.

    ``tokens`` [b, tc] are the next tokens of each lane whose cache already
    holds ``lengths`` [b] tokens; their K/V are written at absolute
    positions ``lengths + arange(tc)`` and the new queries attend over the
    updated cache under the mask ``key_pos <= query_pos`` (which also
    hides never-written padding — anything past a lane's frontier is
    acausal by construction). Returns ``(logits, hidden, k_new, v_new)``:
    f32 logits and final-hidden (hidden feeds LoRA deltas), plus the new
    K/V chunks [layers, b, tc, heads, head_dim] for the caller to page
    back into its block pool. Deterministic given identical shapes, which
    is what makes cached-prefix decode bitwise-equal to uncached decode.

    Which rows get the last norm and the head is the caller's to say, and
    every architecture's ``extend`` takes it the same way
    (``layers.read_rows``). Without ``last``: every fed position, logits
    [b, tc, vocab] and hidden [b, tc, d] (a test that compares all positions
    with a plain forward; ``scripts/program_digest.py``). With ``last`` [b],
    as the serve engine always calls it: row ``last[i]`` of lane ``i`` alone
    (its last *valid* token where the lane emits), picked from the residual
    stream before the norm, so logits [b, vocab] and hidden [b, d]; ``-1``
    marks a lane nobody reads, and a chunk in which that is every lane (a
    prompt's chunks but its last) runs no head: zeros of the two shapes, by a
    ``cond`` on the operand, in the same one program the shape has. A call
    of one token a lane (decode) reads each lane's row and has no ``cond``.

    Its parts carry the scopes ``extend.embed``, ``extend.attention``,
    ``extend.mlp`` and ``extend.logits``: names in the compiled program's
    metadata that a device trace can be grouped by after any refactor.
    """
    if cfg.moe_num_experts:
        raise NotImplementedError("KV-cache decode does not support MoE MLPs")
    dtype = cfg.dtype
    scale = 1.0 / float(np.sqrt(cfg.head_dim))

    def _ln(x, p):
        xf = x.astype(jnp.float32)
        mean = xf.mean(-1, keepdims=True)
        var = (xf * xf).mean(-1, keepdims=True) - mean * mean
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        return y.astype(dtype)

    @jax.named_scope("extend.mlp")
    def _mlp(x, p):
        y = jnp.einsum("btd,df->btf", x, p["wi"]["kernel"].astype(dtype))
        y = nn.gelu(y + p["wi"]["bias"].astype(dtype))
        y = jnp.einsum("btf,fd->btd", y, p["wo"]["kernel"].astype(dtype))
        return y + p["wo"]["bias"].astype(dtype)

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, kc, vc):
        q = jnp.einsum("btd,dhk->bthk", hidden, p["q"]["kernel"].astype(dtype))
        k = jnp.einsum("btd,dhk->bthk", hidden, p["k"]["kernel"].astype(dtype))
        v = jnp.einsum("btd,dhk->bthk", hidden, p["v"]["kernel"].astype(dtype))
        q = layers.rotary(q, positions, cfg.rotary_dim)
        k = layers.rotary(k, positions, cfg.rotary_dim)
        lane = jnp.arange(positions.shape[0])[:, None]
        kc = layers.write_rows(kc, lane, positions, k)
        vc = layers.write_rows(vc, lane, positions, v)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kc, preferred_element_type=jnp.float32
        ) * scale
        kpos = jnp.arange(kc.shape[1], dtype=jnp.int32)
        mask = (kpos[None, None, :] <= positions[:, :, None])[:, None, :, :]
        scores = jnp.where(mask, scores, jnp.float32(layers.MASKED))
        w = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, vc)
        out = jnp.einsum("bqhd,hde->bqe", out, p["o"]["kernel"].astype(dtype))
        return out, k, v

    def _block(x, p, positions, kc, vc):
        hidden = _ln(x, p["ln"])
        a, k, v = _attend(p["attn"], hidden, positions, kc, vc)
        return x + a + _mlp(hidden, p["mlp"]), k, v

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, *, last=None):
        positions, _ = layers.frame(tokens, lengths)    # padding is computed like a token here
        with jax.named_scope("extend.embed"):
            emb = params["wte"]["embedding"].astype(dtype)
            x = layers.look_up(emb, tokens)
        stacked = params["blocks"]["layers"]    # [num_layers, ...] by the scan

        def body(carry, xs):
            p, kc, vc = xs
            y, k, v = _block(carry, p, positions, kc, vc)
            return y, (k, v)

        x, (k_new, v_new) = jax.lax.scan(body, x, (stacked, k_cache, v_cache))

        def head(rows):
            rows = _ln(rows, params["ln_f"])
            if cfg.tie_embeddings:
                kernel, bias = emb.T, None
            else:
                kernel = params["lm_head"]["kernel"].astype(dtype)
                bias = params["lm_head"]["bias"]
            logits = (rows @ kernel).astype(jnp.float32)
            if bias is not None:
                logits = logits + bias.astype(jnp.float32)
            return logits, rows

        with jax.named_scope("extend.logits"):
            logits, x = layers.read_rows(x, last, head)
        return logits, x.astype(jnp.float32), k_new, v_new

    return extend


# ---------------------------------------------------------------------------
# loss helpers
# ---------------------------------------------------------------------------


def next_token_loss(logits: jax.Array, tokens: jax.Array,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean cross-entropy of predicting tokens[t+1] from position t."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


def blockwise_next_token_loss(
    hidden: jax.Array,
    head_kernel: jax.Array,
    head_bias: Optional[jax.Array],
    tokens: jax.Array,
    mask: Optional[jax.Array] = None,
    chunk: int = 256,
) -> jax.Array:
    """Mean next-token cross-entropy without materializing [b, t, vocab].

    Scans over sequence chunks; each chunk's float32 logits are computed and
    reduced to (logsumexp, target-logit), so peak HBM holds one
    [b, chunk, vocab] block instead of three full-size f32 logit tensors.
    Differentiated, it makes its gradients where it makes its logits
    (``jax.custom_vjp``): from the same block, ``softmax - onehot`` and with it
    the chunk's share of the gradients of ``hidden``, ``head_kernel`` and
    ``head_bias``, the last two summed in float32 over the chunks. The head is
    multiplied three times a chunk, which is what the mathematics needs, where
    a rematerialized chunk multiplied it four times; no logits are kept. This
    is the XLA-friendly equivalent of a fused cross-entropy kernel.
    """
    return _blockwise_loss(chunk, hidden, head_kernel, head_bias, tokens, mask)


def _loss_chunks(hidden, tokens, mask, chunk):
    """Positions 0 .. t-2 of ``hidden``, their targets and their weights, padded
    to whole chunks and laid out [chunks, b, chunk, ...] for a scan."""
    b, t, d = hidden.shape
    xs = hidden[:, :-1]
    targets = tokens[:, 1:]
    n = t - 1
    valid = jnp.ones((b, n), jnp.float32) if mask is None else mask[:, 1:].astype(jnp.float32)
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    nc = (n + pad) // chunk
    xs = xs.reshape(b, nc, chunk, d).swapaxes(0, 1)        # [nc, b, chunk, d]
    targets = targets.reshape(b, nc, chunk).swapaxes(0, 1)
    valid = valid.reshape(b, nc, chunk).swapaxes(0, 1)
    return xs, targets, valid


def _chunk_logits(x_c, kernel, head_bias):
    """One chunk's float32 logits; ``kernel`` is in the compute dtype."""
    logits = (x_c @ kernel).astype(jnp.float32)
    if head_bias is not None:
        logits = logits + head_bias.astype(jnp.float32)
    return logits


def _chunk_nll(logits, t_c, m_c):
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
    return ((lse - tl) * m_c).sum(), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blockwise_loss(chunk, hidden, head_kernel, head_bias, tokens, mask):
    xs, targets, valid = _loss_chunks(hidden, tokens, mask, chunk)
    kernel = head_kernel.astype(hidden.dtype)

    def body(acc, args):
        x_c, t_c, m_c = args
        return acc + _chunk_nll(_chunk_logits(x_c, kernel, head_bias), t_c, m_c)[0], None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, targets, valid))
    return total / jnp.maximum(valid.sum(), 1.0)


def _blockwise_loss_fwd(chunk, hidden, head_kernel, head_bias, tokens, mask):
    b, t, d = hidden.shape
    compute_dtype = hidden.dtype
    xs, targets, valid = _loss_chunks(hidden, tokens, mask, chunk)
    kernel = head_kernel.astype(compute_dtype)
    denom = jnp.maximum(valid.sum(), 1.0)
    vocab = jnp.arange(kernel.shape[-1], dtype=targets.dtype)

    def body(carry, args):
        total, d_kernel, d_bias = carry
        x_c, t_c, m_c = args
        logits = _chunk_logits(x_c, kernel, head_bias)
        nll, lse = _chunk_nll(logits, t_c, m_c)
        d_logits = (jnp.exp(logits - lse[..., None]) - (vocab == t_c[..., None])) * (
            m_c / denom)[..., None]
        if head_bias is not None:
            d_bias = d_bias + d_logits.sum((0, 1))
        # the cotangent of the logits' ``astype(float32)``: both products with
        # it read it in the compute dtype, as autodiff's did
        d_logits = d_logits.astype(compute_dtype)
        d_x = jnp.einsum("bcv,dv->bcd", d_logits, kernel)
        d_kernel = d_kernel + jnp.einsum(
            "bcd,bcv->dv", x_c, d_logits, preferred_element_type=jnp.float32)
        return (total + nll, d_kernel, d_bias), d_x

    (total, d_kernel, d_bias), d_xs = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros(kernel.shape, jnp.float32),
         None if head_bias is None else jnp.zeros(head_bias.shape, jnp.float32)),
        (xs, targets, valid),
    )
    # back to [b, t, d]: the padding goes, the last position predicts nothing
    d_hidden = d_xs.swapaxes(0, 1).reshape(b, -1, d)[:, : t - 1]
    d_hidden = jnp.pad(d_hidden, ((0, 0), (0, 1), (0, 0)))
    # in the parameters' dtypes here and now: a cast left to whoever reads the
    # gradient keeps the float32 sum alive until the optimizer does
    gradients = jax.lax.optimization_barrier((
        d_hidden,
        d_kernel.astype(head_kernel.dtype),
        None if head_bias is None else d_bias.astype(head_bias.dtype),
    ))
    return total / denom, gradients


def _blockwise_loss_bwd(chunk, gradients, g):
    # tokens and mask have no cotangent (None is a zero)
    return tuple(
        None if each is None else (g * each).astype(each.dtype) for each in gradients
    ) + (None, None)


_blockwise_loss.defvjp(_blockwise_loss_fwd, _blockwise_loss_bwd)
