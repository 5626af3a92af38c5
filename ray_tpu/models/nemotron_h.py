"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): the train path
behind ``models/training.py``.

``h`` is the residual stream, ``N(x) = x / sqrt(mean(x^2) + norm_eps) * g``. Every
layer ``l`` is **one** pre-normed residual branch, ``h += Mixer_l(N_l(h))``: a mixer
or a feed-forward part alone, chosen by letter ``l`` of ``pattern``
(``hybrid_override_pattern``); behind the last layer ``N_f`` and an **untied** head.

* ``M``, **Mamba-2**: ``[z, xBC, dt] = W_in n`` (no bias); ``xBC <- silu(conv(xBC))``,
  a causal depthwise convolution of ``conv_kernel`` taps with a bias over the ``x``,
  ``B`` and ``C`` channels (zeros before the sequence; ``granitemoehybrid.ssm_conv``: on
  the TPU a kernel pair that reads ``xBC`` once and writes the three parts where the
  scan reads them); ``[x, B, C] = xBC`` with ``x``
  ``[ssm_heads, ssm_head_dim]`` and ``B``, ``C`` ``[ssm_groups, ssm_state]``: head ``h``
  reads group ``h // (ssm_heads / ssm_groups)``. ``D_t = softplus(dt_t + dt_bias)``,
  ``A = -exp(A_log)``; on the state ``S_h`` ``[ssm_head_dim x ssm_state]``, float32:
  ``S_t = exp(D_t A_h) S_(t-1) + D_t x_t (x) B_t^g``, ``y_t = S_t C_t^g + D_h x_t``,
  from zeros at the sequence's start, computed in sub-chunks of ``ssm_chunk`` tokens
  (``granitemoehybrid.ssm_scan``: on the TPU a kernel pair, forward and backward, in
  which a sub-chunk's pairs, decays and weights never leave VMEM; elsewhere
  ``ssm_chunked``'s loop under ``jax.grad``). Out: ``W_out [RMSNorm_group(y * silu(z)) *
  w]``, **the norm over each group's** ``ssm_inner / ssm_groups`` **channels**
  (``granitemoehybrid.ssm_gate_norm``: on the TPU a kernel pair a group's channels wide;
  ``y`` stays float32 from the scan to it). Off the TPU both stages are their
  ``jax.numpy`` lines under plain autodiff, float32 inside either way;
* ``*``, **attention**: ``num_heads`` query heads over ``kv_heads`` K/V heads of
  ``head_dim``, no bias, a causal softmax of ``q . k / sqrt(head_dim)`` and **no
  position encoding**. The flash kernel takes one K/V head a query head, so K and V
  are **repeated** ``num_heads / kv_heads`` times before it (ROADMAP.md, R3b);
* ``E``, **experts** (``models/moe.py``): ``s = sigmoid(W_r n)`` over all
  ``router_experts``, float32; the ``experts_per_token`` largest of ``s + bias``
  chosen (the bias chooses and weighs nothing); their ``s`` over their sum, times
  ``routed_scale``; an expert is ``W_down relu(W_up n)^2``, two matrices and **no
  gate**; the ``num_experts`` from ``expert_offset`` on are held here and every pair
  whose expert is held is computed (no capacity; what the absent experts would add is
  left out); beside them one **shared** expert of the same form, of width
  ``shared_dim``, added unweighted: ``h += Shared(n) + sum_k w_k Expert_k(n)``. The
  bias is a **buffer** (``expert_bias``): no gradient and no optimizer state. With
  ``bias_update_rate`` 0 a step hands it on unchanged; above 0 the step ends with the
  rule that balances the experts' load without a loss (``balance_bias``): every
  scored expert's bias goes ``bias_update_rate`` down where this step's forward sent
  it more than the mean of the layer's pairs, and up where fewer.

The layers run one by one (nine of three kinds in the benchmark's cut: no scan over
layers). Every layer is rematerialized in the backward pass but for its input, the
flash kernel's own residuals (``FLASH_RESIDUALS``) and an expert layer's two grouped
matmuls' results (``moe.TRAINED_RESIDUALS``: the backward runs neither kernel again;
their shapes are static for the worst case, all ``experts_per_token x tokens`` pairs
held here, 0.86 GB a layer at the benchmark's cut, where an eighth of the pairs is:
kept as their bits, which a kernel over the row tiles of the blocks that hold a pair
writes and nothing copies, undefined past those blocks; the layer says how many
experts its router scores, ``routed=``, and at a share this small every pass of
``moe.trained_experts_ffn`` outside its kernels, forward, replay and backward, walks
the held blocks alone)
and the experts its router chose (``moe.ROUTED``: the kept results' rows lie as that
choice sorted the pairs, so the replay reads the choice and does not make it again).
A Mamba layer's replay runs the three forward kernels again (the convolution's, the
scan's, the gated norm's: none of their results is kept), and the three backward
kernels rebuild what they need in VMEM: the scan's a sub-chunk at a time from the states
between sub-chunks, which are all it keeps beside its operands (268 MB a layer at [2,
8192]); the convolution's its pre-activation from ``xBC``; the gated norm's its forward's
values from ``y``, ``x`` and ``z``.

Scopes, inside ``train.forward``: ``train.ssm.proj`` (the in and out projections, and
the copies of ``z`` and ``xBC`` out of the in-projection's result that a kernel's operand
has to be), ``train.ssm.conv`` (the taps, their activation and the split: ``ssm_conv_fwd``,
``ssm_conv_bwd``; and the step's softplus), ``train.ssm.scan`` (the recurrence alone),
``train.ssm.norm`` (``D x``, the gate and the grouped norm: ``ssm_gate_norm_fwd``,
``ssm_gate_norm_bwd``); ``train.attention``; ``train.moe.route``,
``train.moe.experts``, ``train.moe.shared``. The step reports
``moe.TRAINED_COUNTERS``, summed over the expert layers.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import layers, moe
from ray_tpu.models.gpt import TrainModel
from ray_tpu.models.granitemoehybrid import (   # each a kernel pair on the TPU, jax.numpy off it
    ssm_conv,
    ssm_gate_norm,
    ssm_scan,
)
from ray_tpu.ops.attention import FLASH_RESIDUALS, dot_product_attention

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    embed_dim: int = 2688
    num_heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8             # groups of B and C: a head reads its group's
    ssm_chunk: int = 128            # tokens a sub-chunk of the chunked recurrence
    conv_kernel: int = 4
    expert_dim: int = 1856          # width of one routed expert
    shared_dim: int = 3712          # width of the shared expert
    router_experts: int = 128       # experts the router scores
    num_experts: int = 128          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 6
    routed_scale: float = 2.5
    bias_std: float = 0.0           # spread of the seeded expert bias
    bias_update_rate: float = 0.0   # a step moves an expert's bias this far towards an even load
    norm_eps: float = 1e-5
    time_step_min: float = 0.001    # the Mamba-2 initialiser's step, log-uniform between
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    residual_layers: int = 52       # ``rescale_prenorm_residual``: a Mamba out-projection / sqrt of these
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown or not self.pattern:
            raise ValueError(f"layer letters {sorted(unknown)} are not M, E or *, or there is no layer")
        if self.num_heads % self.kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"{self.num_heads} query heads over {self.kv_heads} K/V heads, or {self.ssm_heads} "
                f"Mamba heads over {self.ssm_groups} groups: no whole groups")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels through the convolution: ``x`` and every group's ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def num_params(self) -> int:
        d = self.embed_dim
        shapes = {kind: _layer_shapes(self, kind) for kind in (MAMBA, EXPERTS, ATTENTION)}
        per_layer = {
            kind: sum(math.prod(shape) for shape in shapes[kind].values()) + d
            + (self.router_experts if kind == EXPERTS else 0)
            for kind in shapes}
        return 2 * self.vocab_size * d + d + sum(per_layer[kind] for kind in self.pattern)

    def train_model(self, mesh=None) -> TrainModel:
        """What ``models/training.py`` asks (``gpt.TrainModel``). One device: the
        program names no logical axis and shards nothing."""
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "nemotron_h trains on one device: its experts' exchange over ep is not built")
        return TrainModel(
            lambda rng, tokens: init_params(self, rng),
            lambda params, tokens: forward(self, params, tokens),
            buffers=("expert_bias",),
            update_buffers=(
                functools.partial(balance_bias, self.bias_update_rate)
                if self.bias_update_rate else None))


def nemotron_h_nano(**kw) -> NemotronHConfig:
    """A tiny one for the tests: the cut's nine letters, 8 Mamba heads of 8 in 4
    groups, sub-chunks of 8 tokens, 4 of 16 experts held, 3 a token."""
    sizes = dict(
        vocab_size=256, pattern="MEMEM*EME", embed_dim=64, num_heads=4, kv_heads=2, head_dim=16,
        ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=4, ssm_chunk=8, conv_kernel=4,
        expert_dim=32, shared_dim=48, router_experts=16, num_experts=4, expert_offset=0,
        experts_per_token=3, bias_std=0.05, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    return NemotronHConfig(**{**sizes, **kw})


def _layer_shapes(cfg: NemotronHConfig, kind: str):
    """A layer's parameters but its norm's scale (``ln``) and its bias buffer."""
    d, inner, heads = cfg.embed_dim, cfg.ssm_inner, cfg.ssm_heads
    q, kv = cfg.num_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return {
        MAMBA: {
            "in": (d, inner + cfg.conv_dim + heads), "conv": (cfg.conv_kernel, cfg.conv_dim),
            "conv_bias": (cfg.conv_dim,), "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
            "norm": (inner,), "out": (inner, d)},
        ATTENTION: {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)},
        EXPERTS: {
            "router": (d, cfg.router_experts),
            "wi": (cfg.num_experts, d, cfg.expert_dim), "wo": (cfg.num_experts, cfg.expert_dim, d),
            "shared_wi": (d, cfg.shared_dim), "shared_wo": (cfg.shared_dim, d)},
    }[kind]


def init_params(cfg: NemotronHConfig, rng) -> Any:
    """Seeded weights: ``layers``, one tree a layer in the pattern's order, each with
    its norm's scale ``ln``; ``wte`` and the untied ``head`` ``[embed, vocab]``;
    ``ln_f``; ``expert_bias``, one float32 ``[router_experts]`` an expert layer, in
    their order. Matrices normal with stddev 0.02, drawn in ``param_dtype``, a Mamba
    layer's ``out`` divided by ``sqrt(residual_layers)`` (``rescale_prenorm_residual``);
    norm scales 1; a Mamba layer's own as the published Mamba-2 initialiser has
    them, float32: ``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse softplus
    of a step log-uniform in ``(time_step_min, time_step_max)`` and at least
    ``time_step_floor``, ``D`` 1, the convolution's taps and bias uniform within
    ``conv_kernel^-0.5``; the experts' bias normal with stddev ``bias_std``."""
    keys = iter(jax.random.split(rng, 8 * cfg.num_layers + 2))
    bound = cfg.conv_kernel ** -0.5

    def normal(shape):
        return layers.normal(next(keys), shape, cfg.param_dtype)

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def mamba(shapes):
        step = jnp.maximum(jnp.exp(uniform(
            shapes["dt_bias"], math.log(cfg.time_step_min), math.log(cfg.time_step_max))),
            cfg.time_step_floor)
        return {
            "in": normal(shapes["in"]),
            "out": normal(shapes["out"]) / jnp.asarray(math.sqrt(cfg.residual_layers), cfg.param_dtype),
            "conv": uniform(shapes["conv"], -bound, bound).astype(cfg.param_dtype),
            "conv_bias": uniform(shapes["conv_bias"], -bound, bound).astype(cfg.param_dtype),
            "A_log": jnp.log(uniform(shapes["A_log"], 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "D": jnp.ones(shapes["D"], jnp.float32),
            "norm": jnp.ones(shapes["norm"], cfg.param_dtype),
        }

    def layer(kind):
        shapes = _layer_shapes(cfg, kind)
        drawn = mamba(shapes) if kind == MAMBA else {
            name: normal(shape) for name, shape in shapes.items()}
        return {"ln": jnp.ones((cfg.embed_dim,), cfg.param_dtype), **drawn}

    return {
        "wte": normal((cfg.vocab_size, cfg.embed_dim)),
        "layers": [layer(kind) for kind in cfg.pattern],
        "ln_f": jnp.ones((cfg.embed_dim,), cfg.param_dtype),
        "head": normal((cfg.embed_dim, cfg.vocab_size)),
        "expert_bias": [
            cfg.bias_std * jax.random.normal(next(keys), (cfg.router_experts,), jnp.float32)
            for kind in cfg.pattern if kind == EXPERTS],
    }


def mamba_mixer(cfg: NemotronHConfig, p, r):
    """The Mamba-2 mixer of the normed ``r`` [b, t, d], every sequence from a zero
    state; ``t`` is a whole number of ``ssm_chunk``."""
    dtype, f32, (b, t, _) = cfg.dtype, jnp.float32, r.shape
    heads, inner, groups = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_groups
    with jax.named_scope("train.ssm.proj"):
        # the kernels read z and xbc where the projection wrote them: neither is copied out
        projected = r @ p["in"].astype(dtype)
        z, xbc, dt = jnp.split(projected, (inner, inner + cfg.conv_dim), axis=-1)
    with jax.named_scope("train.ssm.conv"):
        x, bm, cm = ssm_conv(
            xbc, p["conv"].astype(f32), p["conv_bias"].astype(f32), inner, within=(projected, inner))
        x = x.reshape(b, t, heads, cfg.ssm_head_dim)
        bm, cm = (part.reshape(b, t, groups, cfg.ssm_state) for part in (bm, cm))
        step = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])
    with jax.named_scope("train.ssm.scan"):
        y, _ = ssm_scan(
            jnp.zeros((b, heads, cfg.ssm_head_dim, cfg.ssm_state), f32), x, step,
            -jnp.exp(p["A_log"]), bm, cm, cfg.ssm_chunk, dtype)
    with jax.named_scope("train.ssm.norm"):
        # a norm a group: over the channels of the heads that share a B and a C
        y = ssm_gate_norm(
            y.reshape(b, t, inner), x.reshape(b, t, inner), z, p["D"], p["norm"], groups,
            cfg.norm_eps, within=(projected, 0))
    with jax.named_scope("train.ssm.proj"):
        return y @ p["out"].astype(dtype)


@jax.named_scope("train.attention")
def attention_mixer(cfg: NemotronHConfig, p, r):
    """Causal GQA of ``r`` [b, t, d]; nothing is rotated."""
    dtype, (b, t, _) = cfg.dtype, r.shape

    def heads(name, n):
        return (r @ p[name].astype(dtype)).reshape(b, t, n, cfg.head_dim)

    # one K/V head a query head for the flash kernel
    q, k, v = heads("q", cfg.num_heads), *(
        jnp.repeat(heads(name, cfg.kv_heads), cfg.num_heads // cfg.kv_heads, axis=2)
        for name in ("k", "v"))
    out = dot_product_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True)
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1) @ p["o"].astype(dtype)


def expert_layer(cfg: NemotronHConfig, p, bias, r):
    """The shared expert and the held experts' part for the normed tokens ``r``
    [b, t, d] float32, ``moe.TRAINED_COUNTERS``, and the pairs the router sent each
    of the experts it scores, held here or not (``[router_experts]``)."""
    b, t, d = r.shape
    flat = r.reshape(b * t, d)
    with jax.named_scope("train.moe.route"):
        weights, chosen = moe.sigmoid_bias_top_k(
            flat, p["router"], bias, cfg.experts_per_token, cfg.routed_scale, kept=True)
        loads = (
            chosen.reshape(-1, 1) == jnp.arange(cfg.router_experts, dtype=chosen.dtype)
        ).sum(0, dtype=jnp.int32)
    with jax.named_scope("train.moe.experts"):
        y, counters = moe.trained_experts_ffn(
            flat.astype(cfg.dtype), weights, chosen, p["wi"], p["wo"], cfg.expert_offset,
            activation=moe.relu_squared, routed=cfg.router_experts)
    with jax.named_scope("train.moe.shared"):
        up = flat.astype(cfg.dtype) @ p["shared_wi"].astype(cfg.dtype)
        shared = moe.relu_squared(up) @ p["shared_wo"].astype(cfg.dtype)
    return (shared + y.astype(cfg.dtype)).reshape(b, t, d), counters, loads


def balance_bias(rate: float, buffers, counted):
    """``TrainModel.update_buffers``: the correction that balances the experts' load
    without a loss (``topk_method`` ``noaux_tc``'s own rule), after a step, from the
    loads its forward counted (``moe_loads`` ``[expert layers, router_experts]``, which
    it takes out of ``counted``): ``bias += rate * sign(mean load - load)``, every
    expert the router scores, held here or not."""
    counted = dict(counted)
    loads = counted.pop("moe_loads").astype(jnp.float32)
    towards_even = jnp.sign(loads.mean(-1, keepdims=True) - loads)
    bias = [b + rate * move for b, move in zip(buffers["expert_bias"], towards_even)]
    return {**buffers, "expert_bias": bias}, counted


def _nothing_counted():
    return jnp.zeros((len(moe.TRAINED_COUNTERS),), jnp.int32)


def _layer(cfg: NemotronHConfig, kind: str, x, p, bias=None):
    """One layer: its result, its counters and an expert layer's loads; ``bias`` is an
    expert layer's."""
    r = layers.rms_norm(x, p["ln"], cfg.norm_eps)
    if kind == EXPERTS:
        y, counters, loads = expert_layer(cfg, p, bias, r)
        return x + y, counters, loads
    mixer = mamba_mixer if kind == MAMBA else attention_mixer
    return x + mixer(cfg, p, r.astype(cfg.dtype)), _nothing_counted(), None


def forward(cfg: NemotronHConfig, params, tokens):
    """``tokens`` [b, t] through every layer: ``((hidden [b, t, d], the head's kernel
    [d, vocab], None), 0.0, counters)``, as ``gpt.TrainModel.apply`` gives them;
    ``counters`` are ``moe.TRAINED_COUNTERS``, summed over the expert layers, and, where
    a step moves the bias (``bias_update_rate``), ``moe_loads`` for :func:`balance_bias`."""
    # a layer's remat keeps its input, its kernels' results (the attention's own
    # residuals, both grouped matmuls') and the experts its router chose (the grouped
    # matmuls' rows lie as that choice sorted them: a replay that chose again, an ulp
    # otherwise, would read a row of no group as a group's) and replays the rest, the
    # recurrence too. Outside
    # a scan a replay has to be kept from merging with the forward it repeats
    # (``prevent_cse``, the default)
    layer = jax.checkpoint(
        _layer, static_argnums=(0, 1),
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS, *moe.TRAINED_RESIDUALS, moe.ROUTED))

    x = params["wte"].astype(cfg.dtype)[tokens]
    counted, loads = _nothing_counted(), []
    biases = iter(params["expert_bias"])
    for kind, p in zip(cfg.pattern, params["layers"]):
        x, counters, sent = layer(cfg, kind, x, p, next(biases) if kind == EXPERTS else None)
        counted = counted + counters
        loads += [] if sent is None else [sent]
    hidden = layers.rms_norm(x, params["ln_f"], cfg.norm_eps).astype(cfg.dtype)
    counted = dict(zip(moe.TRAINED_COUNTERS, counted))
    if cfg.bias_update_rate:
        counted["moe_loads"] = jnp.stack(loads)
    return (hidden, params["head"], None), jnp.zeros((), jnp.float32), counted
