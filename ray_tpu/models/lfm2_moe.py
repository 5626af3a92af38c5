"""LFM2-24B-A2B (LiquidAI, ``model_type`` ``lfm2_moe``): the train path behind
``models/training.py``.

Every layer is sequential and pre-norm, ``h = x + Mixer(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``, and one of two mixers and one of two FFNs:

* mixer ``conv``: a **gated short convolution**. ``[B, C, x] = W_in r``, ``u = B *
  x``, a causal depthwise convolution of ``conv_kernel`` taps over the sequence
  (zeros before it, no bias, no activation), ``W_out (C * v)``;
* mixer ``full_attention``: GQA, ``num_heads`` query heads over ``kv_heads`` K/V
  heads, an RMSNorm of q and of k over each head's features (one learned scale
  of ``head_dim`` each), rotary over the whole head (:func:`layers.rotary`'s
  half-split pairing), a causal softmax of ``q.k / sqrt(head_dim)``. The flash
  kernel takes one K/V head a query head, so K and V are **repeated**
  ``num_heads / kv_heads`` times before it (ROADMAP.md, R3b: index maps that
  share a K/V tile across a group);
* the first ``dense_layers`` layers have a gated MLP, ``W_2 (silu(W_1 r) * W_3 r)``;
* every other layer an expert layer (``models/moe.py``): float32 sigmoid scores
  over all ``router_experts``, the ``experts_per_token`` with the largest score +
  bias chosen (the bias chooses and weighs nothing), their scores over their sum
  times ``routed_scale``, the ``num_experts`` from ``expert_offset`` on held here.
  No capacity: every pair whose expert is held is computed; what the absent
  experts would add is left out. The bias is a **buffer** (``expert_bias``): it
  takes no gradient, has no optimizer state and a step hands it on unchanged.

The head is tied to the embedding; a final RMSNorm comes before it.

The leading dense layers run one by one; behind them the layers repeat with a
period (the published model: attention, conv, conv, conv) and run as **one scan
over whole periods** whose body holds the period's unlike layers, each with a
stacked tree of its own; what is left of a last, broken period runs one by one
behind the scan. Every layer is rematerialized in the backward pass but for its
input, the flash kernel's own residuals (``FLASH_RESIDUALS``) and an expert layer's
two grouped matmuls' results (``moe.TRAINED_RESIDUALS``): the backward runs neither
kernel again, asks for the sorted rows and the gate once more (a gather and an
elementwise pass) and writes its gradients over the two kept results.

Scopes, inside ``train.forward``: ``train.conv``, ``train.attention``,
``train.mlp``, ``train.moe.route``, ``train.moe.experts``. The step reports
``moe.TRAINED_COUNTERS``, summed over the expert layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import layers, moe
from ray_tpu.models.gpt import TrainModel
from ray_tpu.ops.attention import FLASH_RESIDUALS, dot_product_attention

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    layer_types: Tuple[str, ...] = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 + (
        ATTENTION, CONV)
    dense_layers: int = 2           # leading layers with a gated MLP
    embed_dim: int = 2048
    num_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3            # taps of the short convolution (``conv_L_cache``)
    mlp_dim: int = 11776            # width of a dense layer's MLP
    expert_dim: int = 1536          # width of one expert
    router_experts: int = 64        # experts the router scores
    num_experts: int = 64           # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 4
    routed_scale: float = 1.0
    bias_std: float = 0.0           # spread of the seeded expert bias
    rope_base: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")
        if not 0 <= self.dense_layers < len(self.layer_types):
            raise ValueError(
                f"{self.dense_layers} dense layers of {len(self.layer_types)}: at least one "
                f"expert layer follows them")
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown or self.num_heads % self.kv_heads:
            raise ValueError(
                f"layer types {sorted(unknown)} are not the program's, or {self.num_heads} "
                f"query heads are no whole groups over {self.kv_heads} K/V heads")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern the layers behind the dense ones repeat."""
        rest = self.layer_types[self.dense_layers:]
        return next(
            rest[:n] for n in range(1, len(rest) + 1)
            if all(kind == rest[i % n] for i, kind in enumerate(rest)))

    @property
    def periods(self) -> int:
        """Whole periods behind the dense layers: the scan's length."""
        return (self.num_layers - self.dense_layers) // len(self.period)

    def num_params(self) -> int:
        d, f = self.embed_dim, self.expert_dim
        mixer = {
            CONV: 3 * d * d + self.conv_kernel * d + d * d,
            ATTENTION: 2 * d * self.num_heads * self.head_dim
            + 2 * d * self.kv_heads * self.head_dim + 2 * self.head_dim,
        }
        routed = d * self.router_experts + self.router_experts + self.num_experts * 3 * d * f
        return self.vocab_size * d + d + sum(
            mixer[kind] + 2 * d + (3 * d * self.mlp_dim if at < self.dense_layers else routed)
            for at, kind in enumerate(self.layer_types))

    def train_model(self, mesh=None) -> TrainModel:
        """What ``models/training.py`` asks (``gpt.TrainModel``). One device: the
        program names no logical axis and shards nothing."""
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "lfm2_moe trains on one device: its experts' exchange over ep is not built")
        return TrainModel(
            lambda rng, tokens: init_params(self, rng),
            lambda params, tokens: forward(self, params, tokens),
            buffers=("expert_bias",))


def lfm2_moe_nano(**kw) -> Lfm2MoeConfig:
    """A tiny one for the tests: a dense layer and one period, 4 of 8 experts held."""
    sizes = dict(
        vocab_size=256, layer_types=(CONV, ATTENTION, CONV, CONV, CONV), dense_layers=1,
        embed_dim=64, num_heads=4, kv_heads=2, head_dim=16, mlp_dim=96, expert_dim=32,
        router_experts=8, num_experts=4, expert_offset=0, experts_per_token=2,
        bias_std=0.05, max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return Lfm2MoeConfig(**{**sizes, **kw})


def _layer_shapes(cfg: Lfm2MoeConfig, kind: str, dense: bool):
    d, hd = cfg.embed_dim, cfg.head_dim
    mixer = {
        CONV: {"in": (d, 3 * d), "conv": (cfg.conv_kernel, d), "out": (d, d)},
        ATTENTION: {
            "q": (d, cfg.num_heads * hd), "k": (d, cfg.kv_heads * hd),
            "v": (d, cfg.kv_heads * hd), "o": (cfg.num_heads * hd, d)},
    }[kind]
    ffn = {"wi": (d, 2 * cfg.mlp_dim), "wo": (cfg.mlp_dim, d)} if dense else {
        "router": (d, cfg.router_experts),
        "wi": (cfg.num_experts, d, 2 * cfg.expert_dim),
        "wo": (cfg.num_experts, cfg.expert_dim, d)}
    return mixer, ffn


def init_params(cfg: Lfm2MoeConfig, rng) -> Any:
    """Seeded weights (normal, stddev 0.02, drawn in ``param_dtype``; norm scales
    1; the experts' bias float32 with stddev ``bias_std``): the dense layers one
    by one under ``first``, under ``periods`` one tree a layer of the period,
    each stacked over the scan's ``cfg.periods``, the layers of a last, broken
    period under ``tail``; ``expert_bias`` beside them, laid out the same way.
    An MLP's or an expert's gate and up projection lie side by side in ``wi``."""
    d, hd = cfg.embed_dim, cfg.head_dim
    period, whole = cfg.period, cfg.periods
    tail = cfg.layer_types[cfg.dense_layers + whole * len(period):]
    keys = iter(jax.random.split(rng, 16 * cfg.num_layers + 2))

    def normal(shape):
        return layers.normal(next(keys), shape, cfg.param_dtype)

    def layer(kind, dense, stack=()):
        mixer, ffn = _layer_shapes(cfg, kind, dense)
        ones = {"ln_1": (d,), "ln_2": (d,)}
        if kind == ATTENTION:
            ones.update(q_norm=(hd,), k_norm=(hd,))
        return {
            **{name: normal(stack + shape) for name, shape in {**mixer, **ffn}.items()},
            **{name: jnp.ones(stack + shape, cfg.param_dtype) for name, shape in ones.items()},
        }

    def bias(stack=()):
        return cfg.bias_std * jax.random.normal(
            next(keys), stack + (cfg.router_experts,), jnp.float32)

    return {
        "wte": normal((cfg.vocab_size, d)),
        "first": [layer(kind, True) for kind in cfg.layer_types[:cfg.dense_layers]],
        "periods": [layer(kind, False, (whole,)) for kind in period],
        "tail": [layer(kind, False) for kind in tail],
        "ln_f": jnp.ones((d,), cfg.param_dtype),
        "expert_bias": {
            "periods": [bias((whole,)) for _ in period], "tail": [bias() for _ in tail]},
    }


@jax.named_scope("train.conv")
def conv_mixer(cfg: Lfm2MoeConfig, p, r):
    """The gated short convolution of ``r`` [b, t, d]: the taps read the gated
    input at this position and at the ``conv_kernel - 1`` before it."""
    dtype, t = cfg.dtype, r.shape[1]
    gate_in, gate_out, x = jnp.split(r @ p["in"].astype(dtype), 3, axis=-1)
    u = jnp.pad(gate_in * x, ((0, 0), (cfg.conv_kernel - 1, 0), (0, 0)))
    taps = p["conv"].astype(dtype)
    v = sum(taps[j] * u[:, j:j + t] for j in range(cfg.conv_kernel))
    return (gate_out * v) @ p["out"].astype(dtype)


@jax.named_scope("train.attention")
def attention_mixer(cfg: Lfm2MoeConfig, p, r):
    """Causal GQA of ``r`` [b, t, d] over positions 0 .. t - 1."""
    dtype, (b, t, _) = cfg.dtype, r.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def heads(name, n):
        return (r @ p[name].astype(dtype)).reshape(b, t, n, cfg.head_dim)

    def normed_and_rotated(name, n):
        x = layers.rms_norm(heads(name, n), p[name + "_norm"], cfg.norm_eps)
        return layers.rotary(x, positions, cfg.head_dim, cfg.rope_base).astype(dtype)

    q, k = normed_and_rotated("q", cfg.num_heads), normed_and_rotated("k", cfg.kv_heads)
    # one K/V head a query head for the flash kernel
    k, v = (jnp.repeat(x, cfg.num_heads // cfg.kv_heads, axis=2)
            for x in (k, heads("v", cfg.kv_heads)))
    out = dot_product_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True)
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1) @ p["o"].astype(dtype)


@jax.named_scope("train.mlp")
def dense_mlp(cfg: Lfm2MoeConfig, p, r):
    gate_up = r @ p["wi"].astype(cfg.dtype)
    f = p["wo"].shape[0]
    return (jax.nn.silu(gate_up[..., :f]) * gate_up[..., f:]) @ p["wo"].astype(cfg.dtype)


def expert_ffn(cfg: Lfm2MoeConfig, p, bias, r):
    """The held experts' part of the layer for the normed tokens ``r`` [b, t, d]
    float32, and ``moe.TRAINED_COUNTERS``: every pair whose expert is held is
    computed, whatever the experts' loads."""
    b, t, d = r.shape
    flat = r.reshape(b * t, d)
    with jax.named_scope("train.moe.route"):
        weights, chosen = moe.sigmoid_bias_top_k(
            flat, p["router"], bias, cfg.experts_per_token, cfg.routed_scale)
    with jax.named_scope("train.moe.experts"):
        y, counters = moe.trained_experts_ffn(
            flat.astype(cfg.dtype), weights, chosen, p["wi"], p["wo"], cfg.expert_offset,
            routed=cfg.router_experts)
    return y.astype(cfg.dtype).reshape(b, t, d), counters


def _nothing_counted():
    return jnp.zeros((len(moe.TRAINED_COUNTERS),), jnp.int32)


def _layer(cfg: Lfm2MoeConfig, kind: str, x, p, bias=None):
    """One layer; ``bias`` is an expert layer's, None says a dense one."""
    mixer = conv_mixer if kind == CONV else attention_mixer
    x = x + mixer(cfg, p, layers.rms_norm(x, p["ln_1"], cfg.norm_eps).astype(cfg.dtype))
    r = layers.rms_norm(x, p["ln_2"], cfg.norm_eps)
    if bias is None:
        return x + dense_mlp(cfg, p, r.astype(cfg.dtype)), _nothing_counted()
    y, counters = expert_ffn(cfg, p, bias, r)
    return x + y, counters


def forward(cfg: Lfm2MoeConfig, params, tokens):
    """``tokens`` [b, t] through every layer: ``((hidden [b, t, d], the tied head's
    kernel [d, vocab], None), 0.0, counters)``, as ``gpt.TrainModel.apply`` gives
    them; ``counters`` are ``moe.TRAINED_COUNTERS``, summed over the expert layers."""
    # a layer's remat keeps its input and its kernels' results (the attention's own
    # residuals, both grouped matmuls') and replays the rest
    layer = jax.checkpoint(
        _layer, static_argnums=(0, 1), prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS, *moe.TRAINED_RESIDUALS))

    x = params["wte"].astype(cfg.dtype)[tokens]
    counted = _nothing_counted()
    for kind, p in zip(cfg.layer_types, params["first"]):
        x, _ = layer(cfg, kind, x, p)

    def one_period(carry, stacked):
        x, counted = carry
        for kind, p, bias in zip(cfg.period, *stacked):
            x, counters = layer(cfg, kind, x, p, bias)
            counted = counted + counters
        return (x, counted), None

    biases = params["expert_bias"]
    (x, counted), _ = jax.lax.scan(
        one_period, (x, counted), (params["periods"], biases["periods"]))
    for kind, p, bias in zip(cfg.period, params["tail"], biases["tail"]):
        x, counters = layer(cfg, kind, x, p, bias)
        counted = counted + counters
    hidden = layers.rms_norm(x, params["ln_f"], cfg.norm_eps).astype(cfg.dtype)
    return (
        (hidden, params["wte"].T, None), jnp.zeros((), jnp.float32),
        dict(zip(moe.TRAINED_COUNTERS, counted)))
