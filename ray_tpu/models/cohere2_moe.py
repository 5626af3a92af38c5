"""Command A+ (``model_type`` ``cohere2_moe``): the serving path of one chip's
share of the model, behind ``serve/llm.py``.

One block is the parallel block GPT-J has, ``x + Attn(LN(x)) + FFN(LN(x))``,
with four differences the engine's ``extend`` contract does not see:

* LayerNorm subtracts the mean, scales, and has no bias;
* attention is grouped: ``num_heads // kv_heads`` query heads read one K/V
  head, and a cache stores K/V heads only;
* layers come in periods of ``layer_switch``: all but the last of a period
  are *sliding* layers (RoPE over every feature of a head, a query sees the
  ``sliding_window`` newest positions), the last is a *full* layer (no
  position embedding at all, a query sees everything before it). Every layer
  has the same parameter shapes, so the kind is a flag the scan carries;
* the FFN is an expert layer (``models/moe.py``): sigmoid scores over all
  ``router_experts``, the ``experts_per_token`` largest normalised over
  themselves, the experts **held here** (``num_experts`` of them, from
  ``expert_offset``) computed without dropping a token, plus the mean of
  ``shared_experts`` gated MLPs that every token passes through.

Attention has two forms, chosen by the call's shape and the build and giving
the same result. A prefill chunk (more than one query a lane) on the chip
attends in one kernel, ``ops/attention.masked_attention``, under the layer's own
mask (causal, and on a sliding layer the window): its 16 query heads meet each
tile of their K/V head together, the scores stay in VMEM, and it stops at the
lane's last live key. A decode lane (one query), and every call off the chip,
attends densely (``layers.plain_attend``; a chunk 32 queries at a time).

The embedding is tied to the output head. As in ``models/gpt.py`` the rotation
pairs feature ``i`` with ``i + head_dim / 2`` where the published model pairs
``2i`` with ``2i + 1``: the same function under a fixed permutation of each
head's q and k features (``benchmark/reference/cohere2_moe_reference.py``
applies it and compares).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers, moe
from ray_tpu.ops import attention, backend


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    num_layers: int = 32
    embed_dim: int = 4096
    num_heads: int = 128
    kv_heads: int = 8               # K/V heads: what a cache stores
    head_dim: int = 128
    expert_dim: int = 4096          # width of one routed or shared expert
    router_experts: int = 128       # experts the router scores
    num_experts: int = 128          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 8
    shared_experts: int = 4
    sliding_window: int = 4096
    layer_switch: int = 4           # every layer_switch-th layer is a full one
    rope_base: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not divide over {self.kv_heads} K/V heads")
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")

    @property
    def sliding_layers(self) -> Tuple[bool, ...]:
        """Per layer: sliding (True) or full; the sliding layers of a period
        come first (``order_of_interleaved_layers`` ``local_attn_first``)."""
        return tuple((i + 1) % self.layer_switch != 0 for i in range(self.num_layers))

    def num_params(self) -> int:
        d, f = self.embed_dim, self.expert_dim
        attention = 2 * d * self.num_heads * self.head_dim + 2 * d * self.kv_heads * self.head_dim
        experts = (self.num_experts + self.shared_experts) * 3 * d * f
        per_layer = attention + experts + d * self.router_experts + d
        return self.vocab_size * d + self.num_layers * per_layer + d

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V."""
        return ((self.kv_heads, self.head_dim),) * 2

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def cohere2_moe_nano(**kw) -> Cohere2MoeConfig:
    """A tiny one for the tests: two periods of (sliding, sliding, full)."""
    sizes = dict(
        vocab_size=256, num_layers=6, embed_dim=64, num_heads=8, kv_heads=2, head_dim=16,
        expert_dim=32, router_experts=16, num_experts=4, expert_offset=4,
        experts_per_token=4, shared_experts=2, sliding_window=24, layer_switch=3,
        max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return Cohere2MoeConfig(**{**sizes, **kw})


def init_params(cfg: Cohere2MoeConfig, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; LayerNorm scales 1), made on the
    device in one jitted call, the layers stacked for ``extend``'s scan. The
    gate and the up projection of an expert are stored side by side."""
    L, d, f = cfg.num_layers, cfg.embed_dim, cfg.expert_dim
    shapes = {
        "wte": (cfg.vocab_size, d),
        "q": (L, d, cfg.num_heads, cfg.head_dim),
        "k": (L, d, cfg.kv_heads, cfg.head_dim),
        "v": (L, d, cfg.kv_heads, cfg.head_dim),
        "o": (L, cfg.num_heads, cfg.head_dim, d),
        "router": (L, d, cfg.router_experts),
        "wi": (L, cfg.num_experts, d, 2 * f),
        "wo": (L, cfg.num_experts, f, d),
        "shared_wi": (L, cfg.shared_experts, d, 2 * f),
        "shared_wo": (L, cfg.shared_experts, f, d),
    }

    @jax.jit
    def init(rng):
        w = layers.drawn(jax.random.split(rng, len(shapes)), shapes, cfg.param_dtype)
        return {
            "wte": {"embedding": w["wte"]},
            "blocks": {"layers": {
                "ln": layers.ones_scale(cfg.param_dtype, L, d),
                "attn": {n: {"kernel": w[n]} for n in ("q", "k", "v", "o")},
                "moe": {"router": w["router"], "wi": w["wi"], "wo": w["wo"]},
                "shared": {"wi": w["shared_wi"], "wo": w["shared_wo"]},
            }},
            "ln_f": layers.ones_scale(cfg.param_dtype, d),
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def make_extend_fn(cfg: Cohere2MoeConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache)`` with the
    contract of ``gpt.make_extend_fn`` (caches ``[layers, lanes, cache,
    kv_heads, head_dim]``) and one more, small output: ``(logits, hidden,
    k_new, v_new, counters)``. ``counters`` (int32 [4], summed over the expert
    layers) are ``moe.held_experts_ffn``'s: real tokens, token-expert pairs
    computed here, held experts with at least one token, the busiest held
    expert's pairs. A negative token id marks padding: it computes no expert
    and is not counted.

    A call of one query a lane attends densely over its padded caches
    (``layers.plain_attend``), on the chip and off it; a chunk does so off the
    chip, 32 queries at a time, and on it attends in
    ``ops/attention.masked_attention`` up to ``layers.live_keys``: the same
    mask, the same precisions (bfloat16 operands, float32 scores, maximum, sum
    and accumulator, weights cast to the values' type).

    Scopes: ``extend.embed``, ``extend.attention``, ``extend.moe.route``,
    ``extend.moe.experts``, ``extend.moe.shared``, ``extend.logits`` (the last norm and
    the head, of the rows that are read: ``last=``, ``layers.read_rows``; every row
    without it).
    """
    dtype = cfg.dtype
    scale = 1.0 / float(np.sqrt(cfg.head_dim))
    groups = cfg.num_heads // cfg.kv_heads

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, live, kc, vc, sliding):
        b, tc = positions.shape
        q = jnp.einsum("btd,dhk->bthk", hidden, p["q"]["kernel"].astype(dtype))
        if tc > 1 and backend.on_tpu():
            q = attention.laid_out_by_head(q)
        k = jnp.einsum("btd,dhk->bthk", hidden, p["k"]["kernel"].astype(dtype))
        v = jnp.einsum("btd,dhk->bthk", hidden, p["v"]["kernel"].astype(dtype))

        def rotated(x):         # a full layer has no position embedding
            turned = layers.rotary(
                x.astype(jnp.float32), positions, cfg.head_dim, cfg.rope_base)
            return jnp.where(sliding, turned, x.astype(jnp.float32)).astype(dtype)

        q, k = rotated(q), rotated(k)
        lane = jnp.arange(b)[:, None]
        kc = layers.write_rows(kc, lane, positions, k)
        vc = layers.write_rows(vc, lane, positions, v)
        # the layer's mask, once for the whole chunk: causal, and on a sliding
        # layer the window's newest positions alone
        window = jnp.where(sliding, cfg.sliding_window, kc.shape[1] + tc)
        behind = positions[:, :, None] - jnp.arange(kc.shape[1], dtype=jnp.int32)
        mask = (behind >= 0) & (behind < window)                    # [b, tc, cache]
        q = q.reshape(b, tc, cfg.kv_heads, groups, cfg.head_dim)

        def attend_block(qb, visible):
            return layers.plain_attend(qb, kc, vc, visible, scale)

        if tc > 1 and backend.on_tpu():
            out = attention.masked_attention(q, kc, vc, mask, live, scale=scale)
        else:
            out = (
                attend_block(q, mask) if tc == 1
                else layers.by_query_block(attend_block, q, mask))
        out = out.reshape(b, tc, cfg.num_heads, cfg.head_dim)
        out = jnp.einsum("bqhd,hde->bqe", out, p["o"]["kernel"].astype(dtype))
        return out, k, v

    def _ffn(router, experts, layer, p_shared, normed, valid):
        b, tc, d = normed.shape
        flat = normed.reshape(b * tc, d)
        x = flat.astype(dtype)
        with jax.named_scope("extend.moe.route"):
            weights, chosen = moe.sigmoid_top_k(flat, router, cfg.experts_per_token)
        with jax.named_scope("extend.moe.experts"):
            routed, counters = moe.held_experts_ffn(
                x, weights, chosen, valid.reshape(b * tc), experts["wi"], experts["wo"],
                cfg.expert_offset, layer)
        with jax.named_scope("extend.moe.shared"):
            f = cfg.expert_dim
            gate_up = jnp.einsum("nd,sdf->snf", x, p_shared["wi"].astype(dtype))
            act = jax.nn.silu(gate_up[..., :f]) * gate_up[..., f:]
            shared = jnp.einsum(
                "snf,sfd->nd", act, p_shared["wo"].astype(dtype),
                preferred_element_type=jnp.float32) / cfg.shared_experts
        return (routed + shared).astype(dtype).reshape(b, tc, d), counters

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, *, last=None):
        positions, valid = layers.frame(tokens, lengths)
        live = layers.live_keys(positions, valid)
        with jax.named_scope("extend.embed"):
            emb = params["wte"]["embedding"].astype(dtype)
            x = layers.look_up(emb, tokens)
        scanned, routing, experts = layers.without_experts(params["blocks"]["layers"])

        def body(carry, xs):
            p, router, kc, vc, sliding, layer = xs
            normed = layers.layer_norm(carry, p["ln"]["scale"], cfg.norm_eps)
            a, k, v = _attend(
                p["attn"], normed.astype(dtype), positions, live, kc, vc, sliding)
            f, counters = _ffn(router, experts, layer, p["shared"], normed, valid)
            return carry + a + f, (k, v, counters)

        x, (k_new, v_new, counters) = jax.lax.scan(
            body, x, (
                scanned, routing["router"], k_cache, v_cache,
                jnp.asarray(cfg.sliding_layers), jnp.arange(cfg.num_layers, dtype=jnp.int32)))

        def head(rows):
            rows = layers.layer_norm(rows, params["ln_f"]["scale"], cfg.norm_eps)
            return cfg.logit_scale * jnp.dot(
                rows.astype(dtype), emb.T, preferred_element_type=jnp.float32), rows

        with jax.named_scope("extend.logits"):
            logits, x = layers.read_rows(x, last, head)
        return logits, x, k_new, v_new, counters.sum(0)

    return extend
