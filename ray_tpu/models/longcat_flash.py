"""LongCat-Flash-Chat (``model_type`` ``longcat_flash``, meituan-longcat's 560B-A27B): the
serving path behind ``serve/llm.py``.

A layer is not ``attention -> FFN``. With ``x`` the residual stream and ``N`` an RMSNorm,
a layer is **two sub-blocks and a shortcut**::

    for i in (0, 1):
        h = x + MLA[i](N_in[i](x))
        u = N_post[i](h)
        if i == 0:  s = MoE(u)          # the shortcut: read here ...
        x = h + MLP[i](u)               # a dense gated MLP of ``mlp_dim``
    x = x + s                           # ... and added after the second sub-block

so a token leaves **two** cached rows a layer (``cache_layers`` = 2 x ``num_layers``: the
pool's arena and every padded cache have a slab a sub-block, sub-block ``i`` of layer
``l`` at ``2 l + i``), and the expert layer's result is a data dependency that skips one
attention and one MLP: on a deployment's chips what the expert exchange overlaps with, on
one chip nothing but the order of the sums.

* **attention** is latent (MLA) at Kimi K2's head shapes, through the same kit
  (``layers.latent_queries`` / ``latent_attend``), cached row and kernels: one row of
  ``row_dim`` a sub-block and token for all heads, a decode call absorbed over the
  pool's pages through the block table (``extend`` offers ``table=``), a chunk on the
  chip expanded in ``ops/attention.latent_attention``. Two constants Kimi's and GLM-5's
  do not have (``mla_scale_q_lora``, ``mla_scale_kv_lora``): a head's ``q_nope`` and
  ``q_rope`` times ``(embed_dim / q_rank)^0.5`` and the normed latent times ``(embed_dim
  / kv_rank)^0.5`` before ``W_kvb``. ``W_qb`` is linear, so the first multiplies the
  normed query latent (2 at the published sizes: exact in any type); the second is
  **folded into what is cached**: the row holds the scaled normed latent, rounded once,
  and both forms read the same bits through kernels that know nothing of it. Plain
  rotary at ``rope_base`` (:func:`layers.rotary`'s half-split pairing: the published
  interleaved one under a stored permutation), softmax at ``(nope_dim + rope_dim)^-0.5``;
* **the expert layer** (``models/moe.py``): ``p = softmax(W_r u)`` over all
  ``router_experts`` outputs in float32, the ``experts_per_token`` with the largest ``p +
  b`` chosen (``e_score_correction_bias``: it chooses and does not weigh), weights
  ``routed_scale x p`` of the chosen, **not** divided by their sum
  (:func:`moe.softmax_bias_top_k`). The router's first ``routed_experts`` outputs are
  gated experts of ``expert_dim``, of which the ``num_experts`` from ``expert_offset`` on
  are held here (:func:`moe.held_experts_ffn`); its last ``zero_experts`` outputs are
  **zero-compute experts** with no weights, whose result is the token itself
  (:func:`moe.zero_experts_part`): every chip's own for the tokens it owns. No shared
  expert. The work a token costs varies by token: a third of the picks cost nothing at
  random weights, which is the published average (27 B active of 18.6 to 31.3).

Every layer is of this shape (no leading dense layer): one scan. A final ``N`` and an
untied head.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import layers, moe


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    num_layers: int = 28            # each of two attention sub-blocks and one expert layer
    embed_dim: int = 6144
    num_heads: int = 64
    q_rank: int = 1536              # the queries' latent
    kv_rank: int = 512              # the cached latent ...
    rope_dim: int = 64              # ... and the rotary key behind it
    nope_dim: int = 128             # a head's features that meet the latent
    v_dim: int = 128
    mlp_dim: int = 12288            # width of a sub-block's dense MLP
    expert_dim: int = 2048          # width of one routed expert
    router_experts: int = 768       # outputs of the router: the routed experts, then ...
    zero_experts: int = 256         # ... the zero-compute (identity) experts
    num_experts: int = 512          # routed experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 12
    routed_scale: float = 6.0
    bias_std: float = 0.001         # spread of the seeded e_score_correction_bias
    rope_base: float = 1e7
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.zero_experts < self.router_experts:
            raise ValueError(
                f"{self.zero_experts} zero-compute experts among the router's "
                f"{self.router_experts} outputs")
        if not 0 <= self.expert_offset <= self.routed_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.routed_experts} routed experts the router scores")

    @property
    def routed_experts(self) -> int:
        """The router's outputs that are experts with weights: its first ones."""
        return self.router_experts - self.zero_experts

    @property
    def row_dim(self) -> int:
        """Width of a cached row: the latent and the rotary key, padded with
        zeros to whole 128-lane tiles."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def q_scale(self) -> float:
        """``mla_scale_q_lora``: what a head's query features are multiplied with."""
        return (self.embed_dim / self.q_rank) ** 0.5

    @property
    def kv_scale(self) -> float:
        """``mla_scale_kv_lora``: what the normed latent is multiplied with before ``W_kvb``."""
        return (self.embed_dim / self.kv_rank) ** 0.5

    def num_params(self) -> int:
        d, h = self.embed_dim, self.num_heads
        attention = (
            d * self.q_rank + self.q_rank + self.q_rank * h * (self.nope_dim + self.rope_dim)
            + d * (self.kv_rank + self.rope_dim) + self.kv_rank
            + self.kv_rank * h * (self.nope_dim + self.v_dim) + h * self.v_dim * d)
        sub_block = attention + 3 * d * self.mlp_dim + 2 * d
        layer = (
            2 * sub_block + (d + 1) * self.router_experts
            + self.num_experts * 3 * d * self.expert_dim)
        return 2 * self.vocab_size * d + self.num_layers * layer + d

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output; the last one the
    #: token-expert pairs of real tokens that fell on a zero-compute expert
    counters = moe.COUNTERS + layers.MLA_COUNTERS + ("moe_zero_assignments",)

    @property
    def cache_layers(self) -> int:
        """Slabs of the cache: a token is cached in both sub-blocks of every layer."""
        return 2 * self.num_layers

    @property
    def cache_arrays(self):
        """What a cached token holds a sub-block, ``(heads, dim)`` per array: one row for
        all heads, the scaled normed latent, the rotated rotary key, zeros."""
        return ((1, self.row_dim),)

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def longcat_flash_nano(**kw) -> LongcatFlashConfig:
    """A tiny one for the tests: three layers (six sub-blocks), 4 of 16 routed experts
    held beside 8 zero-compute ones, 6 picks a token."""
    sizes = dict(
        vocab_size=256, num_layers=3, embed_dim=64, num_heads=8, q_rank=32, kv_rank=32,
        rope_dim=8, nope_dim=16, v_dim=16, mlp_dim=96, expert_dim=32, router_experts=24,
        zero_experts=8, num_experts=4, expert_offset=4, experts_per_token=6, bias_std=0.01,
        max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return LongcatFlashConfig(**{**sizes, **kw})


SUB_BLOCKS = ("first", "second")


def init_params(cfg: LongcatFlashConfig, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; norm scales 1; the router's bias float32
    with stddev ``bias_std``), made on the device in one jitted call, stacked under
    ``blocks/layers`` for ``extend``'s scan: a layer's two sub-blocks under ``first`` and
    ``second``, its router, bias and held experts under ``moe``. The two halves of
    ``W_kvb`` are stored apart (``k_up``, ``v_up``), the gate and the up projection of an
    MLP or an expert side by side."""
    d, f, h, L = cfg.embed_dim, cfg.expert_dim, cfg.num_heads, cfg.num_layers
    attn = {
        "q_a": (d, cfg.q_rank),
        "q_b": (cfg.q_rank, h, cfg.nope_dim + cfg.rope_dim),
        "kv_a": (d, cfg.kv_rank + cfg.rope_dim),
        "k_up": (cfg.kv_rank, h, cfg.nope_dim),
        "v_up": (cfg.kv_rank, h, cfg.v_dim),
        "o": (h, cfg.v_dim, d),
    }
    shapes = {
        "wte": (cfg.vocab_size, d),
        "head": (d, cfg.vocab_size),
        **{f"{sub}_{n}": (L,) + s for sub in SUB_BLOCKS for n, s in attn.items()},
        **{f"{sub}_mlp_wi": (L, d, 2 * cfg.mlp_dim) for sub in SUB_BLOCKS},
        **{f"{sub}_mlp_wo": (L, cfg.mlp_dim, d) for sub in SUB_BLOCKS},
        "router": (L, d, cfg.router_experts),
        "wi": (L, cfg.num_experts, d, 2 * f),
        "wo": (L, cfg.num_experts, f, d),
    }

    @jax.jit
    def init(rng):
        *keys, bias_key = jax.random.split(rng, len(shapes) + 1)
        w = layers.drawn(keys, shapes, cfg.param_dtype)
        ones = functools.partial(layers.ones_scale, cfg.param_dtype)

        def sub_block(sub):
            return {
                "ln_in": ones(L, d), "ln_post": ones(L, d),
                "attn": {
                    **{name: {"kernel": w[f"{sub}_{name}"]} for name in attn},
                    "q_norm": ones(L, cfg.q_rank), "kv_norm": ones(L, cfg.kv_rank),
                },
                "mlp": {"wi": w[f"{sub}_mlp_wi"], "wo": w[f"{sub}_mlp_wo"]},
            }

        return {
            "wte": {"embedding": w["wte"]},
            "blocks": {"layers": {
                **{sub: sub_block(sub) for sub in SUB_BLOCKS},
                "moe": {
                    "router": w["router"], "wi": w["wi"], "wo": w["wo"],
                    "bias": cfg.bias_std * jax.random.normal(
                        bias_key, (L, cfg.router_experts), jnp.float32),
                },
            }},
            "ln_f": ones(d),
            "head": {"kernel": w["head"]},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def make_extend_fn(cfg: LongcatFlashConfig):
    """A jitted ``extend(params, tokens, lengths, cache)`` with the contract of
    ``gpt.make_extend_fn`` over one cache of ``cfg.cache_layers`` slabs (``[2 x layers,
    lanes, cache, 1, row_dim]``, ``cfg.cache_arrays``): ``(logits, hidden, rows,
    counters)``, ``rows`` the call's own, a slab a sub-block. With ``table`` [lanes, n] (a
    call of one token a lane) ``cache`` is the pool's arena ``[2 x layers, blocks, block,
    1, row_dim]`` itself, neither written nor copied: each sub-block attends over the rows
    that the lane's first live pages of ``table`` hold and over the call's own row.
    ``counters`` (int32, ``cfg.counters``, summed over the layers) are
    ``moe.held_experts_ffn``'s four, the attention's (``layers.MLA_COUNTERS``, both
    sub-blocks') and the pairs that fell on a zero-compute expert, over real tokens only.
    A negative token id marks padding: it computes no expert and is not counted.

    Scopes: ``extend.embed``; ``extend.attention`` (cache update, the attend (on the chip a
    chunk's the kernel ``latent_attention`` and a call's through the table
    ``paged_attention``, straight under it), ``W_o``) with ``extend.attention.latent``
    inside it (both down-projections, their norms and scales, ``W_qb``, the rotations and,
    in the absorbed form, the absorption and the un-absorption), two a layer;
    ``extend.mlp`` (a sub-block's dense MLP), two a layer; ``extend.moe.route``,
    ``extend.moe.experts`` (the held experts' part), ``extend.moe.zero`` (the zero-compute
    picks' part); ``extend.logits`` (the last norm and the head, of the rows that are
    read: ``last=``, ``layers.read_rows``; every row without it).
    """
    dtype, f32 = cfg.dtype, jnp.float32
    rank = cfg.kv_rank
    scale = float(cfg.softmax_scale)

    def _normed(x, p, name):
        return layers.rms_norm(x, p[name]["scale"], cfg.norm_eps)

    def _rope(x, positions):
        """``x`` [b, t, heads, rope_dim], rotated in float32."""
        return layers.rotary(x.astype(f32), positions, cfg.rope_dim, cfg.rope_base).astype(dtype)

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, visible, live, kc, paged=None):
        """``visible`` [b, t, cache] is what each query may read, ``live`` [b] a bound
        past the lane's farthest real query: the same in every sub-block. ``kc`` is the
        sub-block's slab of the padded cache; or, with ``paged`` (the slab's index and
        the lanes' block table), the pool's arena itself."""
        with jax.named_scope("extend.attention.latent"):
            _, q, row = layers.latent_queries(
                p, hidden, positions, _rope, nope_dim=cfg.nope_dim, rank=rank,
                row_dim=cfg.row_dim, eps=cfg.norm_eps,
                expanded=layers.latent_expands(positions.shape[1]),
                q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)
        out = layers.latent_attend(
            p, q, row, positions, visible, live, kc, paged, rank=rank, scale=scale)
        return jnp.einsum("bthv,hvd->btd", out, p["o"]["kernel"].astype(dtype)), row

    def _sub_block(x, p, positions, reads, kc):
        """``(h, u, MLP(u), the token's row)`` of one sub-block over the stream ``x``."""
        a, row = _attend(p["attn"], _normed(x, p, "ln_in").astype(dtype), positions, *reads, *kc)
        h = x + a
        u = _normed(h, p, "ln_post")
        with jax.named_scope("extend.mlp"):
            m = layers.gated_mlp(u.astype(dtype), p["mlp"]["wi"], p["mlp"]["wo"])
        return h, u, m.astype(dtype), row

    def _experts(p, experts, layer, normed, valid):
        """The shortcut's value: the held experts' part and the zero-compute picks' part
        of the layer's routed sum, and the five counters."""
        b, tc, d = normed.shape
        flat = normed.reshape(b * tc, d)
        x, real = flat.astype(dtype), valid.reshape(b * tc)
        with jax.named_scope("extend.moe.route"):
            weights, chosen = moe.softmax_bias_top_k(
                flat, p["router"], p["bias"], cfg.experts_per_token, cfg.routed_scale)
        with jax.named_scope("extend.moe.experts"):
            routed, counters = moe.held_experts_ffn(
                x, weights, chosen, real, experts["wi"], experts["wo"], cfg.expert_offset, layer)
        with jax.named_scope("extend.moe.zero"):
            zero, fell = moe.zero_experts_part(x, weights, chosen, real, cfg.routed_experts)
        return (routed + zero).astype(dtype).reshape(b, tc, d), jnp.append(counters, fell)

    @jax.jit
    def extend(params, tokens, lengths, cache, *, last=None, table=None):
        positions, valid = layers.frame(tokens, lengths)
        cap = layers.cache_slots(cache, table)
        reads = (layers.visible_keys(positions, valid, cap), layers.live_keys(positions, valid))
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)

        def held(at):
            """What the sub-block whose slab is ``at`` attends over: its slab of the
            padded cache where it lies; or the arena itself, in which the kernel finds
            the slab's pages."""
            if table is not None:
                return cache, (at, table)
            return (jax.lax.dynamic_index_in_dim(cache, at, 0, keepdims=False),)

        scanned, routing, experts = layers.without_experts(params["blocks"]["layers"])
        scanned["moe"] = routing        # the router and its bias are a layer's own

        def body(x, xs):
            p, layer = xs
            h, u, m, first = _sub_block(x, p["first"], positions, reads, held(2 * layer))
            s, counters = _experts(p["moe"], experts, layer, u, valid)
            h, _, m, second = _sub_block(h + m, p["second"], positions, reads, held(2 * layer + 1))
            return h + m + s, (jnp.stack([first, second]), counters)

        x, (rows, routed) = jax.lax.scan(
            body, x, (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        routed = routed.sum(0)
        attended = layers.latent_counted(
            cfg.cache_layers, positions, valid, cap, reads[1],
            layers.latent_expands(tokens.shape[1]))
        return (
            logits, x, rows.reshape((cfg.cache_layers,) + rows.shape[2:]),
            jnp.concatenate([routed[:4], attended, routed[4:]]))

    return extend
