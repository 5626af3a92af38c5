"""Kimi-K2-Instruct (``model_type`` ``kimi_k2``, DeepSeek-V3's block): the
serving path behind ``serve/llm.py``.

One block is sequential and pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``, and differs from the engine's other architectures in what
a token leaves behind and in how a query reads it:

* attention is **latent** (MLA). A token leaves no K and no V but, for all
  heads, the RMS-normed latent ``c_kv`` (``kv_rank``) and one rotated rotary key
  ``k_rope`` (``rope_dim``): 576 values a layer at the published widths where 64
  heads of K and V would be 20480. They are cached as **one row**
  (``cache_arrays``), the latent, the key behind it and zeros up to the next
  whole number of the chip's 128-lane tiles (``row_dim``: 640, 1280 B a layer
  and token in bfloat16). The row as it lies is the key every head scores in
  the absorbed form and its first ``kv_rank`` features are the value: no
  concatenation, one arena, one gather. The padding is what the layout costs:
  a row of 576 makes the compiler lay the arena out with the block's tokens
  innermost, and every gather then re-lays all of it out; the latent and the
  key in two arenas (512 and 64) cost a re-layout of the 64-wide one in every
  gather and page-back, 2.6 ms a call (PERF.md, PR 34);
* queries come through a latent of their own (``q_rank``, RMS-normed); a head
  has ``nope_dim`` features that meet the latent and ``rope_dim`` that are
  rotated (:func:`layers.rotary`'s half-split pairing, YaRN's frequencies:
  :func:`yarn_frequencies`) and meet ``k_rope``;
* a decode call attends in the **absorbed** form: ``q' = q_nope W_kvb^K[h]``
  lies in the latent's space, the score is ``(q' . c_kv + q_rope . k_rope) * s``,
  the weighted sum of latents is taken through ``W_kvb^V[h]`` afterwards. No key
  or value of a head is made, whatever the length of the cache, 2 x 64 x (576 +
  512) operations a query-key pair. The engine hands such a call the pool's arena
  itself and the lanes' block table (``extend`` offers ``table=``, which
  ``serve/llm.py`` ``reads_pages`` looks for): on the chip
  ``ops/attention.paged_attention`` reads a lane's live pages once where they lie
  (a page is fetched once: its rows are scored whole and their first ``kv_rank``
  columns summed, no value arena) beside the call's own row, which is in no page
  yet; nothing is gathered, no slab of a padded cache is copied to write a row
  into it, and no score is made over the bucket. Off the chip the table's pages
  side by side are the padded cache, attended in ``jax.numpy`` as a prefill chunk
  off the chip is, 32 queries at a time: bit for bit what a gathered call gives;
* a prefill chunk on the chip attends in the **expanded** form (``[k_nope ; v] =
  c_kv W_kvb``, a head's own 192-wide key and 128-wide value: 2 x 64 x 320
  operations a pair), in ``ops/attention.latent_attention``: the kernel puts
  each tile of the lane's live rows through ``W_kvb`` in VMEM, eight heads after
  one another, so a head's key and value never reach HBM (1.34 GB a layer over
  32768 slots if they did) and the queries go in as ``W_qb`` leaves them, no
  absorption before and no un-absorption after. ``W_kvb`` over a slot costs 2 x
  512 x 256 x 64 operations once a chunk, what 171 queries save: a chunk's 512
  pay for it three times over (37.8 M operations a slot for 71.3 M, at every
  cache length), a decode call's one query a slot never would. What is cached
  is the same row, to the bit, whichever form reads it;
* the first ``dense_layers`` layers have a gated MLP; the others an expert
  layer (``models/moe.py``): float32 sigmoid scores over all ``router_experts``,
  the ``experts_per_token`` with the largest score + bias chosen
  (``e_score_correction_bias``, which chooses and does not weigh), their scores
  over their sum times ``routed_scale``, the ``num_experts`` from
  ``expert_offset`` on held here; and ``shared_experts`` experts' width of
  gated MLP that every token passes through, added unweighted.

The dense layers run before the scan over the expert layers, and are not of
their shape; their cache rows lie in the same arena, first. The embedding is
not tied to the output head.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers, moe

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(
    dim: int, base: float, factor: float, original_len: int, beta_fast: float,
    beta_slow: float,
) -> np.ndarray:
    """YaRN's ``dim / 2`` rotation frequencies (float64). Frequency ``i`` of
    ``base^(-2i / dim)`` turns ``original_len * that / 2 pi`` times over the
    original context; those that turn more than ``beta_fast`` times stay
    (extrapolation), those that turn fewer than ``beta_slow`` times are divided
    by ``factor`` (interpolation), with a linear ramp over the dimensions between
    the two (``find_correction_range``'s floor and ceiling)."""
    theta = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return theta

    def correction_dim(rotations):
        return dim * math.log(original_len / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / factor * ramp


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    num_layers: int = 61
    dense_layers: int = 1           # leading layers with a gated MLP
    embed_dim: int = 7168
    num_heads: int = 64
    q_rank: int = 1536              # the queries' latent
    kv_rank: int = 512              # the cached latent ...
    rope_dim: int = 64              # ... and the rotary key behind it
    nope_dim: int = 128             # a head's features that meet the latent
    v_dim: int = 128
    mlp_dim: int = 18432            # width of a dense layer's MLP
    expert_dim: int = 2048          # width of one routed or shared expert
    router_experts: int = 384       # experts the router scores
    num_experts: int = 384          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 8
    shared_experts: int = 1
    routed_scale: float = 2.827
    bias_std: float = 0.01          # spread of the seeded e_score_correction_bias
    rope_base: float = 50000.0
    rope_factor: float = 32.0       # YaRN; 1 is plain rotary
    rope_original_len: int = 4096
    rope_beta_fast: float = 1.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0    # (cos and sin are not scaled: ``mscale`` equals it)
    norm_eps: float = 1e-6
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")
        if not 1 <= self.dense_layers < self.num_layers:
            raise ValueError(
                f"{self.dense_layers} dense layers of {self.num_layers}: the program runs "
                f"at least one before its scan over at least one expert layer")

    @property
    def row_dim(self) -> int:
        """Width of a cached row: the latent and the rotary key, padded with
        zeros to whole 128-lane tiles."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def rope_frequencies(self) -> np.ndarray:
        return yarn_frequencies(
            self.rope_dim, self.rope_base, self.rope_factor, self.rope_original_len,
            self.rope_beta_fast, self.rope_beta_slow)

    @property
    def softmax_scale(self) -> float:
        """``(nope_dim + rope_dim)^-0.5``, times YaRN's ``mscale^2``."""
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if self.rope_factor > 1 and self.rope_mscale_all_dim:
            scale *= yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2
        return scale

    def num_params(self) -> int:
        d, h = self.embed_dim, self.num_heads
        attention = (
            d * self.q_rank + self.q_rank + self.q_rank * h * (self.nope_dim + self.rope_dim)
            + d * (self.kv_rank + self.rope_dim) + self.kv_rank
            + self.kv_rank * h * (self.nope_dim + self.v_dim) + h * self.v_dim * d + 2 * d)
        expert = 3 * d * self.expert_dim
        dense = attention + 3 * d * self.mlp_dim
        routed = attention + (self.num_experts + self.shared_experts) * expert + (
            (d + 1) * self.router_experts)
        return (
            2 * self.vocab_size * d + self.dense_layers * dense
            + self.expert_layers * routed + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS + layers.MLA_COUNTERS

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: one row for
        all heads, the normed latent, the rotated rotary key, zeros."""
        return ((1, self.row_dim),)

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def kimi_k2_nano(**kw) -> KimiK2Config:
    """A tiny one for the tests: one dense layer, three expert layers, 4 of 16
    experts held."""
    sizes = dict(
        vocab_size=256, num_layers=4, dense_layers=1, embed_dim=64, num_heads=8, q_rank=32,
        kv_rank=32, rope_dim=8, nope_dim=16, v_dim=16, mlp_dim=96, expert_dim=32,
        router_experts=16, num_experts=4, expert_offset=4, experts_per_token=4,
        shared_experts=1, bias_std=0.05, rope_original_len=32, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return KimiK2Config(**{**sizes, **kw})


def init_params(cfg: KimiK2Config, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; norm scales 1; the router's bias
    float32 with stddev ``bias_std``), made on the device in one jitted call:
    the dense layers stacked under ``first``, the expert layers under
    ``blocks/layers`` for ``extend``'s scan. The two halves of ``W_kvb`` are
    stored apart (``k_up``, ``v_up``), the gate and the up projection of an MLP
    or an expert side by side."""
    d, f, h = cfg.embed_dim, cfg.expert_dim, cfg.num_heads
    D, L = cfg.dense_layers, cfg.expert_layers
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
        **{f"first_{n}": (D,) + s for n, s in attn.items()},
        **{f"layers_{n}": (L,) + s for n, s in attn.items()},
        "mlp_wi": (D, d, 2 * cfg.mlp_dim),
        "mlp_wo": (D, cfg.mlp_dim, d),
        "router": (L, d, cfg.router_experts),
        "wi": (L, cfg.num_experts, d, 2 * f),
        "wo": (L, cfg.num_experts, f, d),
        "shared_wi": (L, d, 2 * f * cfg.shared_experts),
        "shared_wo": (L, f * cfg.shared_experts, d),
    }

    @jax.jit
    def init(rng):
        *keys, bias_key = jax.random.split(rng, len(shapes) + 1)
        w = layers.drawn(keys, shapes, cfg.param_dtype)
        ones = functools.partial(layers.ones_scale, cfg.param_dtype)

        def block(prefix, n):
            return {
                "ln_1": ones(n, d), "ln_2": ones(n, d),
                "attn": {
                    **{name: {"kernel": w[f"{prefix}_{name}"]} for name in attn},
                    "q_norm": ones(n, cfg.q_rank), "kv_norm": ones(n, cfg.kv_rank),
                },
            }

        return {
            "wte": {"embedding": w["wte"]},
            "first": {**block("first", D), "mlp": {"wi": w["mlp_wi"], "wo": w["mlp_wo"]}},
            "blocks": {"layers": {
                **block("layers", L),
                "moe": {
                    "router": w["router"], "wi": w["wi"], "wo": w["wo"],
                    "bias": cfg.bias_std * jax.random.normal(
                        bias_key, (L, cfg.router_experts), jnp.float32),
                },
                "shared": {"wi": w["shared_wi"], "wo": w["shared_wo"]},
            }},
            "ln_f": ones(d),
            "head": {"kernel": w["head"]},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def make_extend_fn(cfg: KimiK2Config):
    """A jitted ``extend(params, tokens, lengths, cache)`` with the contract of
    ``gpt.make_extend_fn`` over one cache (``[layers, lanes, cache, 1,
    row_dim]``, ``cfg.cache_arrays``): ``(logits, hidden, rows, counters)``.
    With ``table`` [lanes, n] (a call of one token a lane) ``cache`` is the pool's
    arena ``[layers, blocks, block, 1, row_dim]`` itself, neither written nor
    copied: each layer attends over the rows that the lane's first live pages of
    ``table`` hold and over the call's own row, which goes back in ``rows`` as ever.
    ``counters`` (int32, ``cfg.counters``, summed over the layers) are
    ``moe.held_experts_ffn``'s four and the attention's (``layers.MLA_COUNTERS``), over
    real tokens only. A negative token id marks padding: it computes no expert
    and is not counted.

    Scopes: ``extend.embed``; ``extend.attention`` (cache update, the attend
    (on the chip a chunk's the kernel ``latent_attention`` and a call's through the
    table ``paged_attention``, straight under it), ``W_o``) with ``extend.attention.latent`` inside it (both down-projections,
    their norms, ``W_qb``, the rotations and, in the absorbed form, the
    absorption and the un-absorption);
    ``extend.mlp`` (a dense layer's); ``extend.moe.route``, ``extend.moe.experts``,
    ``extend.moe.shared``; ``extend.logits`` (the last norm and the head, of the rows
    that are read: ``last=``, ``layers.read_rows``; every row without it).
    """
    dtype, f32 = cfg.dtype, jnp.float32
    rank = cfg.kv_rank
    scale = float(cfg.softmax_scale)
    freqs = jnp.asarray(cfg.rope_frequencies, f32)

    def _normed(x, p, name):
        return layers.rms_norm(x, p[name]["scale"], cfg.norm_eps)

    def _rope(x, positions):
        """``x`` [b, t, heads, rope_dim], rotated in float32."""
        return layers.rotary(x.astype(f32), positions, cfg.rope_dim, freqs=freqs).astype(dtype)

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, visible, live, kc, paged=None):
        """``visible`` [b, t, cache] is what each query may read, ``live`` [b]
        a bound past the lane's farthest real query: the same in every layer.
        ``kc`` is the layer's slab of the padded cache; or, with ``paged`` (the layer's
        index and the lanes' block table: a call of one token a lane), the pool's
        arena itself, read where it lies, not written and not copied."""
        with jax.named_scope("extend.attention.latent"):
            _, q, row = layers.latent_queries(
                p, hidden, positions, _rope, nope_dim=cfg.nope_dim, rank=rank,
                row_dim=cfg.row_dim, eps=cfg.norm_eps,
                expanded=layers.latent_expands(positions.shape[1]))
        out = layers.latent_attend(
            p, q, row, positions, visible, live, kc, paged, rank=rank, scale=scale)
        return jnp.einsum("bthv,hvd->btd", out, p["o"]["kernel"].astype(dtype)), row

    def _experts(p, experts, layer, normed, valid):
        b, tc, d = normed.shape
        flat = normed.reshape(b * tc, d)
        x = flat.astype(dtype)
        with jax.named_scope("extend.moe.route"):
            weights, chosen = moe.sigmoid_bias_top_k(
                flat, p["moe"]["router"], p["moe"]["bias"], cfg.experts_per_token,
                cfg.routed_scale)
        with jax.named_scope("extend.moe.experts"):
            routed, counters = moe.held_experts_ffn(
                x, weights, chosen, valid.reshape(b * tc), experts["wi"], experts["wo"],
                cfg.expert_offset, layer)
        with jax.named_scope("extend.moe.shared"):
            shared = layers.gated_mlp(x, p["shared"]["wi"], p["shared"]["wo"])
        return (routed + shared).astype(dtype).reshape(b, tc, d), counters

    def _block(x, p, positions, reads, kc, ffn):
        a, row = _attend(p["attn"], _normed(x, p, "ln_1").astype(dtype), positions, *reads, *kc)
        x = x + a
        return x, row, ffn(_normed(x, p, "ln_2"))

    @jax.jit
    def extend(params, tokens, lengths, cache, *, last=None, table=None):
        positions, valid = layers.frame(tokens, lengths)
        cap = layers.cache_slots(cache, table)
        reads = (layers.visible_keys(positions, valid, cap), layers.live_keys(positions, valid))
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)

        def held(at, slab):
            """What layer ``at`` attends over: its slab of the padded cache where it
            lies; or the arena itself, in which the kernel finds the layer's pages."""
            return (slab(),) if table is None else (cache, (at, table))

        rows = []
        for at in range(cfg.dense_layers):
            p = jax.tree.map(lambda a: a[at], params["first"])

            def mlp(normed):
                with jax.named_scope("extend.mlp"):
                    return layers.gated_mlp(
                        normed.astype(dtype), p["mlp"]["wi"], p["mlp"]["wo"])

            x, row, f = _block(x, p, positions, reads, held(at, lambda: cache[at]), mlp)
            x = x + f.astype(dtype)
            rows.append(row)

        scanned, routing, experts = layers.without_experts(params["blocks"]["layers"])
        scanned["moe"] = routing        # the router and its bias are a layer's own

        def body(carry, xs):
            p, layer = xs
            at = cfg.dense_layers + layer           # behind the dense layers'
            kc = held(at, lambda: jax.lax.dynamic_index_in_dim(cache, at, 0, keepdims=False))
            carry, row, (f, counters) = _block(
                carry, p, positions, reads, kc,
                lambda normed: _experts(p, experts, layer, normed, valid))
            return carry + f, (row, counters)

        x, (scanned, routed) = jax.lax.scan(
            body, x, (scanned, jnp.arange(cfg.expert_layers, dtype=jnp.int32)))
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        attended = layers.latent_counted(
            cfg.num_layers, positions, valid, cap, reads[1],
            layers.latent_expands(tokens.shape[1]))
        return (
            logits, x, jnp.concatenate([jnp.stack(rows), scanned]),
            jnp.concatenate([routed.sum(0), attended]))

    return extend
