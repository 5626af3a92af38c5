"""Keye-VL-2.0-30B-A3B's language model (``model_type`` ``KeyeVL2``): the
serving path behind ``serve/llm.py``.

One block is sequential and pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``, and differs from the engine's other two architectures in
what a query reads and in what a token leaves behind:

* attention is grouped (``num_heads // kv_heads`` query heads read one K/V
  head), q and k are RMS-normed per head and rotated over every feature of a
  head (``layers.rotary``'s half-split pairing, which is the family's own);
* a learned **indexer** decides which keys a query reads. Every token leaves
  an indexer key ``kI`` [``index_dim``] beside its K and V; a query ``t`` has
  ``index_heads`` indexer queries ``qI`` and as many weights ``w``, scores
  every key before it, ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in
  float32, and attends (all its heads alike) over the ``topk`` keys with the
  largest ``I`` and over no other; ties go to the lower position. Up to
  ``topk`` keys of context that is causal attention. So a cached token is
  three arrays (``cache_arrays``), and the engine's pool holds an arena for
  each;
* the FFN is an expert layer (``models/moe.py``): a float32 softmax over all
  ``num_experts``, the ``experts_per_token`` largest divided by their sum, every
  expert held here, no shared expert, no token dropped.

The selection has two forms, chosen by the call's shape and giving the same
keys. A decode lane (one query) takes the top ``topk`` of its scores and
gathers those rows of K and V from the padded cache. A prefill chunk would move
``tokens x topk`` rows that way (thirty times the cache at 512 tokens), so it
finds each query's ``topk``-th largest score instead (a bisection on the
scores' bits: 32 passes over them, no sort) and attends under the mask ``I >=
that score``, with the ties at that score counted off from the lowest
position: on the chip in one kernel that keeps the scores in VMEM and stops
at the lane's last live key (``ops/attention.masked_attention``), off it
densely, 32 queries at a time.

The embedding is not tied to the output head. Text positions only: the
published rotation splits its frequencies over three position streams
(``mrope_section``), and a text token carries one position in all three.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers, moe
from ray_tpu.ops import attention, backend

@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    num_layers: int = 48
    embed_dim: int = 2048
    num_heads: int = 32
    kv_heads: int = 4               # K/V heads: what a cache stores
    head_dim: int = 128
    expert_dim: int = 768           # width of one expert
    num_experts: int = 128
    experts_per_token: int = 8
    index_heads: int = 16           # the indexer's query heads ...
    index_dim: int = 64             # ... over one key head of this size
    topk: int = 2048                # keys a query attends
    rope_base: float = 1e7
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not divide over {self.kv_heads} K/V heads")

    def num_params(self) -> int:
        d = self.embed_dim
        attention = 2 * d * self.head_dim * (self.num_heads + self.kv_heads) + 2 * self.head_dim
        indexer = d * (self.index_heads * self.index_dim + self.index_dim + self.index_heads)
        experts = self.num_experts * 3 * d * self.expert_dim
        per_layer = attention + indexer + experts + d * self.num_experts + 2 * d
        return 2 * self.vocab_size * d + self.num_layers * per_layer + d

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS + layers.SPARSE_COUNTERS

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K, V and the
        indexer's key."""
        return ((self.kv_heads, self.head_dim),) * 2 + ((1, self.index_dim),)

    def count_gathered(self, lanes: int, cache: int) -> Dict[str, int]:
        """What a call's padded caches hold for the indexer to choose from:
        every slot the engine gathered, in every layer."""
        return {"sparse_slots_gathered": self.num_layers * lanes * cache}

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def keye_vl2_nano(**kw) -> KeyeVL2Config:
    """A tiny one for the tests: a query reads 16 keys."""
    sizes = dict(
        vocab_size=256, num_layers=3, embed_dim=64, num_heads=8, kv_heads=2, head_dim=16,
        expert_dim=32, num_experts=16, experts_per_token=4, index_heads=4, index_dim=8,
        topk=16, max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return KeyeVL2Config(**{**sizes, **kw})


def init_params(cfg: KeyeVL2Config, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; norm scales 1), made on the device
    in one jitted call, the layers stacked for ``extend``'s scan. The gate and
    the up projection of an expert are stored side by side."""
    L, d, f = cfg.num_layers, cfg.embed_dim, cfg.expert_dim
    shapes = {
        "wte": (cfg.vocab_size, d),
        "head": (d, cfg.vocab_size),
        "q": (L, d, cfg.num_heads, cfg.head_dim),
        "k": (L, d, cfg.kv_heads, cfg.head_dim),
        "v": (L, d, cfg.kv_heads, cfg.head_dim),
        "o": (L, cfg.num_heads, cfg.head_dim, d),
        "index_q": (L, d, cfg.index_heads, cfg.index_dim),
        "index_k": (L, d, cfg.index_dim),
        "index_w": (L, d, cfg.index_heads),
        "router": (L, d, cfg.num_experts),
        "wi": (L, cfg.num_experts, d, 2 * f),
        "wo": (L, cfg.num_experts, f, d),
    }

    @jax.jit
    def init(rng):
        w = layers.drawn(jax.random.split(rng, len(shapes)), shapes, cfg.param_dtype)
        ones = functools.partial(layers.ones_scale, cfg.param_dtype)

        return {
            "wte": {"embedding": w["wte"]},
            "blocks": {"layers": {
                "ln_1": ones(L, d), "ln_2": ones(L, d),
                "attn": {
                    **{n: {"kernel": w[n]} for n in ("q", "k", "v", "o")},
                    "q_norm": ones(L, cfg.head_dim), "k_norm": ones(L, cfg.head_dim),
                },
                "index": {n: {"kernel": w["index_" + n]} for n in ("q", "k", "w")},
                "moe": {"router": w["router"], "wi": w["wi"], "wo": w["wo"]},
            }},
            "ln_f": ones(d),
            "head": {"kernel": w["head"]},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# -- extend ---------------------------------------------------------------------


def make_extend_fn(cfg: KeyeVL2Config):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, i_cache)``
    with the contract of ``gpt.make_extend_fn`` over three caches (``[layers,
    lanes, cache, heads, dim]`` each, ``cfg.cache_arrays``): ``(logits, hidden,
    k_new, v_new, i_new, counters)``. ``counters`` (int32, ``cfg.counters``,
    summed over the layers) are ``moe.held_experts_ffn``'s four and the
    indexer's (``layers.SPARSE_COUNTERS``), over real tokens only. A negative token id
    marks padding: it computes no expert, selects no key and is not counted.

    Scopes: ``extend.embed``, ``extend.attention`` (projections, norms, rotary,
    cache update, the attend) with ``extend.attention.index`` (the indexer's
    projections and scores) and ``extend.attention.select`` (top-k or
    threshold, row gather or mask) inside it, ``extend.moe.route``,
    ``extend.moe.experts``, ``extend.logits`` (the last norm and the head, of the rows
    that are read: ``last=``, ``layers.read_rows``; every row without it).
    """
    return _make_extend(cfg, probe=False)


def make_probe_fn(cfg: KeyeVL2Config):
    """``extend`` with one more output behind the counters: what each query
    selected, bool ``[layers, lanes, tokens, cache]``. For the tests and for
    the comparison of the selection with the reference's, not for serving."""
    return _make_extend(cfg, probe=True)


def _make_extend(cfg: KeyeVL2Config, probe: bool):
    dtype = cfg.dtype
    scale = 1.0 / float(np.sqrt(cfg.head_dim))
    groups = cfg.num_heads // cfg.kv_heads
    f32 = jnp.float32

    def _project(hidden, p, name):
        return jnp.einsum("btd,dhk->bthk", hidden, p[name]["kernel"].astype(dtype))

    def _index_scores(p, hidden, positions, ic):
        """The new indexer keys [b, tc, 1, index_dim] and, over the cache with
        them written, ``I`` [b, tc, cache] in float32."""
        with jax.named_scope("extend.attention.index"):
            qi = layers.rotary(
                _project(hidden, p, "q").astype(f32), positions, cfg.index_dim,
                cfg.rope_base).astype(dtype)
            ki = jnp.einsum("btd,dk->btk", hidden, p["k"]["kernel"].astype(dtype))[:, :, None]
            ki = layers.rotary(
                ki.astype(f32), positions, cfg.index_dim, cfg.rope_base).astype(dtype)
            w = jnp.einsum(
                "btd,dh->bth", hidden, p["w"]["kernel"].astype(dtype),
                preferred_element_type=f32)
            lane = jnp.arange(positions.shape[0])[:, None]
            ic = layers.write_rows(ic, lane, positions, ki)

            def score_block(qb, wb):            # [b, n, heads, dim], [b, n, heads]
                dots = jnp.einsum(
                    "bqhd,bkd->bqhk", qb, ic[:, :, 0], preferred_element_type=f32)
                # a float32 sum, not a matmul: the chip would round one to bfloat16
                return (jax.nn.relu(dots) * wb[..., None]).sum(2)

            return ki, layers.by_query_block(score_block, qi, w)

    @jax.named_scope("extend.attention")
    def _attend(p, p_index, hidden, positions, valid, kc, vc, ic):
        b, tc = positions.shape
        cap = kc.shape[1]

        def normed_and_rotated(name):
            x = layers.rms_norm(
                _project(hidden, p, name), p[name + "_norm"]["scale"], cfg.norm_eps)
            return layers.rotary(x, positions, cfg.head_dim, cfg.rope_base).astype(dtype)

        q, k = normed_and_rotated("q"), normed_and_rotated("k")
        v = _project(hidden, p, "v")
        lane = jnp.arange(b)[:, None]
        kc = layers.write_rows(kc, lane, positions, k)
        vc = layers.write_rows(vc, lane, positions, v)
        ki, scores = _index_scores(p_index, hidden, positions, ic)
        visible = layers.visible_keys(positions, valid, cap)
        q = q.reshape(b, tc, cfg.kv_heads, groups, cfg.head_dim)

        if tc == 1:
            # a decode lane: the chosen rows of K and V, and no other
            with jax.named_scope("extend.attention.select"):
                rows, chosen = layers.select_rows(scores, visible, cfg.topk)   # [b, 1, k']
                at = lane[:, :, None]
                k_rows, v_rows = kc[at, rows], vc[at, rows]             # [b, 1, k', kv, hd]
                slots_read = chosen.sum(dtype=jnp.int32)
                selected = (
                    jnp.zeros((b, cap), jnp.int32).at[lane, rows[:, 0]].add(
                        chosen[:, 0].astype(jnp.int32))[:, None] > 0
                    if probe else None)
            logit = jnp.einsum(
                "bqhgd,bqkhd->bqhgk", q, k_rows, preferred_element_type=f32) * scale
            weight = jax.nn.softmax(
                jnp.where(chosen[:, :, None, None], logit, f32(layers.MASKED)), axis=-1)
            out = jnp.einsum("bqhgk,bqkhd->bqhgd", weight.astype(dtype), v_rows)
        else:
            # a prefill chunk: every row of the cache, under each query's mask
            with jax.named_scope("extend.attention.select"):
                selected = layers.select_mask(scores, visible, cfg.topk)       # [b, tc, cache]
                slots_read = selected.any(1).sum(dtype=jnp.int32)

            if backend.on_tpu():
                out = attention.masked_attention(
                    q, kc, vc, selected, layers.live_keys(positions, valid), scale=scale)
            else:
                out = layers.by_query_block(
                    lambda qb, mask: layers.plain_attend(qb, kc, vc, mask, scale), q, selected)
        out = jnp.einsum(
            "bqhd,hde->bqe", out.reshape(b, tc, cfg.num_heads, cfg.head_dim),
            p["o"]["kernel"].astype(dtype))
        seen = jnp.where(valid, jnp.minimum(positions + 1, cap), 0)
        counters = jnp.stack([
            valid.sum(dtype=jnp.int32), seen.sum(dtype=jnp.int32),
            jnp.minimum(seen, cfg.topk).sum(dtype=jnp.int32), slots_read])
        return out, (k, v, ki), counters, selected

    def _ffn(router, experts, layer, normed, valid):
        b, tc, d = normed.shape
        flat = normed.reshape(b * tc, d)
        with jax.named_scope("extend.moe.route"):
            weights, chosen = moe.softmax_top_k(flat, router, cfg.experts_per_token)
        with jax.named_scope("extend.moe.experts"):
            routed, counters = moe.held_experts_ffn(
                flat.astype(dtype), weights, chosen, valid.reshape(b * tc),
                experts["wi"], experts["wo"], 0, layer)
        return routed.astype(dtype).reshape(b, tc, d), counters

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, i_cache, *, last=None):
        positions, valid = layers.frame(tokens, lengths)
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)
        scanned, routing, experts = layers.without_experts(params["blocks"]["layers"])

        def body(carry, xs):
            p, router, kc, vc, ic, layer = xs
            a, news, sparse, selected = _attend(
                p["attn"], p["index"],
                layers.rms_norm(carry, p["ln_1"]["scale"], cfg.norm_eps).astype(dtype),
                positions, valid, kc, vc, ic)
            carry = carry + a
            f, routed = _ffn(
                router, experts, layer,
                layers.rms_norm(carry, p["ln_2"]["scale"], cfg.norm_eps), valid)
            counters = jnp.concatenate([routed, sparse])
            return carry + f, news + ((counters, selected) if probe else (counters,))

        x, (k_new, v_new, i_new, counters, *selected) = jax.lax.scan(
            body, x, (
                scanned, routing["router"], k_cache, v_cache, i_cache,
                jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        return (logits, x, k_new, v_new, i_new, counters.sum(0), *selected)

    return extend
