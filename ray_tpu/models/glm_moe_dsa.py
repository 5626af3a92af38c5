"""GLM-5 (``model_type`` ``glm_moe_dsa``: latent attention under DeepSeek-V3.2's
learned sparse selection, "DSA"): the serving path behind ``serve/llm.py``.

With ``N(x) = x / sqrt(mean(x^2) + norm_eps) * g`` and ``n = N(h)``, a block is
``h += Attn(N(h))``, then ``h += FFN(N(h))``:

* **latents.** ``c_q = N(W_qa n)`` (``q_rank``); ``[c_kv ; k_r] = W_kva n`` (``kv_rank +
  rope_dim``), ``c_kv <- N(c_kv)``, ``k_r <- rot(k_r)``. A head ``j``: ``[q_nope ; q_r] =
  W_qb[j] c_q`` (``nope_dim + rope_dim``), ``q_r <- rot(q_r)``; ``[k_nope ; v] = W_kvb[j]
  c_kv`` (``nope_dim + v_dim``). ``rot`` is plain rotary at ``rope_base`` over
  ``rope_dim`` features (:func:`layers.rotary`'s half-split pairing: the published
  interleaved one under a stored permutation, which leaves every ``q_r . k_r`` as it
  is);
* **the indexer**, a layer's own weights: ``qI[t, i] = W_Iq[i] c_q(t)``
  (``index_heads`` of ``index_dim``; it reads the query latent the attention itself
  makes, so ``c_q`` is made once for both), its first ``index_rope_dim`` features
  rotated; ``kI[s] = LayerNorm(W_Ik n(s))`` (scale and bias, ``index_norm_eps``), its
  first ``index_rope_dim`` rotated; ``w[t] = W_Iw n(t) * index_heads^-0.5 *
  index_dim^-0.5``; ``I(t, s) = sum_i w[t, i] relu(qI[t, i] . kI[s])`` in float32 for ``s
  <= t``. ``S_t`` = the ``topk`` positions with the largest ``I(t, .)``, all of them
  while ``t < topk``; ties go to the lower position;
* **attention** over ``s`` in ``S_t`` only: ``softmax_s((q_nope . k_nope(s) + q_r .
  k_r(s)) * (nope_dim + rope_dim)^-0.5)`` over ``v(s)``, then ``W_o``. A token leaves
  ``[c_kv ; k_r]`` (one row for all heads, zeros up to whole 128-lane tiles:
  ``kimi_k2.py``'s row and its reasons) and ``kI``: two arenas (``cache_arrays``),
  nothing a head. What is selected is a **row every head shares**, and it is read in
  two forms that give the same result from the same cached bits:

  - a decode lane takes the top ``topk`` of its scores (:func:`layers.select_rows`),
    gathers those rows of the padded latent cache once for all heads and attends in
    the **absorbed** form: ``q' = q_nope W_kvb^K[j]`` scores a row's ``kv_rank +
    rope_dim`` features, the value is the row's first ``kv_rank`` through
    ``W_kvb^V[j]`` afterwards. No key or value of a head is made;
  - a prefill chunk would move ``tokens x topk`` rows that way, so it finds each
    query's ``topk``-th score by bisection (:func:`layers.select_mask`) and attends
    every live row under that mask: on the chip in the **expanded** form
    (``ops/attention.latent_attention``: each tile of rows through ``W_kvb`` in
    VMEM, the selection in place of the causal mask; the tiles a block's queries did
    not select are still expanded), off it absorbed, 32 queries at a time;

* the first ``dense_layers`` layers have a gated-SiLU MLP; the others an expert layer
  (``models/moe.py``): ``s = sigmoid(W_r n)`` over all ``router_experts`` in float32,
  the ``experts_per_token`` with the largest ``s + b`` chosen (``b`` chooses and does
  not weigh), weights ``s / sum(chosen s) * routed_scale``, the ``num_experts`` from
  ``expert_offset`` on held here; ``shared_experts`` experts' width of gated MLP that
  every token passes, unweighted.

The dense layers run before the scan over the expert layers; their cache rows lie
first in both arenas. A final ``N`` and an untied head. The multi-token-prediction
layer of the published model is not here: the engine's step yields one token a
sequence.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import layers, moe
from ray_tpu.ops import attention


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    num_layers: int = 78
    dense_layers: int = 3           # leading layers with a gated MLP
    embed_dim: int = 6144
    num_heads: int = 64
    q_rank: int = 2048              # the queries' latent, which the indexer reads too
    kv_rank: int = 512              # the cached latent ...
    rope_dim: int = 64              # ... and the rotary key behind it
    nope_dim: int = 192             # a head's features that meet the latent
    v_dim: int = 256
    index_heads: int = 32           # the indexer's query heads ...
    index_dim: int = 128            # ... over one key of this size a token,
    index_rope_dim: int = 64        # whose first features are rotated
    topk: int = 2048                # cached rows a query attends
    mlp_dim: int = 12288            # width of a dense layer's MLP
    expert_dim: int = 2048          # width of one routed or shared expert
    router_experts: int = 256       # experts the router scores
    num_experts: int = 256          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 8
    shared_experts: int = 1
    routed_scale: float = 2.5
    bias_std: float = 0.01          # spread of the seeded e_score_correction_bias
    index_bias_std: float = 0.5     # spread of the seeded bias of the indexer key's LayerNorm
    rope_base: float = 1e6
    norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    max_seq_len: int = 202752
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
        if not 0 <= self.index_rope_dim <= self.index_dim:
            raise ValueError(
                f"{self.index_rope_dim} rotated features of an indexer head of {self.index_dim}")

    @property
    def row_dim(self) -> int:
        """Width of a cached latent row: the latent and the rotary key, padded with
        zeros to whole 128-lane tiles."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def index_scale(self) -> float:
        """What the indexer's weights ``w`` are multiplied with."""
        return self.index_heads ** -0.5 * self.index_dim ** -0.5

    def num_params(self) -> int:
        d, h = self.embed_dim, self.num_heads
        attention = (
            d * self.q_rank + self.q_rank + self.q_rank * h * (self.nope_dim + self.rope_dim)
            + d * (self.kv_rank + self.rope_dim) + self.kv_rank
            + self.kv_rank * h * (self.nope_dim + self.v_dim) + h * self.v_dim * d + 2 * d)
        indexer = (
            self.q_rank * self.index_heads * self.index_dim + d * self.index_dim
            + d * self.index_heads + 2 * self.index_dim)
        expert = 3 * d * self.expert_dim
        dense = attention + indexer + 3 * d * self.mlp_dim
        routed = attention + indexer + (self.num_experts + self.shared_experts) * expert + (
            (d + 1) * self.router_experts)
        return (
            2 * self.vocab_size * d + self.dense_layers * dense
            + self.expert_layers * routed + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS + layers.MLA_COUNTERS + layers.SPARSE_COUNTERS

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: the latent row
        all heads share, and the indexer's key."""
        return ((1, self.row_dim), (1, self.index_dim))

    def count_gathered(self, lanes: int, cache: int) -> Dict[str, int]:
        """What a call's padded caches hold for the indexer to choose from:
        every slot the engine gathered, in every layer."""
        return {"sparse_slots_gathered": self.num_layers * lanes * cache}

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def glm_moe_dsa_nano(**kw) -> GlmMoeDsaConfig:
    """A tiny one for the tests: one dense layer, three expert layers, 4 of 16
    experts held, a query reads 16 rows."""
    sizes = dict(
        vocab_size=256, num_layers=4, dense_layers=1, embed_dim=64, num_heads=4, q_rank=24,
        kv_rank=32, rope_dim=8, nope_dim=12, v_dim=16, index_heads=2, index_dim=16,
        index_rope_dim=8, topk=16, mlp_dim=96, expert_dim=32, router_experts=16, num_experts=4,
        expert_offset=4, experts_per_token=4, shared_experts=1, bias_std=0.05, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return GlmMoeDsaConfig(**{**sizes, **kw})


def init_params(cfg: GlmMoeDsaConfig, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; norm scales 1; the router's bias float32
    with stddev ``bias_std``, the bias of the indexer key's LayerNorm with
    ``index_bias_std``: drawn, so that leaving it out selects other rows), made
    on the device in one jitted call: the dense layers stacked under ``first``, the
    expert layers under ``blocks/layers`` for ``extend``'s scan. The two halves of
    ``W_kvb`` are stored apart (``k_up``, ``v_up``), the gate and the up projection of
    an MLP or an expert side by side."""
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
    index = {
        "q": (cfg.q_rank, cfg.index_heads, cfg.index_dim),
        "k": (d, cfg.index_dim),
        "w": (d, cfg.index_heads),
    }
    shapes = {
        "wte": (cfg.vocab_size, d),
        "head": (d, cfg.vocab_size),
        **{f"first_{n}": (D,) + s for n, s in attn.items()},
        **{f"layers_{n}": (L,) + s for n, s in attn.items()},
        **{f"first_index_{n}": (D,) + s for n, s in index.items()},
        **{f"layers_index_{n}": (L,) + s for n, s in index.items()},
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
        *keys, bias_key, first_key, layers_key = jax.random.split(rng, len(shapes) + 3)
        w = layers.drawn(keys, shapes, cfg.param_dtype)
        ones = functools.partial(layers.ones_scale, cfg.param_dtype)

        def block(prefix, n, key):
            return {
                "ln_1": ones(n, d), "ln_2": ones(n, d),
                "attn": {
                    **{name: {"kernel": w[f"{prefix}_{name}"]} for name in attn},
                    "q_norm": ones(n, cfg.q_rank), "kv_norm": ones(n, cfg.kv_rank),
                },
                "index": {
                    **{name: {"kernel": w[f"{prefix}_index_{name}"]} for name in index},
                    "k_norm": {
                        **ones(n, cfg.index_dim),
                        "bias": (cfg.index_bias_std * jax.random.normal(
                            key, (n, cfg.index_dim), jnp.float32)).astype(cfg.param_dtype),
                    },
                },
            }

        return {
            "wte": {"embedding": w["wte"]},
            "first": {
                **block("first", D, first_key),
                "mlp": {"wi": w["mlp_wi"], "wo": w["mlp_wo"]}},
            "blocks": {"layers": {
                **block("layers", L, layers_key),
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


def make_extend_fn(cfg: GlmMoeDsaConfig):
    """A jitted ``extend(params, tokens, lengths, cache, i_cache)`` with the contract
    of ``gpt.make_extend_fn`` over two caches (``[layers, lanes, cache, 1, row_dim]``
    and ``[layers, lanes, cache, 1, index_dim]``, ``cfg.cache_arrays``): ``(logits,
    hidden, rows, i_rows, counters)``. ``counters`` (int32, ``cfg.counters``, summed
    over the layers) are ``moe.held_experts_ffn``'s four, the attention's
    (``layers.MLA_COUNTERS``, the pairs **attended**: at most ``topk`` a query) and the
    indexer's (``layers.SPARSE_COUNTERS``), over real tokens only. A negative token id
    marks padding: it computes no expert, selects no row and is not counted.

    Scopes: ``extend.embed``; ``extend.attention`` (cache update, the attend (a
    chunk's on the chip the kernel ``latent_attention`` straight under it), ``W_o``)
    with, inside it, ``extend.attention.latent`` (both down-projections, their norms,
    ``W_qb``, the rotations and, in the absorbed form, the absorption and the
    un-absorption), ``extend.attention.index`` (the indexer's three projections, its
    norm and rotation, the scores) and ``extend.attention.select`` (top-k or
    threshold, row gather or mask); ``extend.mlp`` (a dense layer's);
    ``extend.moe.route``, ``extend.moe.experts``, ``extend.moe.shared``;
    ``extend.logits`` (the last norm and the head, of the rows that are read: ``last=``,
    ``layers.read_rows``; every row without it).
    """
    return _make_extend(cfg, probe=False)


def make_probe_fn(cfg: GlmMoeDsaConfig):
    """``extend`` with one more output behind the counters: what each query
    selected, bool ``[layers, lanes, tokens, cache]``. For the tests and for the
    comparison of the selection with the reference's, not for serving."""
    return _make_extend(cfg, probe=True)


def _make_extend(cfg: GlmMoeDsaConfig, probe: bool):
    dtype, f32 = cfg.dtype, jnp.float32
    rank = cfg.kv_rank
    scale = float(cfg.softmax_scale)

    def _normed(x, p, name):
        return layers.rms_norm(x, p[name]["scale"], cfg.norm_eps)

    def _kernel(p, name):
        return p[name]["kernel"].astype(dtype)

    def _rope(x, positions, width=cfg.rope_dim):
        """``x`` [b, t, heads, d], its first ``width`` features rotated in float32."""
        return layers.rotary(x.astype(f32), positions, width, cfg.rope_base).astype(dtype)

    @jax.named_scope("extend.attention.latent")
    def _latents(p, hidden, positions, expanded):
        """The query latent ``c_q`` [b, t, q_rank], which the indexer reads too, the
        queries and the token's own row [b, t, 1, row_dim] (``layers.latent_queries``)."""
        return layers.latent_queries(
            p, hidden, positions, _rope, nope_dim=cfg.nope_dim, rank=rank, row_dim=cfg.row_dim,
            eps=cfg.norm_eps, expanded=expanded)

    @jax.named_scope("extend.attention.index")
    def _index_scores(p, hidden, c_q, positions, ic):
        """The new indexer keys [b, tc, 1, index_dim] and, over the cache with them
        written, ``I`` [b, tc, cache] in float32."""
        qi = _rope(
            jnp.einsum("btr,rhk->bthk", c_q, _kernel(p, "q")), positions, cfg.index_rope_dim)
        norm = p["k_norm"]
        ki = layers.layer_norm(
            hidden @ _kernel(p, "k"), norm["scale"], cfg.index_norm_eps) + norm["bias"].astype(f32)
        ki = _rope(ki[:, :, None], positions, cfg.index_rope_dim)
        w = jnp.einsum(
            "btd,dh->bth", hidden, _kernel(p, "w"), preferred_element_type=f32) * cfg.index_scale
        ic = layers.write_rows(ic, jnp.arange(positions.shape[0])[:, None], positions, ki)

        def score_block(qb, wb):            # [b, n, heads, dim], [b, n, heads]
            dots = jnp.einsum("bqhd,bkd->bqhk", qb, ic[:, :, 0], preferred_element_type=f32)
            # a float32 sum, not a matmul: the chip would round one to bfloat16
            return (jax.nn.relu(dots) * wb[..., None]).sum(2)

        return ki, layers.by_query_block(score_block, qi, w)

    @jax.named_scope("extend.attention")
    def _attend(p, p_index, hidden, positions, visible, live, kc, ic):
        """``visible`` [b, t, cache] is what each query may read, ``live`` [b] a bound
        past the lane's farthest real query: the same in every layer."""
        b, tc = positions.shape
        cap = kc.shape[1]
        expanded = layers.latent_expands(tc)
        c_q, q, row = _latents(p, hidden, positions, expanded)
        lane = jnp.arange(b)[:, None]
        kc = layers.write_rows(kc, lane, positions, row)
        ki, scores = _index_scores(p_index, hidden, c_q, positions, ic)

        def attend_block(qb, mask, keys):       # [b, n, heads, row], [b, n, k], [b, k, row]
            logit = jnp.einsum("bqhc,bkc->bhqk", qb, keys, preferred_element_type=f32) * scale
            weight = jax.nn.softmax(jnp.where(mask[:, None], logit, f32(layers.MASKED)), axis=-1)
            # over the whole row: what is behind the latent is cut from the
            # result and not from the rows, which would be copied for it
            return jnp.einsum("bhqk,bkc->bqhc", weight.astype(dtype), keys)[..., :rank]

        if tc == 1:
            # a decode lane: the chosen rows of the latent cache, once for all heads
            with jax.named_scope("extend.attention.select"):
                at, chosen = layers.select_rows(scores, visible, cfg.topk)      # [b, 1, k']
                rows = kc[lane, at[:, 0], 0]                                    # [b, k', row]
                slots_read = chosen.sum(dtype=jnp.int32)
                selected = (
                    jnp.zeros((b, cap), jnp.int32).at[lane, at[:, 0]].add(
                        chosen[:, 0].astype(jnp.int32))[:, None] > 0
                    if probe else None)
            attended = attend_block(q, chosen, rows)
        else:
            # a prefill chunk: every live row, under each query's mask
            with jax.named_scope("extend.attention.select"):
                selected = layers.select_mask(scores, visible, cfg.topk)        # [b, tc, cache]
                slots_read = selected.any(1).sum(dtype=jnp.int32)
            if not expanded:
                attended = layers.by_query_block(
                    lambda qb, mask: attend_block(qb, mask, kc[:, :, 0]), q, selected)
        if expanded:
            # a head's own key and value, made of each tile of rows inside the kernel
            out = attention.latent_attention(
                *q, kc[:, :, 0], _kernel(p, "k_up"), _kernel(p, "v_up"), selected, live,
                scale=scale)
        else:
            with jax.named_scope("extend.attention.latent"):
                out = jnp.einsum("bthc,chv->bthv", attended, _kernel(p, "v_up"))
        return jnp.einsum("bthv,hvd->btd", out, _kernel(p, "o")), (row, ki), slots_read, selected

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

    def _block(x, p, positions, reads, kc, ic, ffn):
        a, news, slots_read, selected = _attend(
            p["attn"], p["index"], _normed(x, p, "ln_1").astype(dtype), positions, *reads, kc, ic)
        x = x + a
        return x, news + ((slots_read, selected) if probe else (slots_read,)), ffn(
            _normed(x, p, "ln_2"))

    @jax.jit
    def extend(params, tokens, lengths, cache, i_cache, *, last=None):
        positions, valid = layers.frame(tokens, lengths)
        cap = cache.shape[2]
        reads = (layers.visible_keys(positions, valid, cap), layers.live_keys(positions, valid))
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)

        first = []
        for at in range(cfg.dense_layers):
            p = jax.tree.map(lambda a: a[at], params["first"])

            def mlp(normed):
                with jax.named_scope("extend.mlp"):
                    return layers.gated_mlp(
                        normed.astype(dtype), p["mlp"]["wi"], p["mlp"]["wo"])

            x, left, f = _block(x, p, positions, reads, cache[at], i_cache[at], mlp)
            x = x + f.astype(dtype)
            first.append(left)

        scanned, routing, experts = layers.without_experts(params["blocks"]["layers"])
        scanned["moe"] = routing        # the router and its bias are a layer's own

        def body(carry, xs):
            p, layer = xs
            # the layer's slabs of the caches where they lie, behind the dense layers'
            kc, ic = (
                jax.lax.dynamic_index_in_dim(c, cfg.dense_layers + layer, 0, keepdims=False)
                for c in (cache, i_cache))
            carry, left, (f, counters) = _block(
                carry, p, positions, reads, kc, ic,
                lambda normed: _experts(p, experts, layer, normed, valid))
            return carry + f, left + (counters,)

        x, (*left, routed) = jax.lax.scan(
            body, x, (scanned, jnp.arange(cfg.expert_layers, dtype=jnp.int32)))
        rows, i_rows, slots_read, *selected = (
            jnp.concatenate([jnp.stack(before), after])
            for before, after in zip(zip(*first), left))
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        seen = jnp.where(valid, jnp.minimum(positions + 1, cap), 0)
        queries, scored = valid.sum(dtype=jnp.int32), seen.sum(dtype=jnp.int32)
        attended = jnp.minimum(seen, cfg.topk).sum(dtype=jnp.int32)
        if layers.latent_expands(tokens.shape[1]):
            # every live slot of a lane goes through W_kvb once a layer, selected or not
            slots = jnp.minimum(reads[1], cap).sum(dtype=jnp.int32)
            by_form = (jnp.int32(0), attended, slots)
        else:
            by_form = (attended, jnp.int32(0), jnp.int32(0))
        counted = cfg.num_layers * jnp.stack([queries, *by_form, queries, scored, attended])
        return (
            logits, x, rows, i_rows,
            jnp.concatenate([routed.sum(0), counted, slots_read.sum(keepdims=True)]), *selected)

    return extend
