"""Qwen3-Next (``model_type`` ``qwen3_next``): the serving path behind
``serve/llm.py`` of a model three of whose four mixers are a **gated delta rule**: a
linear recurrence whose write depends on what the state already holds for the key.

With ``h`` the residual stream and ``N(x) = x / sqrt(mean(x^2) + norm_eps) * (1 + g)``
(a zero-centred RMSNorm: the scale is ``1 + g``), a layer is sequential and pre-norm:
``h += Mixer(N(h))``, then ``h += FFN(N(h))``. Layer ``i`` is full attention where
``(i + 1) % full_interval == 0``, else a delta layer:

* a **delta layer** (Gated DeltaNet). From ``n = N(h)``: ``q, k`` [``delta_key_heads``
  x ``delta_key_dim``] each and ``v, z`` [``delta_value_heads`` x ``delta_value_dim``]
  each (one matrix, its columns ``q | k | v | z``), ``b, a`` [``delta_value_heads``] each
  (one matrix, ``b | a``). ``[q, k, v] <- silu(conv(q | k | v))``, a causal depthwise
  convolution of ``conv_width`` taps over those channels, no bias. A head at a time
  ``q <- q / |q| / sqrt(delta_key_dim)``, ``k <- k / |k|`` (``x * rsqrt(sum x^2 +
  1e-6)``); a key head serves ``delta_value_heads / delta_key_heads`` value heads
  (value head ``j`` reads key head ``j // that``). A value head ``j`` and token ``t``:
  ``beta_t = sigmoid(b_t)``, ``alpha_t = exp(-exp(A_log_j) * softplus(a_t +
  dt_bias_j))``, and on the state ``S`` [keys x values], float32, zero at a
  sequence's start::

      S <- alpha_t S;   S <- S + k_t (beta_t (v_t - S^T k_t))^T;   o_t = S^T q_t

  then ``W_o [RMSNorm(o_t) * w * silu(z_t)]`` (a plain weight ``w`` a feature of a
  head, ``norm_eps``). Such a layer caches **nothing per token**: what a sequence leaves
  behind is the state of each value head and the last ``conv_width - 1`` inputs of the
  convolution, a size that does not grow with the context. The configuration names both
  (``state_arrays``), the engine keeps a slot of each a sequence in arenas
  ``[delta layers, slots, ...]`` and hands ``extend`` the arenas themselves with the
  lanes' slot ids (``models/granitemoehybrid.py`` has the rules; they are the
  engine's). A decode lane does the three lines above once (:func:`delta_step`). A
  prefill chunk runs in sub-chunks of ``delta_chunk`` tokens (:func:`delta_chunked`,
  the WY / UT form): with ``G_t = sum_{s<=t} log alpha_s`` and ``D_ts = exp(G_t -
  G_s)``, ``L = strict_lower(diag(beta) (K K^T * D))``, ``T = (I + L)^-1`` (unit lower
  triangular; ``L`` is nilpotent, so ``T = (I - L)(I + L^2)(I + L^4) ...``, float32),
  ``W = T diag(beta) (K * exp(G))``, ``U = T diag(beta) V``, ``V' = U - W S_0``, ``O =
  (Q * exp(G)) S_0 + tril(Q K^T * D) V'``, ``S_C = exp(G_C) S_0 + (K * exp(G_C -
  G))^T V'``; between sub-chunks the state, one ``lax.scan`` body for every sub-chunk so
  that the same tokens from the same state give the same bits wherever in a call they
  lie. The state ``snap_at`` tokens in (a whole number of sub-chunks) goes to the slot
  ``snap_slots`` names: what the prefix cache keeps with a chain;
* a **full layer**: ``[q | gate] = W_q n`` (a head's 2 x ``head_dim`` columns side by
  side), ``k, v`` [``kv_heads`` x ``head_dim``]; ``q, k <- N(.)`` a head (zero-centred
  too); the first ``rotary_dim`` features of q and k rotate (half-split pairs,
  :func:`layers.rotary`) at ``rope_base``; causal softmax of ``q . k /
  sqrt(head_dim)``; ``W_o [a * sigmoid(gate)]``. A token caches K and V of these layers
  alone (``cache_layers``, ``cached_layers``), all K/V heads of each side by side in one
  row (``cache_arrays``);
* the FFN of **every** layer is an expert layer (``models/moe.py``): a float32 softmax
  over all ``router_experts``, the ``experts_per_token`` largest over their sum, the
  ``num_experts`` from ``expert_offset`` on held here (one chip's share; what the absent
  ones would add is left out), beside one **shared** gated MLP under a sigmoid gate of
  its own: ``sum_k w_k E_k(n) + sigmoid(w_s . n) E_shared(n)``. A final ``N`` and an
  untied head.

The layers run as a scan over periods of ``full_interval`` unlike layers (the delta
layers and the full one that ends them), the experts of every layer in one stack
beside the scan, read in place.

A padded token (id < 0) has ``alpha = 1`` and ``beta = 0``: it neither decays nor
writes a state, and the convolution's tail skips it; a lane of length 0 starts from
zeros whatever its slot holds. State, norms, softmaxes, ``alpha``, ``beta`` and every
accumulation are float32; weights, cached rows and the operands of the matmuls
``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers, moe
from ray_tpu.ops import attention, backend

#: what the delta layers count over the real lanes and tokens of a device call, summed
#: over those layers: tokens through the recurrence, and states read and written once
#: (a lane, a layer)
DELTA_COUNTERS = ("delta_tokens", "delta_state_passes")

#: what the L2 norm of a query or key head adds under its root
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    num_layers: int = 48
    full_interval: int = 4          # layer i is full attention where (i + 1) % this == 0
    embed_dim: int = 2048
    num_heads: int = 16             # a full layer's query heads ...
    kv_heads: int = 2               # ... over these K/V heads
    head_dim: int = 256
    rotary_dim: int = 64            # the features of a head that rotate: the first
    rope_base: float = 10000000.0
    delta_key_heads: int = 16
    delta_value_heads: int = 32
    delta_key_dim: int = 128
    delta_value_dim: int = 128
    delta_chunk: int = 64           # tokens a sub-chunk of the chunked recurrence
    conv_width: int = 4
    expert_dim: int = 512
    shared_dim: int = 512           # width of the shared expert
    router_experts: int = 512       # experts the router scores
    num_experts: int = 512          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 10
    norm_std: float = 0.1           # spread of the seeded norm scales (g, and w about 1)
    qk_norm_mean: float = 0.0       # what the seeded g of a full layer's q and k norms lie about
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the delta layers' state, on the device and in ``extend``

    def __post_init__(self):
        if self.full_interval < 2 or self.num_layers % self.full_interval:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of {self.full_interval - 1} delta "
                f"layers and the full layer that ends them")
        if self.num_heads % self.kv_heads or self.delta_value_heads % self.delta_key_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.kv_heads} K/V heads, "
                f"{self.delta_value_heads} value heads over {self.delta_key_heads} key heads")
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"{self.rotary_dim} rotary features of a head of {self.head_dim}")
        if self.delta_chunk & (self.delta_chunk - 1):
            raise ValueError(
                f"sub-chunks of {self.delta_chunk}: the triangular inverse is a product of "
                f"squarings, over a power of two")

    @property
    def period(self) -> int:
        return self.full_interval

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    @property
    def delta_layers(self) -> int:
        return self.periods * (self.period - 1)

    @property
    def key_inner(self) -> int:
        return self.delta_key_heads * self.delta_key_dim

    @property
    def value_inner(self) -> int:
        return self.delta_value_heads * self.delta_value_dim

    @property
    def conv_dim(self) -> int:
        """Channels through the convolution: ``q``, ``k`` and ``v``."""
        return 2 * self.key_inner + self.value_inner

    def num_params(self) -> int:
        """What ``init_params`` holds, ``A_log`` and ``dt_bias`` with the weights."""
        d, heads = self.embed_dim, self.delta_value_heads
        delta = (
            d * (self.conv_dim + self.value_inner) + d * 2 * heads + self.conv_width * self.conv_dim
            + 2 * heads + self.delta_value_dim + self.value_inner * d)
        full = d * self.head_dim * (3 * self.num_heads + 2 * self.kv_heads) + 2 * self.head_dim
        ffn = (
            d * self.router_experts + 3 * d * self.shared_dim + d
            + self.num_experts * 3 * d * self.expert_dim + 2 * d)
        return (
            2 * self.vocab_size * d + self.delta_layers * delta + self.periods * full
            + self.num_layers * ffn + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS + DELTA_COUNTERS

    @property
    def cached_layers(self) -> Tuple[bool, ...]:
        """Per layer: whether a token is cached in it. The full layers alone."""
        return tuple((i + 1) % self.period == 0 for i in range(self.num_layers))

    @property
    def cache_layers(self) -> int:
        return self.periods

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V of a full
        layer, all K/V heads of each side by side in one row."""
        row = self.kv_heads * self.head_dim
        return ((1, row), (1, row))

    @property
    def state_arrays(self):
        """What a sequence holds, ``(layers, shape, dtype)`` per array: a delta layer's
        state ``[value heads, key features, value features]``, and the inputs its
        convolution still needs."""
        return (
            (self.delta_layers,
             (self.delta_value_heads, self.delta_key_dim, self.delta_value_dim),
             self.state_dtype),
            (self.delta_layers, (self.conv_width - 1, self.conv_dim), self.dtype),
        )

    @property
    def state_chunk(self) -> int:
        """Tokens between the states ``extend`` can hand back (``snap_at``)."""
        return self.delta_chunk

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def qwen3_next_nano(**kw) -> Qwen3NextConfig:
    """A tiny one for the tests: two periods of three delta layers (4 key and 8 value
    heads of 16, sub-chunks of 8) and a full one (4 query heads over 2 K/V heads of 16,
    8 features rotated); 16 scored experts of which 4 held, 3 a token."""
    sizes = dict(
        vocab_size=256, num_layers=8, full_interval=4, embed_dim=64, num_heads=4, kv_heads=2,
        head_dim=16, rotary_dim=8, delta_key_heads=4, delta_value_heads=8, delta_key_dim=16,
        delta_value_dim=16, delta_chunk=8, expert_dim=32, shared_dim=32, router_experts=16,
        num_experts=4, expert_offset=4, experts_per_token=3, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return Qwen3NextConfig(**{**sizes, **kw})


def init_params(cfg: Qwen3NextConfig, seed: int = 0):
    """Seeded weights, made on the device in one jitted call: under ``periods`` one
    tree for each layer of a period (``delta``: a tuple of the delta layers', ``full``,
    ``ffn``: a tuple of every layer's), each leaf stacked ``[periods, ...]`` for
    ``extend``'s scan; beside them, out of the scan's reach, ``experts`` holds the held
    experts of every layer in one stack (``wi`` ``[layers, num_experts, embed, 2
    expert_dim]``, ``wo`` ``[layers, num_experts, expert_dim, embed]``), which the
    grouped matmul reads where they lie. Matrices normal with stddev 0.02 (the shared
    expert's gate ``w_s`` too); every norm's scale normal with stddev ``norm_std``: ``g``
    about 0 where the norm is zero-centred (about ``qk_norm_mean`` for a full layer's q
    and k norms), ``w`` about 1 where it is plain (the delta layers' output norm); a delta layer's own as the family's initialiser has them:
    ``A_log = log(uniform(0, 16))``, ``dt_bias`` the inverse softplus of a step
    log-uniform in (0.001, 0.1) (float32, both), the convolution's kernel uniform within
    ``conv_width^-0.5``, no bias. The gate and the up projection of an expert side by
    side; a full layer's ``q`` holds, a head, the query's columns and then its gate's."""
    d, P, L = cfg.embed_dim, cfg.periods, cfg.period
    heads, hd = cfg.delta_value_heads, cfg.head_dim
    row = cfg.kv_heads * hd
    delta = {
        "in_qkvz": (P, d, cfg.conv_dim + cfg.value_inner), "in_ba": (P, d, 2 * heads),
        "out": (P, cfg.value_inner, d)}
    full = {
        "q": (P, d, cfg.num_heads * 2 * hd), "k": (P, d, row), "v": (P, d, row),
        "o": (P, cfg.num_heads * hd, d)}
    ffn = {
        "router": (P, d, cfg.router_experts), "wi": (P, d, 2 * cfg.shared_dim),
        "wo": (P, cfg.shared_dim, d), "gate": (P, d)}
    experts = {
        "wi": (cfg.num_layers, cfg.num_experts, d, 2 * cfg.expert_dim),
        "wo": (cfg.num_layers, cfg.num_experts, cfg.expert_dim, d)}
    bound = cfg.conv_width ** -0.5

    def drawn(key, shapes):
        return layers.drawn(jax.random.split(key, len(shapes)), shapes, cfg.param_dtype)

    def scale(key, about, *shape):
        return {"scale": (
            about + cfg.norm_std * jax.random.normal(key, shape, jnp.float32)
        ).astype(cfg.param_dtype)}

    def delta_layer(key):
        k_w, k_conv, k_a, k_dt, k_ln, k_norm = jax.random.split(key, 6)
        step = jnp.exp(jax.random.uniform(
            k_dt, (P, heads), jnp.float32, np.log(0.001), np.log(0.1)))
        return {
            "ln": scale(k_ln, 0.0, P, d),
            **{n: {"kernel": w} for n, w in drawn(k_w, delta).items()},
            "conv": {"kernel": jax.random.uniform(
                k_conv, (P, cfg.conv_width, cfg.conv_dim), jnp.float32, -bound, bound
            ).astype(cfg.param_dtype)},
            "A_log": jnp.log(jax.random.uniform(k_a, (P, heads), jnp.float32, 1e-3, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "norm": scale(k_norm, 1.0, P, cfg.delta_value_dim),
        }

    def full_layer(key):
        k_w, k_ln, k_q, k_k = jax.random.split(key, 4)
        return {
            "ln": scale(k_ln, 0.0, P, d),
            **{n: {"kernel": w} for n, w in drawn(k_w, full).items()},
            "q_norm": scale(k_q, cfg.qk_norm_mean, P, hd),
            "k_norm": scale(k_k, cfg.qk_norm_mean, P, hd)}

    def ffn_layer(key):
        k_w, k_ln = jax.random.split(key)
        return {"ln": scale(k_ln, 0.0, P, d), **drawn(k_w, ffn)}

    @jax.jit
    def init(rng):
        k_wte, k_head, k_full, k_experts, k_f, *keys = jax.random.split(rng, 5 + 2 * L - 1)
        return {
            "wte": {"embedding": layers.normal(k_wte, (cfg.vocab_size, d), cfg.param_dtype)},
            "periods": {
                "delta": tuple(delta_layer(k) for k in keys[:L - 1]),
                "full": full_layer(k_full),
                "ffn": tuple(ffn_layer(k) for k in keys[L - 1:]),
            },
            "experts": drawn(k_experts, experts),
            "ln_f": scale(k_f, 0.0, d),
            "head": {"kernel": layers.normal(k_head, (d, cfg.vocab_size), cfg.param_dtype)},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# -- the gated delta rule -----------------------------------------------------------


def delta_step(state, q, k, v, alpha, beta):
    """One token of the rule, every value head: ``state`` [lanes, heads, dk, dv]
    float32, ``q``, ``k`` [lanes, heads, dk] (normalised, ``q`` scaled), ``v`` [lanes,
    heads, dv], ``alpha``, ``beta`` [lanes, heads] (1 and 0 for a padded token), all
    float32. Returns ``o`` [lanes, heads, dv] and the new state."""
    highest = jax.lax.Precision.HIGHEST
    state = alpha[..., None, None] * state
    held = jnp.einsum("bhk,bhkv->bhv", k, state, precision=highest)
    state = state + k[..., :, None] * (beta[..., None] * (v - held))[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, state, precision=highest), state


def delta_chunked(state, q, k, v, log_alpha, beta, chunk: int, dtype, keep=None):
    """The rule over ``t`` tokens in sub-chunks of ``chunk`` (a power of two, ``t`` a
    whole number of them), in the WY / UT form of the module's docstring: ``q``, ``k``
    [lanes, t, heads, dk] (normalised, ``q`` scaled, a row a value head), ``v`` [lanes,
    t, heads, dv], ``log_alpha``, ``beta`` [lanes, t, heads] float32 (0 and 0 for a
    padded token: it neither decays nor writes). The triangular inverse and the state's
    own read-out are float32 at the highest precision; the other products take ``dtype``
    operands and sum in float32. Returns ``o`` [lanes, t, heads, dv] float32, the last
    state, and the state after sub-chunk ``keep[lane]`` (zeros where ``keep`` is None).
    One ``lax.scan`` body: a sub-chunk's result does not depend on where in the call it
    lies."""
    lanes, t, heads, _ = q.shape
    f32, nc = jnp.float32, t // chunk
    highest = jax.lax.Precision.HIGHEST
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=f32)
    keep = jnp.full((lanes,), -1, jnp.int32) if keep is None else keep

    def split(x):                   # [lanes, t, heads, ...] -> [nc, lanes, heads, chunk, ...]
        x = x.reshape((lanes, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    def one(carry, xs):
        state, kept = carry
        qc, kc, vc, gc, bc, index = xs      # [lanes, heads, chunk, d] x 3, [lanes, heads, chunk] x 2
        run = jnp.cumsum(gc, axis=-1)                               # G_t
        # masked before the exponential: a later token's difference is positive
        span = run[..., :, None] - run[..., None, :]
        decay = jnp.exp(jnp.where(lower, span, -jnp.inf))           # D_ts, s <= t
        kb = (kc.astype(f32) * bc[..., None]).astype(dtype)
        pair = jnp.einsum("bhtd,bhsd->bhts", kb, kc, preferred_element_type=f32)
        low = jnp.where(strict, pair * decay, 0.0)                  # L
        # (I + L)^-1 = (I - L)(I + L^2)(I + L^4) ...: L^chunk = 0
        inverse, power, reach = eye - low, low, 2
        while reach < chunk:
            power = jnp.einsum("bhts,bhsr->bhtr", power, power, precision=highest)
            inverse = inverse + jnp.einsum("bhts,bhsr->bhtr", inverse, power, precision=highest)
            reach *= 2
        inverse = inverse.astype(dtype)
        grown = jnp.exp(run)[..., None]                             # exp(G_t)
        w = jnp.einsum(
            "bhts,bhsd->bhtd", inverse, (kb.astype(f32) * grown).astype(dtype),
            preferred_element_type=f32)
        u = jnp.einsum(
            "bhts,bhsd->bhtd", inverse, (vc.astype(f32) * bc[..., None]).astype(dtype),
            preferred_element_type=f32)
        fed = u - jnp.einsum("bhtk,bhkv->bhtv", w, state, precision=highest)     # V'
        weight = jnp.einsum("bhtd,bhsd->bhts", qc, kc, preferred_element_type=f32) * decay
        o = jnp.einsum(
            "bhtk,bhkv->bhtv", qc.astype(f32) * grown, state, precision=highest
        ) + jnp.einsum(
            "bhts,bhsv->bhtv", weight.astype(dtype), fed.astype(dtype),
            preferred_element_type=f32)
        to_end = jnp.exp(run[..., -1:] - run)[..., None]            # exp(G_C - G_t)
        state = jnp.exp(run[..., -1])[..., None, None] * state + jnp.einsum(
            "bhsk,bhsv->bhkv", (kc.astype(f32) * to_end).astype(dtype), fed.astype(dtype),
            preferred_element_type=f32)
        kept = jnp.where((keep == index)[:, None, None, None], state, kept)
        return (state, kept), o

    (state, kept), o = jax.lax.scan(
        one, (state, jnp.zeros_like(state)),
        (*map(split, (q, k, v, log_alpha, beta)), jnp.arange(nc, dtype=jnp.int32)))
    # [nc, lanes, heads, chunk, dv] -> [lanes, t, heads, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(lanes, t, heads, -1)
    return o, state, kept


def l2_normed(x):
    """``x / |x|`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


# -- extend -----------------------------------------------------------------------


def make_extend_fn(cfg: Qwen3NextConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, delta, conv, slots,
    snap_at, snap_slots)``: the contract of ``gpt.make_extend_fn`` over the full layers'
    caches (``[cache_layers, lanes, cache, 1, kv_heads x head_dim]``) and the pool's state
    arenas themselves (``cfg.state_arrays``: ``[delta_layers, state slots, ...]``; a
    caller that keeps them donates them) with each lane's slot in them (``slots``
    [lanes]). Returns ``(logits, hidden, k rows, v rows, delta, conv, counters)``: the
    arenas with, in each lane's slot, the states after its last real token; a call of
    more than one token a lane also writes the states after ``snap_at[lane]`` tokens (a
    whole number of sub-chunks, at least one: 0 reads as one) to slot
    ``snap_slots[lane]`` (0, nobody's, where none is to be kept). No other slot is
    touched. A lane of length 0 starts from zeros whatever its slot holds; a negative
    token id is padding and changes no state; a lane of padding alone points at slot 0.
    ``counters`` (``cfg.counters``) over real lanes and tokens. With ``table=`` [lanes, n]
    (a call of one token a lane, from an engine that reads the keyword off the signature:
    ``serve/llm.reads_pages``) ``k_cache`` and ``v_cache`` are the pool's block arenas
    themselves, ``[cache_layers, blocks, block, 1, kv_heads x head_dim]``, and a full
    layer attends over the lanes' pages where they lie (:func:`layers.paged_attend`).

    Scopes: ``extend.embed``; ``extend.delta`` (projections, convolution, norms, gate,
    out) with ``extend.delta.scan`` inside it (the recurrence alone in either form, with
    the state's read and its writes); ``extend.attention`` (a full layer's projections,
    norms, rotation, cache update, attend and gate: a chunk's attend on the chip
    ``ops/attention.masked_attention``, a decode lane's through the block table
    ``ops/attention.paged_attention``, any off the chip :func:`layers.plain_attend`);
    ``extend.moe.route`` (the router), ``extend.moe.experts``
    and ``extend.moe.shared`` (with its gate); ``extend.logits`` (the last norm and the
    head, of the rows that are read: ``last=``, ``layers.read_rows``; every row without
    it)."""
    dtype, f32 = cfg.dtype, jnp.float32
    heads, dk, dv = cfg.delta_value_heads, cfg.delta_key_dim, cfg.delta_value_dim
    key_heads, key_inner, tail = cfg.delta_key_heads, cfg.key_inner, cfg.conv_width - 1
    per_key = heads // key_heads
    kv, hd = cfg.kv_heads, cfg.head_dim
    groups = cfg.num_heads // kv
    scale = 1.0 / float(np.sqrt(hd))
    delta_scale = 1.0 / float(np.sqrt(dk))
    per_period = cfg.period - 1

    def _normed(x, p):
        """The zero-centred RMSNorm: the scale is ``1 + g``."""
        return layers.rms_norm(x, 1.0 + p["scale"].astype(f32), cfg.norm_eps)

    # An arena is read and written one slot at a time, with a dynamic slice and an
    # in-place dynamic update: indexed with the slots the TPU compiler first copies all
    # of it (``models/granitemoehybrid.py``; ``tests/test_chip_compile_serve.py`` holds
    # ``extend`` to this).

    def _take(arena, slots, at=0, layers=1):
        """``arena[at:at + layers, slots]``: [layers, lanes, ...]."""
        return jnp.concatenate([
            jax.lax.dynamic_slice(
                arena, (at, slots[i]) + (0,) * (arena.ndim - 2), (layers, 1) + arena.shape[2:])
            for i in range(slots.shape[0])], axis=1)

    def _put(arena, slots, new, at=0):
        """``arena[at:at + len(new), slots] = new``, lane by lane where the arena lies."""
        new = new.astype(arena.dtype)
        for i in range(slots.shape[0]):
            arena = jax.lax.dynamic_update_slice(
                arena, new[:, i:i + 1], (at, slots[i]) + (0,) * (arena.ndim - 2))
        return arena

    def _layer(stack, at):
        return jax.lax.dynamic_index_in_dim(stack, at, 0, keepdims=False)

    def _set_layer(stack, new, at):
        return jax.lax.dynamic_update_index_in_dim(stack, new.astype(stack.dtype), at, 0)

    @jax.named_scope("extend.delta")
    def _delta(p, hidden, valid, fresh, slots, snap_at, snap_slots, arena, tails, at):
        """``arena`` holds every sequence's state of every delta layer: lane ``i``'s of
        layer ``at`` is read from slot ``slots[i]`` and, after the lane's last real
        token, written there, and into slot ``snap_slots[i]`` (a chunk) the one after
        ``snap_at[i]`` tokens. ``tails`` are the lanes' own convolution inputs
        (``[delta_layers, lanes, tail, conv_dim]``: a 1/44 of a state, taken from their
        slots before the layers and put back behind them): layer ``at``'s are read and
        the new ones written, and for a chunk the kept ones beside them. Returns the
        mixer's output, ``arena`` and ``tails``."""
        b, tc = valid.shape
        conv = jnp.where(fresh[:, None, None], 0, _layer(tails[0], at)).astype(dtype)
        qkvz = hidden @ p["in_qkvz"]["kernel"].astype(dtype)
        qkv, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
        ba = jnp.dot(hidden, p["in_ba"]["kernel"].astype(dtype), preferred_element_type=f32)
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :heads]), 0.0)
        log_alpha = jnp.where(
            valid[..., None],
            -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., heads:] + p["dt_bias"]), 0.0)
        # the convolution over the lane's last inputs and this call's
        seen = jnp.concatenate([conv, qkv], axis=1)                 # [b, tail + tc, conv_dim]
        kernel = p["conv"]["kernel"].astype(f32)
        mixed = sum(kernel[i] * seen[:, i:i + tc].astype(f32) for i in range(cfg.conv_width))
        mixed = jax.nn.silu(mixed)
        # what the next call's first tokens need: the inputs of the last real ones
        lane = jnp.arange(b)[:, None]
        after = jnp.arange(tail)[None, :]
        tails = tuple(
            _set_layer(stack, seen[lane, upto[:, None] + after], at)
            for stack, upto in zip(tails, (
                valid.sum(1, dtype=jnp.int32), jnp.maximum(snap_at, cfg.delta_chunk))))

        def to_value_heads(x):      # [b, tc, key heads, dk] -> a row a value head
            return jnp.repeat(x, per_key, axis=2)

        q = to_value_heads(
            l2_normed(mixed[..., :key_inner].reshape(b, tc, key_heads, dk)) * delta_scale)
        k = to_value_heads(
            l2_normed(mixed[..., key_inner:2 * key_inner].reshape(b, tc, key_heads, dk)))
        v = mixed[..., 2 * key_inner:].reshape(b, tc, heads, dv)
        with jax.named_scope("extend.delta.scan"):
            # the state's read and its writes are the recurrence's own traffic
            before = jnp.where(fresh[:, None, None, None], 0.0, _take(arena, slots, at)[0])
            if tc == 1:
                o, state = delta_step(
                    before, q[:, 0], k[:, 0], v[:, 0], jnp.exp(log_alpha[:, 0]), beta[:, 0])
                o = o[:, None]
            else:
                keep = jnp.clip(snap_at // cfg.delta_chunk - 1, 0, tc // cfg.delta_chunk - 1)
                o, state, kept = delta_chunked(
                    before, q.astype(dtype), k.astype(dtype), v.astype(dtype), log_alpha, beta,
                    cfg.delta_chunk, dtype, keep)
                arena = _put(arena, snap_slots, kept[None], at)
            arena = _put(arena, slots, state[None], at)
        # the gated norm: a plain weight a feature of a head, then silu(z)
        y = layers.rms_norm(o, p["norm"]["scale"], cfg.norm_eps).reshape(b, tc, heads * dv)
        y = y * jax.nn.silu(z.astype(f32))
        out = jnp.dot(
            y.astype(dtype), p["out"]["kernel"].astype(dtype), preferred_element_type=f32)
        return out, arena, tails

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, visible, live, kc, vc, paged=None):
        """``kc``, ``vc`` the layer's slab of the padded caches; or, with ``paged`` (the
        layer's index and the lanes' block table), the pool's arenas themselves."""
        b, tc = positions.shape
        both = (hidden @ p["q"]["kernel"].astype(dtype)).reshape(b, tc, cfg.num_heads, 2 * hd)
        q, gate = both[..., :hd], both[..., hd:]

        def normed_and_rotated(x, name):
            x = _normed(x, p[name])
            return layers.rotary(x, positions, cfg.rotary_dim, cfg.rope_base).astype(dtype)

        q = normed_and_rotated(q, "q_norm").reshape(b, tc, kv, groups, hd)
        k = normed_and_rotated(
            (hidden @ p["k"]["kernel"].astype(dtype)).reshape(b, tc, kv, hd), "k_norm")
        k = k.reshape(b, tc, 1, kv * hd)                            # one row for all K/V heads
        v = (hidden @ p["v"]["kernel"].astype(dtype))[:, :, None]
        if paged is not None:
            out = layers.paged_attend(q, k, v, kc, vc, *paged, positions, visible, scale)
        else:
            cap = kc.shape[1]
            lane = jnp.arange(b)[:, None]
            keys = layers.write_rows(kc, lane, positions, k).reshape(b, cap, kv, hd)
            values = layers.write_rows(vc, lane, positions, v).reshape(b, cap, kv, hd)
            if tc > 1 and backend.on_tpu():
                out = attention.masked_attention(q, keys, values, visible, live, scale=scale)
            elif tc == 1:
                out = layers.plain_attend(q, keys, values, visible, scale)
            else:
                out = layers.by_query_block(
                    lambda qb, mask: layers.plain_attend(qb, keys, values, mask, scale),
                    q, visible)
        gated = out.reshape(b, tc, cfg.num_heads, hd).astype(f32) * jax.nn.sigmoid(
            gate.astype(f32))
        out = jnp.dot(
            gated.reshape(b, tc, -1).astype(dtype), p["o"]["kernel"].astype(dtype),
            preferred_element_type=f32)
        return out, (k, v)

    def _ffn(x, p, experts, layer, valid):
        """The expert layer: this chip's part of the routed half, of ``experts`` (every
        layer's stack) the ``layer``-th in place, and the shared expert under its gate.
        Returns it and what the expert layer counted."""
        b, tc, d = x.shape
        with jax.named_scope("extend.moe.route"):
            flat = _normed(x, p["ln"]).reshape(b * tc, d)
            weights, chosen = moe.softmax_top_k(flat, p["router"], cfg.experts_per_token)
            flat = flat.astype(dtype)
        with jax.named_scope("extend.moe.experts"):
            routed, counted = moe.held_experts_ffn(
                flat, weights, chosen, valid.reshape(b * tc), experts["wi"], experts["wo"],
                cfg.expert_offset, layer)
        with jax.named_scope("extend.moe.shared"):
            gate = jax.nn.sigmoid(jnp.dot(
                flat, p["gate"].astype(dtype), preferred_element_type=f32))
            shared = gate[:, None] * layers.gated_mlp(flat, p["wi"], p["wo"])
        return (routed + shared).reshape(b, tc, d), counted

    def _add(x, out):
        return x + out.astype(dtype)

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, delta, conv, slots, snap_at,
               snap_slots, *, last=None, table=None):
        tc = tokens.shape[1]
        (positions, valid), fresh = layers.frame(tokens, lengths), lengths == 0
        paged = table is not None
        visible = layers.visible_keys(positions, valid, layers.cache_slots(k_cache, table))
        live = layers.live_keys(positions, valid)
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)
        experts = params["experts"]

        def body(carry, xs):
            # the state arena is carried whole and each layer's slots are read and
            # written where they lie
            x, delta, tails = carry
            p, kc, vc, period = xs
            if paged:       # the arenas themselves, this period's layer found by the kernel
                kc, vc = k_cache, v_cache
            rows, counted = None, []
            for i in range(cfg.period):
                if i == per_period:
                    layer = p["full"]
                    out, rows = _attend(
                        layer, _normed(x, layer["ln"]).astype(dtype), positions, visible, live,
                        kc, vc, (period, table) if paged else None)
                else:
                    layer = p["delta"][i]
                    out, delta, tails = _delta(
                        layer, _normed(x, layer["ln"]).astype(dtype), valid, fresh, slots,
                        snap_at, snap_slots, delta, tails, period * per_period + i)
                x = _add(x, out)
                out, pairs = _ffn(x, p["ffn"][i], experts, period * cfg.period + i, valid)
                x = _add(x, out)
                counted.append(pairs)
            return (x, delta, tails), (rows, sum(counted))

        # the convolution's inputs are small (48 KB a lane and layer, where the state
        # is 2 MB): every layer's leave their slots in one slice a lane and go back in
        # one update a lane (``models/granitemoehybrid.py``)
        own = _take(conv, slots, layers=conv.shape[0])
        tails = (own, own) if tc > 1 else (own,)    # the new ones; a chunk's kept ones
        (x, delta, tails), (rows, counted) = jax.lax.scan(
            body, (x, delta, tails), (
                params["periods"], *((None, None) if paged else (k_cache, v_cache)),
                jnp.arange(cfg.periods, dtype=jnp.int32)))
        if tc > 1:
            conv = _put(conv, snap_slots, tails[1])
        conv = _put(conv, slots, tails[0])

        def head(rows):
            rows = _normed(rows, params["ln_f"])
            return jnp.dot(
                rows.astype(dtype), params["head"]["kernel"].astype(dtype),
                preferred_element_type=f32), rows

        with jax.named_scope("extend.logits"):
            logits, x = layers.read_rows(x, last, head)
        counters = jnp.concatenate([
            counted.sum(0), cfg.delta_layers * jnp.stack([
                valid.sum(dtype=jnp.int32), valid.any(1).sum(dtype=jnp.int32)])])
        return (logits, x, *rows, delta, conv, counters)

    return extend
