"""MiniCPM-SALA (``model_type`` ``minicpm_sala``): the serving path behind
``serve/llm.py`` of a model that mixes two ways of not paying for a long context.

Every layer is ``h += r * Mixer(RMSNorm(h))``, ``h += r * MLP(RMSNorm(h))`` with ``r
= scale_depth / sqrt(depth_layers)`` (the *published* depth, whatever depth runs
here) and a gated MLP; the embedding is scaled by ``scale_emb`` and the last norm's
output divided by ``embed_dim / dim_model_base`` before an untied head (the MiniCPM
family's scalings). ``mixer_types`` says which mixer a layer has:

* ``lightning-attn``: **linear attention** (Lightning Attention-2). A key, a query
  and a value head a head, q and k RMS-normed per head and rotated over every
  feature; ``S_t = l_h S_{t-1} + k_t^T v_t`` (``S`` ``head_dim x head_dim`` a head,
  float32, zero at a sequence's start), ``o_t = (q_t / sqrt(d)) S_t``: no softmax and
  no denominator. ``l_h = exp(-slope_h)``, a constant a head, held as a buffer
  ``[layers, heads]`` in the parameters (``lightning_slopes``). Then a per-head
  RMSNorm of ``o``, a sigmoid gate from the mixer's input, and ``W_o``. Such a layer
  caches nothing per token: the configuration names the state (``state_arrays``), the
  engine keeps a slot of it a sequence, and ``extend`` reads and writes a lane's where
  the pool keeps it (``models/granitemoehybrid.py`` has the rules; they are the
  engine's, not Mamba's). A decode lane does one step; a prefill chunk runs in
  sub-chunks of ``linear_chunk`` tokens, inside one the masked product ``((q k^T) *
  L) v`` with ``L_ts = l^(t-s)``, between them the state, one ``lax.scan`` body for
  every sub-chunk so that the same tokens from the same state give the same bits
  wherever in a call they lie; the states between sub-chunks are what the prefix
  cache can restore (``snap_at``, ``snap_slots``);
* ``minicpm4``: **block-sparse grouped attention** (InfLLM-V2). ``num_heads`` query
  heads over ``kv_heads`` K/V heads, q and k RMS-normed per head, **no position
  encoding**, a sigmoid gate on the output. A query at position ``t < dense_len``
  attends every key up to its own. A query at ``t >= dense_len`` attends a chosen set,
  one for each K/V head (its query heads choose together): *compressed keys*, the
  mean of ``kernel_size`` consecutive keys every ``kernel_stride`` tokens, are scored
  by each query head with an exact float32 softmax over those wholly before ``t``,
  the heads' weights summed; a block of ``select_block`` tokens scores the maximum
  over the compressed keys whose window meets it; the first ``init_blocks`` blocks and
  every block that meets the last ``window_size`` tokens are always read, and of the
  others the ``topk`` with the largest score (a tie to the lower block). A token
  caches K and V of these layers alone (``cache_layers``), all K/V heads of each
  side by side in one row, **and a compressed key for every ``kernel_stride``
  tokens**: a third cache array at its own grain (``cache_arrays``' third element:
  tokens a row), whose row ``c`` is the compressed key whose *last* key is token
  ``kernel_stride c + kernel_stride - 1``. It belongs to that token's page: ``extend``
  writes the rows whose last key a call brings (from keys that may lie in the page
  before), and the pool gathers, pages back, clones and evicts them with their page.

The selection has two forms giving the same keys. A decode lane takes the top
``topk`` of its block scores and **gathers** the rows of its blocks from the padded
cache, a K/V head at a time. A prefill chunk builds each query's mask from its block
ids and attends under it (on the chip ``ops/attention.masked_attention``, whose mask
has no head axis: the K/V heads go in as lanes of their own, so each brings its own
mask and the kernel is the one the other architectures call). A call whose cache is
no longer than ``dense_len`` selects nothing: its shape says so.

State, norms, softmaxes, decays and every accumulation are float32; weights and the
operands of the matmuls ``dtype``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers
from ray_tpu.ops import attention, backend

SPARSE, LINEAR = "minicpm4", "lightning-attn"

#: the published list: 8 sparse layers among 24 linear ones, in no period
PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LINEAR for i in range(32))

#: what the sparse layers count over the real queries of a device call, summed over
#: those layers: queries at or past ``dense_len``; (such a query, visible compressed
#: key, K/V head) triples scored; keys those queries attend, K/V head by K/V head;
#: token slots of the call's padded caches that some query of the call read; and the
#: keys those queries would attend densely, K/V head by K/V head
SPARSE_COUNTERS = (
    "sparse_queries", "sparse_keys_scored", "sparse_keys_attended", "sparse_slots_read",
    "sparse_keys_causal")
#: and the linear layers: tokens through the recurrence, and states read and written
#: once (a lane, a layer)
LINEAR_COUNTERS = ("linear_tokens", "linear_state_passes")


def _period(mixers) -> int:
    """The shortest period that the whole list repeats (its length where none)."""
    n = len(mixers)
    return next(
        p for p in range(1, n + 1) if n % p == 0 and tuple(mixers[:p]) * (n // p) == tuple(mixers))


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    num_layers: int = 32
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    embed_dim: int = 4096
    mlp_dim: int = 16384
    num_heads: int = 32             # the sparse layers' query heads ...
    kv_heads: int = 2               # ... over these K/V heads
    head_dim: int = 128
    linear_heads: int = 32          # a key, a query and a value head each
    linear_head_dim: int = 128
    linear_chunk: int = 256         # tokens a sub-chunk of the chunked recurrence
    kernel_size: int = 32           # keys a compressed key is the mean of ...
    kernel_stride: int = 16         # ... one every so many tokens
    select_block: int = 64          # tokens a block that is chosen whole
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192           # a query before this position attends densely
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_layers: int = 32          # the published depth: what the residual scale is of
    dim_model_base: int = 256
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 524288
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the linear layers' state, on the device and in ``extend``

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if len(self.mixer_types) != self.num_layers or set(self.mixer_types) - {SPARSE, LINEAR}:
            raise ValueError(
                f"mixer_types names {len(self.mixer_types)} layers of {sorted(set(self.mixer_types))}"
                f": {self.num_layers} of {SPARSE!r} or {LINEAR!r} are wanted")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{self.num_heads} query heads over {self.kv_heads} K/V heads")
        if (self.kernel_size % self.kernel_stride or self.select_block % self.kernel_stride
                or self.dense_len % self.select_block):
            raise ValueError(
                f"compressed keys of {self.kernel_size} every {self.kernel_stride} tokens, blocks of "
                f"{self.select_block}, dense up to {self.dense_len}: each must be whole in the next")

    @property
    def period(self) -> int:
        return _period(self.mixer_types)

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    @property
    def sparse_layers(self) -> int:
        return self.mixer_types.count(SPARSE)

    @property
    def linear_layers(self) -> int:
        return self.mixer_types.count(LINEAR)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / float(np.sqrt(self.depth_layers))

    def num_params(self) -> int:
        """What ``init_params`` holds, the decay slopes (a buffer) with the weights."""
        d, hd = self.embed_dim, self.head_dim
        mlp = 3 * d * self.mlp_dim + d
        inner = self.linear_heads * self.linear_head_dim
        sparse = d * hd * (3 * self.num_heads + 2 * self.kv_heads) + 2 * hd + d
        linear = 5 * d * inner + 3 * self.linear_head_dim + d + self.linear_heads
        return (
            2 * self.vocab_size * d + self.sparse_layers * (sparse + mlp)
            + self.linear_layers * (linear + mlp) + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = SPARSE_COUNTERS + LINEAR_COUNTERS

    @property
    def cache_layers(self) -> int:
        """The layers a token is cached in: the sparse ones alone."""
        return self.sparse_layers

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V, all K/V
        heads of each side by side in one row; and, ``(heads, dim, tokens a row)``,
        the compressed keys: one row for every ``kernel_stride`` tokens."""
        row = self.kv_heads * self.head_dim
        return ((1, row), (1, row), (1, row, self.kernel_stride))

    @property
    def state_arrays(self):
        """What a sequence holds, ``(layers, shape, dtype)`` per array: a linear
        layer's state, ``[heads, key features, value features]``."""
        return ((
            self.linear_layers,
            (self.linear_heads, self.linear_head_dim, self.linear_head_dim), self.state_dtype),)

    @property
    def state_chunk(self) -> int:
        """Tokens between the states ``extend`` can hand back (``snap_at``)."""
        return self.linear_chunk

    def count_gathered(self, lanes: int, cache: int) -> Dict[str, int]:
        """What a call's padded caches hold for the selection to choose from: every
        slot the engine gathered, in every sparse layer."""
        return {"sparse_slots_gathered": self.sparse_layers * lanes * cache}

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def minicpm_sala_nano(**kw) -> MiniCPMSALAConfig:
    """A tiny one for the tests: two periods of a sparse layer and three linear
    ones; compressed keys of 4 every 2 tokens, blocks of 8, a window of 16 and the 2
    best blocks beside it, dense up to 32; sub-chunks of 8."""
    sizes = dict(
        vocab_size=256, num_layers=8, mixer_types=(SPARSE, LINEAR, LINEAR, LINEAR) * 2,
        embed_dim=64, mlp_dim=96, num_heads=8, kv_heads=2, head_dim=16, linear_heads=4,
        linear_head_dim=16, linear_chunk=8, kernel_size=4, kernel_stride=2, select_block=8,
        init_blocks=1, window_size=16, topk=2, dense_len=32, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MiniCPMSALAConfig(**{**sizes, **kw})


def decay_slopes(cfg: MiniCPMSALAConfig):
    """Lightning Attention-2's slopes, the same in every linear layer: ``2^(-8 h /
    heads)``, ``h = 1 .. heads``, as ``[linear_layers, heads]`` float32."""
    h = np.arange(1, cfg.linear_heads + 1, dtype=np.float64)
    return jnp.asarray(
        np.tile(2.0 ** (-8.0 * h / cfg.linear_heads), (cfg.linear_layers, 1)), jnp.float32)


def init_params(cfg: MiniCPMSALAConfig, seed: int = 0):
    """Seeded weights, made on the device in one jitted call: under ``periods`` one
    tree for each layer of a period (``sparse`` and ``linear``: a tuple of those
    mixers', in their order; ``mlp``: a tuple of every layer's), each leaf stacked
    ``[periods, ...]`` for ``extend``'s scan. Matrices normal with stddev 0.02, norm
    scales 1, the gate and the up projection of an MLP side by side;
    ``lightning_slopes`` ``[linear_layers, heads]`` float32 is :func:`decay_slopes`."""
    d, P = cfg.embed_dim, cfg.periods
    one = cfg.mixer_types[:cfg.period]
    hd, lhd = cfg.head_dim, cfg.linear_head_dim
    inner = cfg.linear_heads * lhd
    sparse = {
        "q": (P, d, cfg.num_heads * hd), "k": (P, d, cfg.kv_heads * hd),
        "v": (P, d, cfg.kv_heads * hd), "g": (P, d, cfg.num_heads * hd),
        "o": (P, cfg.num_heads * hd, d)}
    linear = {name: (P, d, inner) for name in ("q", "k", "v", "g")}
    linear["o"] = (P, inner, d)
    mlp = {"wi": (P, d, 2 * cfg.mlp_dim), "wo": (P, cfg.mlp_dim, d)}
    ones = functools.partial(layers.ones_scale, cfg.param_dtype)

    def drawn(key, shapes):
        return layers.drawn(jax.random.split(key, len(shapes)), shapes, cfg.param_dtype)

    def mixer(key, shapes, head, out_norm):
        return {
            "ln": ones(P, d), **{n: {"kernel": w} for n, w in drawn(key, shapes).items()},
            "q_norm": ones(P, head), "k_norm": ones(P, head),
            **({"o_norm": ones(P, head)} if out_norm else {})}

    @jax.jit
    def init(rng):
        k_wte, k_head, *keys = jax.random.split(rng, 2 + 2 * cfg.period)
        mixers, mlps = keys[:cfg.period], keys[cfg.period:]
        return {
            "wte": {"embedding": layers.normal(k_wte, (cfg.vocab_size, d), cfg.param_dtype)},
            "periods": {
                "sparse": tuple(
                    mixer(k, sparse, hd, False) for k, m in zip(mixers, one) if m == SPARSE),
                "linear": tuple(
                    mixer(k, linear, lhd, True) for k, m in zip(mixers, one) if m == LINEAR),
                "mlp": tuple({"ln": ones(P, d), **drawn(k, mlp)} for k in mlps),
            },
            "lightning_slopes": decay_slopes(cfg),
            "ln_f": ones(d),
            "head": {"kernel": layers.normal(k_head, (d, cfg.vocab_size), cfg.param_dtype)},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# -- the linear recurrence -------------------------------------------------------


def linear_step(state, q, k, v, slopes, real):
    """One token of the recurrence, every head: ``state`` [lanes, heads, dk, dv]
    float32, ``q``, ``k``, ``v`` [lanes, heads, d] float32, ``slopes`` [heads],
    ``real`` [lanes] (a padded token neither decays nor feeds a state). Returns ``q
    S`` [lanes, heads, dv] (unscaled) and the new state."""
    decay = jnp.where(real[:, None], jnp.exp(-slopes)[None, :], 1.0)[..., None, None]
    fed = jnp.where(real[:, None, None, None], k[..., :, None] * v[..., None, :], 0.0)
    state = decay * state + fed
    return jnp.einsum("bhk,bhkv->bhv", q, state, precision=jax.lax.Precision.HIGHEST), state


def linear_chunked(state, q, k, v, slopes, valid, chunk: int, dtype):
    """The recurrence over ``t`` tokens in sub-chunks of ``chunk`` (``t`` a whole
    number of them), as matmuls: ``q``, ``k``, ``v`` [lanes, t, heads, d], ``valid``
    [lanes, t] (a padded token neither decays nor feeds a state). Inside a sub-chunk
    ``o_t = sum_{s<=t} l^(t-s) (q_t . k_s) v_s``, from the state before it ``l^(t+1)
    q_t S``. The matmuls take ``dtype`` operands and sum in float32; the state's own
    read-out is float32 throughout. Returns ``o`` [lanes, t, heads, dv] float32
    (unscaled), the last state, and the state after each sub-chunk ``[t / chunk,
    lanes, ...]``. One ``lax.scan`` body: a sub-chunk's result does not depend on
    where in the call it lies."""
    lanes, t, heads, _ = q.shape
    f32, nc = jnp.float32, t // chunk
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def split(x):                   # [lanes, t, ...] -> [nc, lanes, chunk, ...]
        return jnp.moveaxis(x.reshape((lanes, nc, chunk) + x.shape[2:]), 1, 0)

    def one(state, xs):
        qc, kc, vc, real = xs       # [lanes, chunk, heads, d] x 3, [lanes, chunk]
        # minus the slope times the real tokens up to and with this one
        run = -slopes[None, :, None] * jnp.cumsum(real, axis=1, dtype=f32)[:, None, :]
        qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (qc, kc, vc))    # [lanes, heads, chunk, d]
        kh = jnp.where(real[:, None, :, None], kh, 0)
        pair = jnp.einsum("bhqd,bhsd->bhqs", qh, kh, preferred_element_type=f32)
        # masked before the exponential: a later token's difference is positive
        span = jnp.where(causal, run[..., :, None] - run[..., None, :], -jnp.inf)
        weight = (pair * jnp.exp(span)).astype(dtype)
        o = jnp.einsum("bhqs,bhsv->bhqv", weight, vh, preferred_element_type=f32)
        o = o + jnp.exp(run)[..., None] * jnp.einsum(
            "bhqk,bhkv->bhqv", qh.astype(f32), state, precision=jax.lax.Precision.HIGHEST)
        to_end = jnp.exp(run[..., -1:] - run)                       # [lanes, heads, chunk]
        state = jnp.exp(run[..., -1])[..., None, None] * state + jnp.einsum(
            "bhsk,bhsv->bhkv", (kh.astype(f32) * to_end[..., None]).astype(dtype), vh,
            preferred_element_type=f32)
        return state, (o.transpose(0, 2, 1, 3), state)

    state, (o, between) = jax.lax.scan(one, state, tuple(map(split, (q, k, v, valid))))
    return jnp.moveaxis(o, 0, 1).reshape(lanes, t, heads, -1), state, between


# -- the selection ---------------------------------------------------------------


def block_scores(weights, cfg: MiniCPMSALAConfig):
    """From the summed softmax weights of the compressed keys ``[..., rows]`` (row
    ``c`` the key whose last token is ``kernel_stride (c + 1) - 1``; 0 where a query
    does not see it) to a score a block ``[..., rows x kernel_stride /
    select_block]``: the maximum over the compressed keys whose ``kernel_size`` tokens
    meet the block."""
    per_block = cfg.select_block // cfg.kernel_stride
    reach = cfg.kernel_size // cfg.kernel_stride - 1     # rows past a block's own that meet it
    lead = (1,) * (weights.ndim - 1)
    return jax.lax.reduce_window(
        weights, 0.0, jax.lax.max, lead + (per_block + reach,), lead + (per_block,),
        ((0, 0),) * (weights.ndim - 1) + ((0, reach),))


def forced_blocks(t, blocks: int, cfg: MiniCPMSALAConfig):
    """``(forced, reachable)`` [..., blocks] for queries at ``t`` [...]: the blocks
    always read (the first ``init_blocks`` and those that meet the last
    ``window_size`` tokens) and the blocks that begin at or before ``t``."""
    block = jnp.arange(blocks, dtype=jnp.int32)
    t = t[..., None]
    reachable = block * cfg.select_block <= t
    window = (block + 1) * cfg.select_block > t - cfg.window_size + 1
    return ((block < cfg.init_blocks) | window) & reachable, reachable


def choose_blocks(scores, t, cfg: MiniCPMSALAConfig):
    """The ``topk`` blocks with the largest ``scores`` [..., blocks] among those a
    query at ``t`` [...] may reach and does not read anyway, a tie to the lower
    block: ``(ids [..., topk'], chosen [..., topk'])``, ``topk' = min(topk,
    blocks)``; where fewer can be chosen the rest are not ``chosen``."""
    forced, reachable = forced_blocks(t, scores.shape[-1], cfg)
    top, ids = jax.lax.top_k(
        jnp.where(reachable & ~forced, scores, -1.0), min(cfg.topk, scores.shape[-1]))
    return ids, top >= 0.0


# -- extend -----------------------------------------------------------------------


def make_extend_fn(cfg: MiniCPMSALAConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, c_cache, states,
    slots, snap_at, snap_slots)``: the contract of ``gpt.make_extend_fn`` over the
    sparse layers' caches (K and V ``[cache_layers, lanes, cache, 1, kv_heads x
    head_dim]``, the compressed keys ``[cache_layers, lanes, cache / kernel_stride, 1,
    kv_heads x head_dim]``) and the pool's state arena itself (``cfg.state_arrays``:
    ``[linear_layers, state slots, heads, d, d]``; a caller that keeps it donates it)
    with each lane's slot in it. Returns ``(logits, hidden, k rows, v rows, compressed
    rows, states, counters)``: the compressed rows are those whose last key the call
    brings, ``ceil(tokens / kernel_stride)`` a lane, in the order of their tokens;
    the arena holds, in each lane's slot, the state after its last real token, and a
    call of more than one token a lane also writes the state after ``snap_at[lane]``
    tokens (a whole number of sub-chunks, at least one: 0 reads as one) to slot
    ``snap_slots[lane]`` (0, nobody's, where none is to be kept). No other slot is
    touched. A lane of length 0 starts from zeros whatever its slot holds; a negative
    token id is padding and changes no state; a lane of padding alone points at slot
    0. ``counters`` (``cfg.counters``) over real lanes and tokens.

    Scopes: ``extend.embed``; ``extend.linear`` (projections, norms, rotation, gate,
    out) with ``extend.linear.scan`` inside it (the recurrence alone, with the state's
    read and its writes); ``extend.attention`` (projections, norms, cache update, the
    attend, the gate) with ``extend.attention.index`` (compressing, scoring, pooling)
    and ``extend.attention.select`` (top-k, the mask or the gather) inside it;
    ``extend.mlp``; ``extend.logits`` (the last norm and the head, of the rows that are
    read: ``last=``, ``layers.read_rows``; every row without it)."""
    return _make_extend(cfg, probe=False)


def make_probe_fn(cfg: MiniCPMSALAConfig):
    """``extend`` with one more output behind the counters: the keys each query
    attended, bool ``[sparse layers, lanes, kv_heads, tokens, cache]``. For the tests
    and for the comparison of the selection with the reference's, not for serving."""
    return _make_extend(cfg, probe=True)


def _make_extend(cfg: MiniCPMSALAConfig, probe: bool):
    dtype, f32 = cfg.dtype, jnp.float32
    kv, hd = cfg.kv_heads, cfg.head_dim
    groups = cfg.num_heads // kv
    heads, lhd = cfg.linear_heads, cfg.linear_head_dim
    stride, kernel, blk = cfg.kernel_stride, cfg.kernel_size, cfg.select_block
    scale = 1.0 / float(np.sqrt(hd))
    linear_scale = 1.0 / float(np.sqrt(lhd))
    res = cfg.residual_scale
    one_period = cfg.mixer_types[:cfg.period]
    per_period = one_period.count(LINEAR)
    #: blocks a decode lane gathers: the first, those the window meets, the chosen
    window_blocks = cfg.window_size // blk + 1

    def _normed(x, p):
        return layers.rms_norm(x, p["scale"], cfg.norm_eps)

    def _project(hidden, p, name, n_heads, width):
        b, tc, _ = hidden.shape
        return (hidden @ p[name]["kernel"].astype(dtype)).reshape(b, tc, n_heads, width)

    def _gated_out(y, hidden, p):
        b, tc = hidden.shape[:2]
        gate = jax.nn.sigmoid((hidden @ p["g"]["kernel"].astype(dtype)).astype(f32))
        return jnp.dot(
            (y.reshape(b, tc, -1) * gate).astype(dtype), p["o"]["kernel"].astype(dtype),
            preferred_element_type=f32)

    # An arena is read and written one slot at a time, with a dynamic slice and an
    # in-place dynamic update: indexed with the slots the TPU compiler first copies all
    # of it (``models/granitemoehybrid.py``; ``tests/test_chip_compile_serve.py`` holds
    # ``extend`` to this).

    def _take(arena, slots, at):
        """``arena[at, slots]``: [lanes, ...]."""
        return jnp.concatenate([
            jax.lax.dynamic_slice(
                arena, (at, slots[i]) + (0,) * (arena.ndim - 2), (1, 1) + arena.shape[2:])[0]
            for i in range(slots.shape[0])], axis=0)

    def _put(arena, slots, new, at):
        """``arena[at, slots] = new``, lane by lane where the arena lies."""
        new = new.astype(arena.dtype)
        for i in range(slots.shape[0]):
            arena = jax.lax.dynamic_update_slice(
                arena, new[None, i:i + 1], (at, slots[i]) + (0,) * (arena.ndim - 2))
        return arena

    @jax.named_scope("extend.linear")
    def _linear(p, slopes, hidden, positions, valid, fresh, slots, snap_at, snap_slots, arena, at):
        """``arena`` holds every sequence's state of every linear layer: lane ``i``'s of
        layer ``at`` is read from slot ``slots[i]`` and, after the lane's last real
        token, written there, and into slot ``snap_slots[i]`` (a chunk) the one after
        ``snap_at[i]`` tokens. Returns the mixer's output and ``arena``."""
        b, tc = valid.shape

        def normed_and_rotated(name):
            x = _normed(_project(hidden, p, name, heads, lhd), p[name + "_norm"])
            return layers.rotary(x, positions, lhd, cfg.rope_base).astype(dtype)

        q, k = normed_and_rotated("q"), normed_and_rotated("k")
        v = _project(hidden, p, "v", heads, lhd)
        with jax.named_scope("extend.linear.scan"):
            # the state's read and its writes are the recurrence's own traffic
            before = jnp.where(fresh[:, None, None, None], 0.0, _take(arena, slots, at))
            if tc == 1:
                o, state = linear_step(
                    before, q[:, 0].astype(f32), k[:, 0].astype(f32), v[:, 0].astype(f32),
                    slopes, valid[:, 0])
                o = o[:, None]
            else:
                o, state, between = linear_chunked(
                    before, q, k, v, slopes, valid, cfg.linear_chunk, dtype)
                chunk = jnp.clip(snap_at // cfg.linear_chunk - 1, 0, tc // cfg.linear_chunk - 1)
                arena = _put(arena, snap_slots, jnp.take_along_axis(
                    between, chunk[None, :, None, None, None], axis=0)[0], at)
            arena = _put(arena, slots, state, at)
        y = _normed(o * linear_scale, p["o_norm"])
        return _gated_out(y, hidden, p), arena

    def _compress(kc4, lengths, valid, cc):
        """The compressed keys whose last key this call brings, ``[b, rows, 1, kv x
        hd]`` in the order of their tokens (zeros where the call brings fewer), from
        ``kc4`` (the padded K cache with the call's rows written, ``[b, cap, kv, hd]``),
        and ``cc`` (``[b, cap / stride, 1, kv x hd]``) with them written."""
        b, tc = valid.shape
        rows = -(-tc // stride)
        lane = jnp.arange(b)[:, None]
        at = lengths[:, None] // stride + jnp.arange(rows, dtype=jnp.int32)[None, :]
        last = (at + 1) * stride - 1                                    # [b, rows]
        whole = (last - kernel + 1 >= 0) & (last < lengths[:, None] + valid.sum(1)[:, None])
        taken = kc4[
            lane[:, :, None],
            jnp.clip(last[..., None] - (kernel - 1) + jnp.arange(kernel), 0, kc4.shape[1] - 1)]
        new = jnp.where(
            whole[..., None, None], taken.astype(f32).mean(2), 0.0).astype(dtype)
        new = new.reshape(b, rows, 1, kv * hd)
        # a row the call does not bring is dropped: written past the cache's end
        return new, layers.write_rows(
            cc, lane, jnp.where(whole, at, cc.shape[1]), new)

    def _scores(q, cc, positions, valid):
        """``(block scores [b, kv, n, blocks] float32, pairs scored)`` of queries ``q``
        [b, n, kv, groups, hd] at ``positions`` over the compressed keys ``cc``."""
        with jax.named_scope("extend.attention.index"):
            b, rows = cc.shape[:2]
            keys = cc.reshape(b, rows, kv, hd)
            last = (jnp.arange(rows, dtype=jnp.int32) + 1) * stride - 1
            seen = (
                (last[None, None, :] <= positions[:, :, None]) & (last >= kernel - 1)
                & valid[:, :, None])                                        # [b, n, rows]

            def score_block(qb, seen_b):
                logit = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qb, keys, preferred_element_type=f32) * scale
                weight = jax.nn.softmax(
                    jnp.where(seen_b[:, None, None], logit, f32(layers.MASKED)), axis=-1)
                summed = jnp.where(seen_b[:, None], weight.sum(2), 0.0)     # [b, kv, q, rows]
                return block_scores(summed, cfg).transpose(0, 2, 1, 3)      # [b, q, kv, blocks]

            scores = (
                score_block(q, seen) if q.shape[1] == 1
                else layers.by_query_block(score_block, q, seen))
            return scores.transpose(0, 2, 1, 3), seen

    def _attend_under(q, kc4, vc4, mask, live):
        """``q`` [b, n, kv, groups, hd] over the padded caches under ``mask`` [b, kv,
        n, cap]: each K/V head a lane of its own, with its own mask."""
        b, n = q.shape[:2]
        cap = kc4.shape[1]
        qf = q.transpose(0, 2, 1, 3, 4).reshape(b * kv, n, 1, groups, hd)
        kf, vf = (x.transpose(0, 2, 1, 3).reshape(b * kv, cap, 1, hd) for x in (kc4, vc4))
        mf = mask.reshape(b * kv, n, cap)
        if n > 1 and backend.on_tpu():
            out = attention.masked_attention(qf, kf, vf, mf, jnp.repeat(live, kv), scale=scale)
        elif n == 1:
            out = layers.plain_attend(qf, kf, vf, mf, scale)
        else:
            out = layers.by_query_block(
                lambda qb, mb: layers.plain_attend(qb, kf, vf, mb, scale), qf, mf)
        return out.reshape(b, kv, n, groups, hd).transpose(0, 2, 1, 3, 4)

    @jax.named_scope("extend.attention")
    def _sparse(p, hidden, positions, valid, lengths, kc, vc, cc):
        b, tc = positions.shape
        cap = kc.shape[1]
        q = _normed(_project(hidden, p, "q", cfg.num_heads, hd), p["q_norm"]).astype(dtype)
        q = q.reshape(b, tc, kv, groups, hd)
        k = _normed(_project(hidden, p, "k", kv, hd), p["k_norm"]).astype(dtype)
        v = _project(hidden, p, "v", kv, hd)
        k, v = (x.reshape(b, tc, 1, kv * hd) for x in (k, v))       # one row for all K/V heads
        lane = jnp.arange(b)[:, None]
        kc4 = layers.write_rows(kc, lane, positions, k).reshape(b, cap, kv, hd)
        vc4 = layers.write_rows(vc, lane, positions, v).reshape(b, cap, kv, hd)
        with jax.named_scope("extend.attention.index"):
            c_new, cc = _compress(kc4, lengths, valid, cc)
        visible = layers.visible_keys(positions, valid, cap)           # [b, tc, cap]
        live = layers.live_keys(positions, valid)
        reads = jnp.where(valid, positions + 1, 0)                      # keys a dense query reads
        zero = jnp.zeros((), jnp.int32)

        if cap <= cfg.dense_len:
            # no query of this shape is past ``dense_len``: plain grouped attention
            mask = jnp.broadcast_to(visible[:, None], (b, kv, tc, cap))
            out = _attend_under(q, kc4, vc4, mask, live)
            counters = jnp.stack([zero, zero, zero, reads.max(1).sum(dtype=jnp.int32), zero])
            selected = mask if probe else None
        else:
            sparse = valid & (positions >= cfg.dense_len)               # queries that select
            scores, seen = _scores(q, cc, positions, valid)             # [b, kv, tc, blocks]
            blocks = cap // blk

            def under_masks():
                """Every row of the cache under each query's mask, a K/V head a mask."""
                with jax.named_scope("extend.attention.select"):
                    ids, chosen = choose_blocks(scores, positions[:, None, :], cfg)
                    forced, _ = forced_blocks(positions, blocks, cfg)   # [b, tc, blocks]
                    picked = (
                        (ids[..., None] == jnp.arange(blocks, dtype=jnp.int32))
                        & chosen[..., None]).any(-2)                    # [b, kv, tc, blocks]
                    of_block = jnp.repeat(picked | forced[:, None], blk, axis=-1)
                    mask = jnp.where(
                        sparse[:, None, :, None], of_block, True) & visible[:, None]
                return _attend_under(q, kc4, vc4, mask, live), mask

            def gathered():
                """A decode lane past ``dense_len``: the rows of its blocks and no
                other, a K/V head at a time."""
                with jax.named_scope("extend.attention.select"):
                    t = positions[:, 0]                                 # [b]
                    ids, chosen = choose_blocks(scores[:, :, 0], t[:, None], cfg)   # [b, kv, k']
                    near = t[:, None] // blk - jnp.arange(window_blocks, dtype=jnp.int32)
                    near_ok = (near + 1) * blk > (t[:, None] - cfg.window_size + 1)
                    first = jnp.arange(cfg.init_blocks, dtype=jnp.int32)
                    # the first blocks, unless the window holds them already
                    first_ok = (first[None] + 1) * blk <= t[:, None] - cfg.window_size + 1
                    fixed = jnp.concatenate([jnp.broadcast_to(first, (b,) + first.shape), near], 1)
                    fixed_ok = jnp.concatenate([first_ok, near_ok & (near >= 0)], 1)
                    n_fixed = fixed.shape[1]
                    block_ids = jnp.concatenate(
                        [jnp.broadcast_to(fixed[:, None], (b, kv, n_fixed)), ids], -1)
                    block_ok = jnp.concatenate(
                        [jnp.broadcast_to(fixed_ok[:, None], (b, kv, n_fixed)), chosen], -1)
                    rows = (
                        block_ids[..., None] * blk + jnp.arange(blk, dtype=jnp.int32)
                    ).reshape(b, kv, -1)                                # [b, kv, r]
                    ok = (
                        jnp.repeat(block_ok, blk, axis=-1) & (rows <= t[:, None, None])
                        & valid[:, 0, None, None])
                    rows = jnp.clip(rows, 0, cap - 1)
                    head = jnp.arange(kv)[None, :, None]
                    k_rows = kc4[lane[:, :, None], rows, head]          # [b, kv, r, hd]
                    v_rows = vc4[lane[:, :, None], rows, head]
                logit = jnp.einsum(
                    "bhgd,bhrd->bhgr", q[:, 0], k_rows, preferred_element_type=f32) * scale
                weight = jax.nn.softmax(
                    jnp.where(ok[:, :, None], logit, f32(layers.MASKED)), axis=-1)
                out = jnp.einsum("bhgr,bhrd->bhgd", weight.astype(dtype), v_rows)[:, None]
                mask = jnp.zeros((b, kv, cap), jnp.int32).at[
                    lane[:, :, None], head, rows].add(ok.astype(jnp.int32)) > 0
                return out.astype(dtype), mask[:, :, None]

            if tc == 1:
                # lanes under ``dense_len`` beside the others: the general form
                out, mask = jax.lax.cond(
                    (sparse | ~valid).all(), gathered, lambda: under_masks())
            else:
                out, mask = under_masks()
            attended = jnp.where(sparse[:, None, :], mask.sum(-1, dtype=jnp.int32), 0)
            counters = jnp.stack([
                sparse.sum(dtype=jnp.int32),
                kv * jnp.where(sparse[..., None], seen, False).sum(dtype=jnp.int32),
                attended.sum(dtype=jnp.int32),
                mask.any((1, 2)).sum(dtype=jnp.int32),
                kv * jnp.where(sparse, reads, 0).sum(dtype=jnp.int32)])
            selected = mask if probe else None
        return _gated_out(out, hidden, p), (k, v, c_new), counters, selected

    @jax.named_scope("extend.mlp")
    def _mlp(x, p):
        return layers.gated_mlp(_normed(x, p["ln"]).astype(dtype), p["wi"], p["wo"])

    def _add(x, out):
        return x + (res * out).astype(dtype)

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, c_cache, states, slots, snap_at,
               snap_slots, *, last=None):
        (positions, valid), fresh = layers.frame(tokens, lengths), lengths == 0
        lengths = lengths.astype(jnp.int32)
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)
            x = x * jnp.asarray(cfg.scale_emb, dtype)
        slopes = params["lightning_slopes"].reshape(cfg.periods, per_period, heads)

        def body(carry, xs):
            # the state arena is carried whole and each layer's slots are read and
            # written where they lie
            x, arena = carry
            p, slope, kc, vc, cc, period = xs
            s = m = 0
            rows, counted, selected = [], [], []
            for i, mixer in enumerate(one_period):
                if mixer == SPARSE:
                    layer = p["sparse"][s]
                    out, new, counts, chose = _sparse(
                        layer, _normed(x, layer["ln"]).astype(dtype), positions, valid, lengths,
                        kc[s], vc[s], cc[s])
                    rows.append(new), counted.append(counts), selected.append(chose)
                    s += 1
                else:
                    layer = p["linear"][m]
                    out, arena = _linear(
                        layer, slope[m], _normed(x, layer["ln"]).astype(dtype), positions,
                        valid, fresh, slots, snap_at, snap_slots, arena, period * per_period + m)
                    m += 1
                x = _add(x, out)
                x = _add(x, _mlp(x, p["mlp"][i]))
            news = tuple(jnp.stack(each) for each in zip(*rows))
            return (x, arena), (news, sum(counted), jnp.stack(selected) if probe else ())

        def by_period(cache):       # [sparse layers, ...] -> [periods, sparse layers a period, ...]
            return cache.reshape((cfg.periods, -1) + cache.shape[1:])

        (x, states), (news, counted, selected) = jax.lax.scan(
            body, (x, states), (
                params["periods"], slopes, by_period(k_cache), by_period(v_cache),
                by_period(c_cache), jnp.arange(cfg.periods, dtype=jnp.int32)))
        news = tuple(n.reshape((cfg.sparse_layers,) + n.shape[2:]) for n in news)
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        logits = logits / (cfg.embed_dim / cfg.dim_model_base)
        counters = jnp.concatenate([
            counted.sum(0), cfg.linear_layers * jnp.stack([
                valid.sum(dtype=jnp.int32), valid.any(1).sum(dtype=jnp.int32)])])
        probed = (selected.reshape((cfg.sparse_layers,) + selected.shape[2:]),) if probe else ()
        return (logits, x, *news, states, counters, *probed)

    return extend
