"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``): the serving path behind
``serve/llm.py`` of a model five of whose six attention layers see a **window** of
the newest ``sliding_window`` tokens and nothing else.

With ``h`` the residual stream and ``n = RMSNorm(h)`` (``norm_eps``), a layer is
sequential and pre-norm: ``h += Attn(n)``, then ``h += FFN(RMSNorm(h))``.
``sliding_layers`` says which attention a layer has; both are grouped, with queries
and keys of ``head_dim`` and values of ``v_dim`` features a head, the first
``rotary_dim`` features of every query and key head rotated (half-split pairs,
:func:`layers.rotary`) and the rest not, scores ``q . k / sqrt(head_dim)``, and the
attended values scaled by ``value_scale`` before ``W_o`` [heads x v_dim, embed]:

* a **sliding** layer: ``sliding_kv_heads`` K/V heads, rotation at
  ``sliding_rope_base``; query ``t`` sees the keys ``t - sliding_window < s <= t``
  and a learned **sink** ``b_h`` a query head (float32, ``attn/sinks``): ``p_ts =
  exp(q_t . k_s / sqrt(head_dim)) / (exp(b_h) + sum_s' exp(q_t . k_s' /
  sqrt(head_dim)))``; the sink takes weight and adds no value. Such a layer caches
  **nothing per token**. What a sequence leaves behind in it is its newest
  ``sliding_window`` rows of K and of V, a size that does not grow with the
  context: the configuration names them as state (``state_arrays``), the engine
  keeps a slot of them a sequence in arenas ``[sliding layers, slots,
  sliding_window, K/V heads x features]`` (a row holds all K/V heads side by side:
  whole 128-lane tiles) and hands ``extend`` the arenas themselves with the lanes'
  slot ids, as it does a recurrent layer's state (``models/granitemoehybrid.py``
  has the rules; they are the engine's). A slot is a **ring**: the row of position
  ``p`` lies at ``p mod sliding_window``, rotated as it was written, so a decode
  lane reads its slot's rows, attends over them with its own and writes **one row**
  (5,120 B a layer and lane at the published widths; rows kept in order would be
  shifted, all 0.65 MB of them); which position a row holds follows from the
  lane's length, and a row never written (a sequence shorter than the window, a
  fresh lane whatever its slot holds) is masked. A prefill chunk attends over
  ``[the slot's rows ‖ its own]`` under the band and leaves the newest
  ``sliding_window`` rows of the two in the slot; the rows that end ``snap_at``
  tokens in go to the slot ``snap_slots`` names: what the prefix cache keeps with
  a chain. The chunk attends in sub-chunks of a window's tokens, each over the
  window before it and itself, as a lane of its own: a block ends at a whole
  number of windows (``state_chunk``), so a chunk begins at one, and the same
  tokens give the same bits after a prefix hit as without one;
* a **full** layer: ``kv_heads`` K/V heads, rotation at ``rope_base``, every key
  ``s <= t``, no sink. A token caches K and V of these layers alone
  (``cache_layers``, ``cached_layers``), all K/V heads of each side by side in one
  row (``cache_arrays``), in the pool's block arenas;
* layer 0 (full) has a gated MLP of ``mlp_dim``; every other layer an expert layer
  (``models/moe.py``): float32 sigmoid scores over all ``router_experts``, the
  ``experts_per_token`` with the largest score + bias chosen
  (``e_score_correction_bias``, which chooses and does not weigh), their scores
  over their sum times ``routed_scale``, the ``num_experts`` from ``expert_offset``
  on held here; no shared expert. A final RMSNorm and an untied head.

The layers behind layer 0 come in periods of ``period`` = some sliding layers and
the full layer that ends them: layer 0 runs before a scan over the periods, whose
body scans the period's sliding layers and runs its full layer beside them. (The
published list's first period is one sliding layer short, 0 1 1 1 1 0 and then 1
1 1 1 1 0 seven times: the program runs whole periods of one length.)

Norms, rotations, softmaxes and every accumulation are float32; weights, cached
rows and the operands of the matmuls ``dtype``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import layers, moe
from ray_tpu.ops import attention, backend

#: what the attention counts over the real queries of a device call: query-key
#: pairs inside the mask, summed over the full layers and over the sliding ones
ATTENTION_COUNTERS = ("full_keys", "window_keys")


@dataclasses.dataclass(frozen=True)
class MiMoV2FlashConfig:
    vocab_size: int = 152576
    num_layers: int = 7             # of the published 48: layer 0 and one whole period
    sliding_layers: Tuple[bool, ...] = (False, True, True, True, True, True, False)
    embed_dim: int = 4096
    num_heads: int = 64
    head_dim: int = 192             # a query's and a key's features ...
    v_dim: int = 128                # ... and a value's
    kv_heads: int = 4               # a full layer's K/V heads ...
    sliding_kv_heads: int = 8       # ... and a sliding layer's
    rotary_dim: int = 64            # the features of a head that rotate: the first
    sliding_window: int = 128
    rope_base: float = 5000000.0
    sliding_rope_base: float = 10000.0
    value_scale: float = 0.707
    sink_std: float = 4.0           # spread of the seeded sinks
    mlp_dim: int = 16384            # width of layer 0's MLP
    expert_dim: int = 2048
    router_experts: int = 256       # experts the router scores
    num_experts: int = 256          # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 8
    routed_scale: float = 1.0
    bias_std: float = 0.01          # spread of the seeded e_score_correction_bias
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        flags = tuple(bool(s) for s in self.sliding_layers)
        object.__setattr__(self, "sliding_layers", flags)
        rest = flags[1:]
        period = rest.index(False) + 1 if False in rest else 0
        if (len(flags) != self.num_layers or flags[0] or period < 2 or len(rest) % period
                or rest != ((True,) * (period - 1) + (False,)) * (len(rest) // period)):
            raise ValueError(
                f"sliding_layers {tuple(map(int, flags))} of {self.num_layers} layers: the program "
                f"runs a full layer 0 and behind it whole periods of sliding layers that a full "
                f"layer ends, all of one length")
        for heads in (self.kv_heads, self.sliding_kv_heads):
            if self.num_heads % heads:
                raise ValueError(f"{self.num_heads} query heads over {heads} K/V heads")
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"{self.rotary_dim} rotary features of a head of {self.head_dim}")

    @property
    def period(self) -> int:
        """Layers a period behind layer 0: its sliding layers and the full one."""
        return self.sliding_layers[1:].index(False) + 1

    @property
    def periods(self) -> int:
        return (self.num_layers - 1) // self.period

    @property
    def window_layers(self) -> int:
        return sum(self.sliding_layers)

    def num_params(self) -> int:
        """What ``init_params`` holds, the sinks and the router's bias with the weights."""
        d, h = self.embed_dim, self.num_heads
        shared = d * h * self.head_dim + h * self.v_dim * d + 2 * d      # q, o, two norms
        full = shared + d * self.kv_heads * (self.head_dim + self.v_dim)
        sliding = shared + d * self.sliding_kv_heads * (self.head_dim + self.v_dim) + h
        experts = (d + 1) * self.router_experts + self.num_experts * 3 * d * self.expert_dim
        return (
            2 * self.vocab_size * d + full + 3 * d * self.mlp_dim
            + self.window_layers * (sliding + experts)
            + (self.cache_layers - 1) * (full + experts) + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    #: what ``extend`` counts, in the order of its last output
    counters = moe.COUNTERS + ATTENTION_COUNTERS

    @property
    def cached_layers(self) -> Tuple[bool, ...]:
        """Per layer: whether a token is cached in it. The full layers alone."""
        return tuple(not s for s in self.sliding_layers)

    @property
    def cache_layers(self) -> int:
        return sum(self.cached_layers)

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V of a full
        layer, all K/V heads of each side by side in one row."""
        return ((1, self.kv_heads * self.head_dim), (1, self.kv_heads * self.v_dim))

    @property
    def state_arrays(self):
        """What a sequence holds, ``(layers, shape, dtype)`` per array: a sliding
        layer's newest ``sliding_window`` rows of K and of V, a ring by position,
        all K/V heads of a row side by side."""
        return tuple(
            (self.window_layers, (self.sliding_window, self.sliding_kv_heads * width), self.dtype)
            for width in (self.head_dim, self.v_dim))

    @property
    def state_chunk(self) -> int:
        """Tokens between the states ``extend`` can hand back (``snap_at``), and what a
        chunk begins at a whole number of: a window, the sub-chunk of a chunk's attend."""
        return self.sliding_window

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def mimo_v2_flash_nano(**kw) -> MiMoV2FlashConfig:
    """A tiny one for the tests: layer 0 and two periods of two sliding layers (4
    K/V heads, a window of 8) and a full one (2 K/V heads); keys of 24 with 8
    rotated, values of 16; 4 of 16 scored experts held."""
    sizes = dict(
        vocab_size=256, num_layers=7, sliding_layers=(0, 1, 1, 0, 1, 1, 0), embed_dim=64,
        num_heads=8, head_dim=24, v_dim=16, kv_heads=2, sliding_kv_heads=4, rotary_dim=8,
        sliding_window=8, sink_std=2.0, mlp_dim=96, expert_dim=32, router_experts=16,
        num_experts=4, expert_offset=4, experts_per_token=4, bias_std=0.05, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    return MiMoV2FlashConfig(**{**sizes, **kw})


def init_params(cfg: MiMoV2FlashConfig, seed: int = 0):
    """Seeded weights (normal, stddev 0.02; norm scales 1; float32 and normal too the
    sinks, stddev ``sink_std``, and the router's bias, stddev ``bias_std``), made on
    the device in one jitted call: layer 0 under ``first``, the layers behind it
    under ``periods`` (``sliding``: leaves ``[periods, sliding layers a period,
    ...]``; ``full``: ``[periods, ...]``) for ``extend``'s two scans, and every
    expert layer's experts in one stack ``experts`` ``[expert layers, held, ...]`` in
    the layers' order, which the grouped matmul reads in place. The gate and the up
    projection of an MLP or an expert side by side."""
    d, f, h = cfg.embed_dim, cfg.expert_dim, cfg.num_heads
    P, S = cfg.periods, cfg.period - 1

    def attn(lead, kv):
        return {
            "q": lead + (d, h, cfg.head_dim), "k": lead + (d, kv, cfg.head_dim),
            "v": lead + (d, kv, cfg.v_dim), "o": lead + (h, cfg.v_dim, d)}

    kinds = {
        "first": attn((), cfg.kv_heads), "sliding": attn((P, S), cfg.sliding_kv_heads),
        "full": attn((P,), cfg.kv_heads)}
    shapes = {
        "wte": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
        **{f"{kind}_{name}": s for kind, of in kinds.items() for name, s in of.items()},
        "mlp_wi": (d, 2 * cfg.mlp_dim), "mlp_wo": (cfg.mlp_dim, d),
        "sliding_router": (P, S, d, cfg.router_experts), "full_router": (P, d, cfg.router_experts),
        "wi": (P * cfg.period, cfg.num_experts, d, 2 * f),
        "wo": (P * cfg.period, cfg.num_experts, f, d),
    }

    @jax.jit
    def init(rng):
        *keys, k_sinks, k_sliding, k_full = jax.random.split(rng, len(shapes) + 3)
        w = layers.drawn(keys, shapes, cfg.param_dtype)
        ones = functools.partial(layers.ones_scale, cfg.param_dtype)

        def block(kind, lead):
            return {
                "ln_1": ones(*lead, d), "ln_2": ones(*lead, d),
                "attn": {name: {"kernel": w[f"{kind}_{name}"]} for name in kinds[kind]}}

        def routed(kind, lead, key):
            return {"moe": {
                "router": w[f"{kind}_router"],
                "bias": cfg.bias_std * jax.random.normal(
                    key, lead + (cfg.router_experts,), jnp.float32)}}

        sliding = {**block("sliding", (P, S)), **routed("sliding", (P, S), k_sliding)}
        sliding["attn"]["sinks"] = cfg.sink_std * jax.random.normal(
            k_sinks, (P, S, h), jnp.float32)
        return {
            "wte": {"embedding": w["wte"]},
            "first": {**block("first", ()), "mlp": {"wi": w["mlp_wi"], "wo": w["mlp_wo"]}},
            "periods": {
                "sliding": sliding,
                "full": {**block("full", (P,)), **routed("full", (P,), k_full)}},
            "experts": {"wi": w["wi"], "wo": w["wo"]},
            "ln_f": ones(d),
            "head": {"kernel": w["head"]},
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def ring_positions(tokens, window: int):
    """The position each row of a ring holds once a lane has ``tokens`` [b] tokens,
    [b, window]: row ``r`` the newest position that is ``r`` modulo ``window``;
    negative where no such token was written."""
    last = tokens[:, None].astype(jnp.int32) - 1
    return last - (last - jnp.arange(window, dtype=jnp.int32)[None, :]) % window


def make_extend_fn(cfg: MiMoV2FlashConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, k_window,
    v_window, slots, snap_at, snap_slots)``: the contract of ``gpt.make_extend_fn``
    over the full layers' caches (``[cache_layers, lanes, cache, 1, kv_heads x
    features]``, layer 0's first) and the pool's window arenas themselves
    (``cfg.state_arrays``: ``[sliding layers, state slots, sliding_window, K/V heads
    x features]``; a caller that keeps them donates them) with each lane's slot in
    them. Returns ``(logits, hidden, k rows, v rows, k_window, v_window,
    counters)``: the arenas hold, in each lane's slot, the ring after its last real
    token, and a call of more than one token a lane also writes the ring after
    ``snap_at[lane]`` of its tokens to slot ``snap_slots[lane]`` (0, nobody's, where
    none is to be kept). No other slot is touched, and of a decode lane's slot one
    row. A negative token id is padding and changes no ring; a lane of padding alone
    points at slot 0. ``counters`` (``cfg.counters``) over real lanes and tokens.
    With ``table=`` [lanes, n] (a call of one token a lane, from an engine that reads
    the keyword off the signature: ``serve/llm.reads_pages``) ``k_cache`` and
    ``v_cache`` are the pool's block arenas themselves, ``[cache_layers, blocks,
    block, 1, kv_heads x features]``, and a full layer attends over the lanes' pages
    where they lie (:func:`layers.paged_attend`); the same rows come back.

    Scopes: ``extend.embed``; ``extend.dense`` (layer 0's MLP); ``extend.attention``
    (a full layer's projections, rotation, cache update and attend: a chunk's on the
    chip ``ops/attention.masked_attention``, a decode lane's through the block table
    ``ops/attention.paged_attention``, any off the chip :func:`layers.plain_attend`);
    ``extend.attention.window`` (a sliding layer's
    projections, rotation, the slot's read, the attend under the sink (the same
    kernel with ``sinks``, or densely), the slot's write and the snapshot's);
    ``extend.moe.route``, ``extend.moe.experts``; ``extend.logits`` (the last norm and
    the head, of the rows that are read: ``last=``, ``layers.read_rows``; every row
    without it)."""
    dtype, f32 = cfg.dtype, jnp.float32
    hd, vd, window = cfg.head_dim, cfg.v_dim, cfg.sliding_window
    scale = 1.0 / float(np.sqrt(hd))
    per_period = cfg.period - 1

    def _normed(x, p, name):
        return layers.rms_norm(x, p[name]["scale"], cfg.norm_eps)

    def _project(p, hidden, positions, base):
        """``q`` [b, t, kv, groups, hd], ``k`` [b, t, kv, hd] (both rotated over their
        first ``rotary_dim`` features) and ``v`` [b, t, kv, vd]."""
        def to_heads(name):
            return jnp.einsum("btd,dhk->bthk", hidden, p[name]["kernel"].astype(dtype))

        def rotated(x):
            return layers.rotary(x.astype(f32), positions, cfg.rotary_dim, base).astype(dtype)

        q, k = rotated(to_heads("q")), rotated(to_heads("k"))
        kv = k.shape[2]
        return q.reshape(q.shape[:2] + (kv, cfg.num_heads // kv, hd)), k, to_heads("v")

    def _out(p, attended):
        """The attended values of every head, scaled, through ``W_o``."""
        b, tc = attended.shape[:2]
        attended = (attended.astype(f32) * cfg.value_scale).astype(dtype)
        return jnp.einsum(
            "bthv,hvd->btd", attended.reshape(b, tc, cfg.num_heads, vd),
            p["o"]["kernel"].astype(dtype))

    def _rows(x):
        """``x`` [b, t, kv, width] as rows of all K/V heads side by side."""
        return x.reshape(x.shape[:2] + (-1,))

    @jax.named_scope("extend.attention")
    def _attend_full(p, hidden, positions, visible, live, kc, vc, paged=None):
        """``kc``, ``vc`` the layer's slab of the padded caches; or, with ``paged`` (the
        layer's index and the lanes' block table), the pool's arenas themselves."""
        b, tc = positions.shape
        q, k, v = _project(p, hidden, positions, cfg.rope_base)
        k, v = _rows(k)[:, :, None], _rows(v)[:, :, None]       # [b, tc, 1, kv x width]
        if paged is not None:
            out = layers.paged_attend(q, k, v, kc, vc, *paged, positions, visible, scale)
            return _out(p, out), k, v
        cap = kc.shape[1]
        lane = jnp.arange(b)[:, None]
        keys = layers.write_rows(kc, lane, positions, k).reshape(b, cap, cfg.kv_heads, hd)
        values = layers.write_rows(vc, lane, positions, v).reshape(b, cap, cfg.kv_heads, vd)
        if tc > 1 and backend.on_tpu():
            out = attention.masked_attention(q, keys, values, visible, live, scale=scale)
        elif tc == 1:
            out = layers.plain_attend(q, keys, values, visible, scale)
        else:
            out = layers.by_query_block(
                lambda qb, mask: layers.plain_attend(qb, keys, values, mask, scale), q, visible)
        return _out(p, out), k, v

    def _sunk_attend(q, keys, values, mask, sinks):
        """:func:`layers.plain_attend` with ``sinks`` [kv, groups] in the softmax's
        denominator: a last logit that every query sees and that has no value."""
        logit = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q, keys, preferred_element_type=f32) * scale
        logit = jnp.where(mask[:, None, None], logit, f32(layers.MASKED))
        sink = jnp.broadcast_to(sinks[None, :, :, None, None], logit.shape[:-1] + (1,))
        weight = jax.nn.softmax(jnp.concatenate([logit, sink], -1), axis=-1)[..., :-1]
        return jnp.einsum("bhgqk,bkhd->bqhgd", weight.astype(values.dtype), values)

    # An arena is read and written one slot at a time, with a dynamic slice and an
    # in-place dynamic update: indexed with the slots the TPU compiler first copies all
    # of it (``models/granitemoehybrid.py``; ``tests/test_chip_compile_serve.py`` holds
    # ``extend`` to this).

    def _as_it_lies(rows):
        """``rows`` [lanes, n, width] held to the arena's own layout, a row's features
        innermost: left free, the compiler lays a lane's ring out as the attend's
        matmul would like it (the window's rows innermost) and, to spare the 0.4 MB
        re-layout, carries the **whole arena** through the layers' loop that way: two
        copies of all of it a call (1.68 GB each way at 512 slots)."""
        if not backend.on_tpu():
            return rows
        from jax.experimental.layout import Layout, with_layout_constraint

        return with_layout_constraint(rows, Layout(major_to_minor=tuple(range(rows.ndim))))

    def _take(arena, slots, at):
        """``arena[at, slots]``: [lanes, window, width]."""
        return _as_it_lies(jnp.concatenate([
            jax.lax.dynamic_slice(arena, (at, slots[i], 0, 0), (1, 1) + arena.shape[2:])[0]
            for i in range(slots.shape[0])], axis=0))

    def _put(arena, slots, rows, new, at):
        """``arena[at, slots, rows:rows + n] = new`` [lanes, n, width], lane by lane
        where the arena lies."""
        new = _as_it_lies(new.astype(arena.dtype))
        for i in range(slots.shape[0]):
            arena = jax.lax.dynamic_update_slice(
                arena, new[None, i:i + 1], (at, slots[i], rows[i], 0))
        return arena

    @jax.named_scope("extend.attention.window")
    def _attend_window(p, hidden, positions, valid, lengths, where, arenas, at):
        """``arenas`` (K, V) hold every sequence's ring of every sliding layer: lane
        ``i``'s of layer ``at`` is read from slot ``slots[i]`` and, with the call's
        rows in it, written back there. Returns the layer's output and ``arenas``."""
        slots, snap_at, snap_slots = where
        b, tc = positions.shape
        kv = cfg.sliding_kv_heads
        sinks = p["sinks"].astype(f32).reshape(kv, cfg.num_heads // kv)
        q, k, v = _project(p, hidden, positions, cfg.sliding_rope_base)
        news = (_rows(k), _rows(v))                                 # [b, tc, kv x width]
        rings = tuple(_take(arena, slots, at) for arena in arenas)  # [b, window, kv x width]
        real = valid.sum(1, dtype=jnp.int32)
        zero = jnp.zeros((b,), jnp.int32)

        def by_head(rows, width):
            return rows.reshape(rows.shape[:2] + (kv, width))

        if tc == 1:
            # the lane's row goes into the ring it has read, and into its slot
            row = lengths % window
            here = (jnp.arange(window)[None, :] == row[:, None]) & valid    # [b, window]
            rings = tuple(
                jnp.where(here[:, :, None], new, ring) for new, ring in zip(news, rings))
            mask = (ring_positions(lengths + real, window) >= 0)[:, None, :] & valid[:, :, None]
            out = _sunk_attend(q, by_head(rings[0], hd), by_head(rings[1], vd), mask, sinks)
            arenas = tuple(
                _put(arena, slots, row, jnp.take_along_axis(ring, row[:, None, None], axis=1), at)
                for arena, ring in zip(arenas, rings))
            return _out(p, out), arenas

        # a chunk, in sub-chunks of a window's tokens: each attends over [the window's
        # rows before it ‖ its own] under the band, the first over the slot's rows at
        # the positions the lane's length says. A sub-chunk is a lane of its own to the
        # attend, so the same tokens behind the same rows give the same bits wherever
        # in a call they lie (a chunk begins at a whole number of windows: the engine's
        # blocks are, ``state_chunk``), which a prefix hit's bitwise gate rests on
        sub = window if tc % window == 0 else tc
        held = ring_positions(lengths, window)                              # [b, window]

        def before_and_own(ring, own):
            """``[b x sub-chunks, window + sub, ...]``: ``ring`` [b, window, ...] before the
            first sub-chunk of ``own`` [b, tc, ...], every other behind its predecessor."""
            own = own.reshape((b, tc // sub, sub) + own.shape[2:])
            before = jnp.concatenate([ring[:, None], own[:, :-1, sub - window:]], axis=1)
            both = jnp.concatenate([before, own], axis=2)
            return both.reshape((b * (tc // sub),) + both.shape[2:])

        key_at = before_and_own(held, positions)
        key_ok = before_and_own(held >= 0, valid)
        at_own, ok_own = key_at[:, window:], key_ok[:, window:]
        behind = at_own[:, :, None] - key_at[:, None, :]
        mask = (behind >= 0) & (behind < window) & key_ok[:, None, :] & ok_own[:, :, None]
        keys = by_head(before_and_own(rings[0], news[0]), hd)
        values = by_head(before_and_own(rings[1], news[1]), vd)
        queries = q.reshape((b * (tc // sub), sub) + q.shape[2:])
        if backend.on_tpu():
            out = attention.masked_attention(
                queries, keys, values, mask, jnp.full(keys.shape[:1], window + sub, jnp.int32),
                scale=scale, sinks=sinks)
        else:
            out = _sunk_attend(queries, keys, values, mask, sinks)
        out = out.reshape((b, tc) + out.shape[2:])
        both = tuple(jnp.concatenate([ring, new], axis=1) for ring, new in zip(rings, news))

        def ring_after(fed):
            """The rings once ``fed`` [b] of the call's tokens are in: row ``r`` the
            call's own where one of them is the newest at ``r``, else as it was."""
            newest = ring_positions(lengths + fed, window)
            source = jnp.where(
                newest >= lengths[:, None], window + newest - lengths[:, None],
                jnp.arange(window, dtype=jnp.int32)[None, :])
            return tuple(jnp.take_along_axis(x, source[:, :, None], axis=1) for x in both)

        kept, ended = ring_after(jnp.clip(snap_at, 0, real)), ring_after(real)
        arenas = tuple(
            _put(_put(arena, snap_slots, zero, snap, at), slots, zero, end, at)
            for arena, snap, end in zip(arenas, kept, ended))
        return _out(p, out), arenas

    def _experts(p, experts, layer, normed, valid):
        b, tc, d = normed.shape
        flat = normed.reshape(b * tc, d)
        with jax.named_scope("extend.moe.route"):
            weights, chosen = moe.sigmoid_bias_top_k(
                flat, p["moe"]["router"], p["moe"]["bias"], cfg.experts_per_token,
                cfg.routed_scale)
        with jax.named_scope("extend.moe.experts"):
            routed, counters = moe.held_experts_ffn(
                flat.astype(dtype), weights, chosen, valid.reshape(b * tc), experts["wi"],
                experts["wo"], cfg.expert_offset, layer)
        return routed.astype(dtype).reshape(b, tc, d), counters

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, k_window, v_window, slots, snap_at,
               snap_slots, *, last=None, table=None):
        positions, valid = layers.frame(tokens, lengths)
        lengths = lengths.astype(jnp.int32)
        where = (slots, snap_at, snap_slots)
        cap = layers.cache_slots(k_cache, table)
        reads = (layers.visible_keys(positions, valid, cap), layers.live_keys(positions, valid))
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)
        experts = params["experts"]

        def full_layer(x, p, at, ffn):
            # the layer's slab of the caches where it lies; the arenas' the kernel finds
            kc, vc = (k_cache, v_cache) if table is not None else (
                jax.lax.dynamic_index_in_dim(c, at, 0, keepdims=False)
                for c in (k_cache, v_cache))
            a, k, v = _attend_full(
                p["attn"], _normed(x, p, "ln_1").astype(dtype), positions, *reads, kc, vc,
                None if table is None else (at, table))
            x = x + a
            f, counted = ffn(_normed(x, p, "ln_2"))
            return x + f, (k, v), counted

        def dense(normed):
            with jax.named_scope("extend.dense"):
                mlp = params["first"]["mlp"]
                return layers.gated_mlp(
                    normed.astype(dtype), mlp["wi"], mlp["wo"]).astype(dtype), ()

        x, first_rows, _ = full_layer(x, params["first"], 0, dense)

        def one_period(carry, xs):
            p, period = xs

            def one_sliding(carry, xs):
                # the window arenas are carried whole and each layer's slots are read
                # and written where they lie
                x, arenas = carry
                layer, i = xs
                a, arenas = _attend_window(
                    layer["attn"], _normed(x, layer, "ln_1").astype(dtype), positions, valid,
                    lengths, where, arenas, period * per_period + i)
                x = x + a
                f, counted = _experts(
                    layer, experts, period * cfg.period + i, _normed(x, layer, "ln_2"), valid)
                return (x + f, arenas), counted

            (x, arenas), counted = jax.lax.scan(
                one_sliding, carry, (p["sliding"], jnp.arange(per_period, dtype=jnp.int32)))
            x, rows, last = full_layer(
                x, p["full"], 1 + period,
                lambda normed: _experts(
                    p["full"], experts, period * cfg.period + per_period, normed, valid))
            return (x, arenas), (rows, counted.sum(0) + last)

        (x, (k_window, v_window)), (rows, counted) = jax.lax.scan(
            one_period, (x, (k_window, v_window)),
            (params["periods"], jnp.arange(cfg.periods, dtype=jnp.int32)))
        k_new, v_new = (
            jnp.concatenate([first[None], behind]) for first, behind in zip(first_rows, rows))
        logits, x = layers.rms_head(
            x, params["ln_f"]["scale"], cfg.norm_eps, params["head"]["kernel"], dtype, last)
        seen = jnp.where(valid, positions + 1, 0)
        attended = jnp.stack([
            cfg.cache_layers * jnp.minimum(seen, cap).sum(dtype=jnp.int32),
            cfg.window_layers * jnp.minimum(seen, window).sum(dtype=jnp.int32)])
        return (
            logits, x, k_new, v_new, k_window, v_window,
            jnp.concatenate([counted.sum(0), attended]))

    return extend
