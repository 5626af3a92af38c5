"""granite-4.0-h (``model_type`` ``granitemoehybrid``): the serving path behind
``serve/llm.py`` of a model whose layers are mostly **recurrent**.

Every layer is ``h += r * Mixer(RMSNorm(h))``, ``h += r * MLP(RMSNorm(h))``
(``r`` the ``residual_multiplier``; the MLP gated, ``W_out (silu(g) * u)``). Where
the configuration has routed experts (``router_experts``: granite-4.0-h-small;
micro has none) the second half is ``h += r * (Shared(n) + sum_k w_k
Expert_k(n))``, ``n = RMSNorm(h)``: the MLP above is the *shared* expert, beside it
the dropless expert layer of ``models/moe.py``: a softmax router over all
``router_experts``, the ``experts_per_token`` largest normalised over themselves,
and the experts **held here** (``num_experts`` of width ``expert_dim``, from
``expert_offset``: one chip's share of an expert-parallel deployment) computed for
the tokens routed to them, in place in their stack; what the absent experts would
add is left out. The layers come in periods of ``period`` with one attention layer at
``attention_at`` and Mamba-2 layers around it, and differ from the engine's
other architectures in what a *sequence* leaves behind:

* a **Mamba-2** layer caches nothing per token. For every head ``h`` it keeps
  one state ``S`` (``ssm_head_dim x ssm_state``, float32) and the last
  ``conv_width - 1`` inputs of its causal depthwise convolution: per token ``t``
  ``[z, xBC, dt] = W_in r``, ``xBC <- silu(conv(xBC))``, ``[x, B, C] = xBC`` (``B``
  and ``C`` shared by all heads: one group), ``D_t = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``, ``y_t = S_t
  C_t + D x_t``, then ``W_out RMSNorm(y * silu(z))``. The configuration names the
  two arrays (``state_arrays``); the engine keeps a slot of each for a sequence
  in arenas ``[ssm_layers, slots, ...]`` and hands ``extend`` the arenas
  themselves with the lanes' slot ids: a layer reads a lane's state where the
  pool keeps it and writes the new one to the same place, and nothing else of an
  arena is touched (slot 0 is nobody's: a padded lane's);
* a decode lane does one step of the recurrence: on the chip one kernel
  (:func:`ssm_step_slots`) that fetches a lane's state from its slot, updates
  it and reads it out in VMEM and writes it back, one read and one write of the
  state; elsewhere the same addressing in ``jax.numpy``. A prefill chunk computes it in
  sub-chunks of ``ssm_chunk`` tokens (the chunked, "SSD" form: inside a
  sub-chunk a masked matmul, between them the state), one ``lax.scan`` body for
  every sub-chunk, so that the same tokens from the same state give the same
  bits wherever in a call they lie. The states between sub-chunks are what a
  prefix cache can restore: ``extend`` writes the one ``snap_at`` tokens in (a
  whole number of sub-chunks) to the slot it is told (``snap_slots``), beside
  the state at the chunk's end in the lane's own;
* a padded token (id < 0) has ``D_t = 0``: it neither decays nor feeds a state,
  and the convolution's tail skips it; a lane of length 0 starts from zeros,
  whatever its slot holds;
* the **attention** layers are grouped (``num_heads`` over ``kv_heads``), with
  no position encoding at all and a softmax of ``attention_multiplier * q . k``.
  A token caches K and V of those layers only (``cache_layers``), each as one
  row of ``kv_heads x head_dim`` values: whole 128-lane tiles, where a last axis
  of ``head_dim`` 64 would be re-laid out in every gather;
* the embedding is scaled by ``embedding_multiplier`` and tied to the head,
  whose logits are divided by ``logits_scaling``.

State, norms, softmax, ``D_t``, ``exp(D_t A)`` and every accumulation are
float32; weights and the operands of the matmuls ``dtype``.
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
from ray_tpu.ops import attention, backend

#: what ``extend`` counts over the real lanes and tokens of a device call,
#: summed over the Mamba layers: tokens through the recurrence, and states read
#: and written once (a lane, a layer)
SSM_COUNTERS = ("ssm_tokens", "ssm_state_passes")


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    num_layers: int = 40
    period: int = 10                # layers a period: one attention, the others Mamba-2
    attention_at: int = 5           # where in a period the attention layer stands
    embed_dim: int = 2048
    mlp_dim: int = 8192             # the MLP every token passes: with experts, the shared one
    expert_dim: int = 0             # width of one routed expert
    router_experts: int = 0         # experts the router scores: 0, and the block has no routed half
    num_experts: int = 0            # experts held here ...
    expert_offset: int = 0          # ... from this one on
    experts_per_token: int = 0
    num_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_chunk: int = 256            # tokens a sub-chunk of the chunked recurrence
    conv_width: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the recurrent state, on the device and in ``extend``

    def __post_init__(self):
        if self.num_layers % self.period or not 0 <= self.attention_at < self.period:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of {self.period} with the "
                f"attention layer at {self.attention_at}")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{self.num_heads} query heads over {self.kv_heads} K/V heads")
        if not 0 <= self.expert_offset <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + self.num_experts - 1} "
                f"are not among the {self.router_experts} the router scores")

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    @property
    def ssm_layers(self) -> int:
        return self.periods * (self.period - 1)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels through the convolution: ``x`` and one group's ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def layer_types(self):
        return tuple(
            "attention" if i % self.period == self.attention_at else "mamba"
            for i in range(self.num_layers))

    def num_params(self) -> int:
        d, inner = self.embed_dim, self.ssm_inner
        mlp = 3 * d * self.mlp_dim + d * self.router_experts + (
            self.num_experts * 3 * d * self.expert_dim)
        mamba = (
            d * (inner + self.conv_dim + self.ssm_heads) + (self.conv_width + 1) * self.conv_dim
            + 3 * self.ssm_heads + inner + inner * d)
        attn = d * self.head_dim * (2 * self.num_heads + 2 * self.kv_heads)
        return (
            self.vocab_size * d + self.ssm_layers * mamba + self.periods * attn
            + self.num_layers * (mlp + 2 * d) + d)

    # -- what the serving engine asks of a configuration (``serve/llm.py``) --

    @property
    def counters(self):
        """What ``extend`` counts, in the order of its last output: the
        recurrence's two and, with experts, the expert layers' four."""
        return SSM_COUNTERS + (moe.COUNTERS if self.router_experts else ())

    @property
    def cache_layers(self) -> int:
        """The layers a token is cached in: the attention layers alone."""
        return self.periods

    @property
    def cache_arrays(self):
        """What a cached token holds, ``(heads, dim)`` per array: K and V, all
        K/V heads of each side by side in one row."""
        row = self.kv_heads * self.head_dim
        return ((1, row), (1, row))

    @property
    def state_arrays(self):
        """What a sequence holds, ``(layers, shape, dtype)`` per array: a Mamba
        layer's state, and the inputs its convolution still needs."""
        return (
            (self.ssm_layers, (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
             self.state_dtype),
            (self.ssm_layers, (self.conv_width - 1, self.conv_dim), self.dtype),
        )

    @property
    def state_chunk(self) -> int:
        """Tokens between the states ``extend`` can hand back (``snap_at``)."""
        return self.ssm_chunk

    def make_extend_fn(self):
        return make_extend_fn(self)

    def init_params(self, seed: int = 0):
        return init_params(self, seed)


def granite_hybrid_nano(**kw) -> GraniteMoeHybridConfig:
    """A tiny one for the tests: two periods of three Mamba-2 layers and one
    attention layer, sub-chunks of 8 tokens. Given ``router_experts`` it has the
    routed half too: experts of width 32, four of them held, three a token."""
    sizes = dict(
        vocab_size=256, num_layers=8, period=4, attention_at=2, embed_dim=64, mlp_dim=96,
        num_heads=4, kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=16, ssm_state=16,
        ssm_chunk=8, max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    if kw.get("router_experts"):
        sizes.update(expert_dim=32, num_experts=4, experts_per_token=3)
    return GraniteMoeHybridConfig(**{**sizes, **kw})


def init_params(cfg: GraniteMoeHybridConfig, seed: int = 0):
    """Seeded weights, made on the device in one jitted call: under
    ``periods`` one tree for each layer of a period (``mamba``: a tuple of the
    Mamba layers', ``attn``, ``mlp``: a tuple of every layer's), each leaf
    stacked ``[periods, ...]`` for ``extend``'s scan, which so reads a layer's
    weights where they lie. Matrices normal with stddev 0.02 and norm scales 1; a
    Mamba layer's own as the published Mamba-2 initialiser has them: ``A_log = log(uniform(1, 16))``,
    ``dt_bias`` the inverse softplus of a step log-uniform in (0.001, 0.1), ``D``
    1 (float32, all three), the convolution's kernel and bias uniform within
    ``conv_width^-0.5``. ``W_in`` is stored as its three parts (``z``, ``xBC``,
    ``dt``), the gate and the up projection of an MLP side by side. With experts
    an ``mlp`` tree has the ``router`` ``[periods, embed, router_experts]`` too, and
    beside ``periods``, out of the scan's reach, ``experts`` holds the held ones of
    each layer of a period (``wi`` ``[periods, num_experts, embed, 2 expert_dim]``,
    ``wo`` ``[periods, num_experts, expert_dim, embed]``): the grouped matmul reads
    them where they lie."""
    d, inner, heads = cfg.embed_dim, cfg.ssm_inner, cfg.ssm_heads
    P, M, L = cfg.periods, cfg.period - 1, cfg.period
    kv = cfg.kv_heads * cfg.head_dim
    mamba = {
        "in_z": (P, d, inner), "in_xbc": (P, d, cfg.conv_dim), "in_dt": (P, d, heads),
        "out": (P, inner, d)}
    attn = {
        "q": (P, d, cfg.num_heads * cfg.head_dim), "k": (P, d, kv), "v": (P, d, kv),
        "o": (P, cfg.num_heads * cfg.head_dim, d)}
    mlp = {"wi": (P, d, 2 * cfg.mlp_dim), "wo": (P, cfg.mlp_dim, d)}
    if cfg.router_experts:
        mlp["router"] = (P, d, cfg.router_experts)
        experts = {
            "wi": (P, cfg.num_experts, d, 2 * cfg.expert_dim),
            "wo": (P, cfg.num_experts, cfg.expert_dim, d)}
    bound = cfg.conv_width ** -0.5

    def drawn(key, shapes):
        return layers.drawn(jax.random.split(key, len(shapes)), shapes, cfg.param_dtype)

    ones = functools.partial(layers.ones_scale, cfg.param_dtype)

    def mamba_layer(key):
        k_w, k_conv, k_bias, k_a, k_dt = jax.random.split(key, 5)
        step = jnp.exp(jax.random.uniform(
            k_dt, (P, heads), jnp.float32, np.log(0.001), np.log(0.1)))
        return {
            "ln": ones(P, d),
            **{n: {"kernel": w} for n, w in drawn(k_w, mamba).items()},
            "conv": {
                "kernel": jax.random.uniform(
                    k_conv, (P, cfg.conv_width, cfg.conv_dim), jnp.float32, -bound, bound
                ).astype(cfg.param_dtype),
                "bias": jax.random.uniform(
                    k_bias, (P, cfg.conv_dim), jnp.float32, -bound, bound
                ).astype(cfg.param_dtype),
            },
            "A_log": jnp.log(jax.random.uniform(k_a, (P, heads), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "D": jnp.ones((P, heads), jnp.float32),
            "norm": ones(P, inner),
        }

    @jax.jit
    def init(rng):
        k_wte, k_attn, *keys = jax.random.split(rng, 2 + M + L)
        held = {"experts": tuple(
            drawn(jax.random.fold_in(k, 1), experts) for k in keys[M:])} if cfg.router_experts else {}
        return {
            "wte": {"embedding": layers.normal(k_wte, (cfg.vocab_size, d), cfg.param_dtype)},
            "periods": {
                "mamba": tuple(mamba_layer(k) for k in keys[:M]),
                "attn": {
                    "ln": ones(P, d),
                    **{n: {"kernel": w} for n, w in drawn(k_attn, attn).items()}},
                "mlp": tuple({"ln": ones(P, d), **drawn(k, mlp)} for k in keys[M:]),
            },
            "ln_f": ones(d),
            **held,
        }

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def ssm_step(state, x, dt, a, b, c):
    """One token of the recurrence, every head: ``state`` [lanes, heads, p, n]
    float32, ``x`` [lanes, heads, p], ``dt`` [lanes, heads] (0 for a padded
    token), ``a`` [heads] (negative), ``b``, ``c`` [lanes, n], all float32.
    Returns ``y`` [lanes, heads, p] (without ``D x``) and the new state."""
    decay = jnp.exp(dt * a)[..., None, None]
    state = decay * state + (dt[..., None] * x)[..., None] * b[:, None, None, :]
    return (state * c[:, None, None, :]).sum(-1), state


#: heads of a lane's state that one grid step of :func:`ssm_step_slots` moves:
#: 32 x 64 x 128 float32 = 1 MB in and 1 MB out, each buffered twice in VMEM
SSM_STEP_HEADS = 32


def _ssm_step_kernel(slots, real, fresh, at, fed, decay, b, c, state, y, out, *, heads):
    """One block of ``heads`` heads of one lane: ``fed`` [p, heads] (``dt x``,
    a head a column), ``decay`` [1, heads], ``b``, ``c`` [1, n], ``state`` and
    ``out`` [heads, p, n] (one block of the arena, aliased), ``y`` [p, heads]."""
    from jax.experimental import pallas as pl

    lane = pl.program_id(0)

    @pl.when(real[lane] == 0)
    def _():
        # a padded lane: slot 0's first block goes back as it came
        out[...] = state[...]
        y[...] = jnp.zeros_like(y)

    @pl.when(real[lane] != 0)
    def _():
        start_over = fresh[lane] != 0
        column = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        read = jnp.zeros(y.shape, jnp.float32)
        for h in range(heads):
            old = jnp.where(start_over, 0.0, state[h])               # [p, n]
            new = decay[:, h:h + 1] * old + fed[:, h:h + 1] * b[...]
            out[h] = new
            read = jnp.where(
                column == h, (new * c[...]).sum(-1, keepdims=True), read)
        y[...] = read


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def ssm_step_slots(arena, at, slots, real, fresh, x, dt, a, b, c, *,
                   heads: int = SSM_STEP_HEADS, interpret: bool = False):
    """:func:`ssm_step` on states where the pool keeps them, as one kernel:
    ``arena`` [layers, slots, heads, p, n] float32 holds every sequence's state
    of every layer, and lane ``i``'s of layer ``at`` is ``arena[at, slots[i]]``.
    A grid step fetches ``heads`` heads of it, updates them and reads them out
    in VMEM (float32) and writes them back where they came from: the state is
    read once and written once, and the arena is the kernel's output too
    (``input_output_aliases``), untouched wherever no lane points. ``real``
    [lanes] says which lanes hold a token: the others neither read nor write
    their slot (each step of theirs revisits the first block of slot 0, which is
    nobody's: it is fetched once and written back as it came); ``fresh`` which
    start from zeros whatever their slot holds. ``x`` [lanes, heads, p], ``dt``
    [lanes, heads] (0 for a padded token), ``a`` [heads], ``b``, ``c`` [lanes,
    n], float32. Returns ``y`` [lanes, heads, p] and the arena. A jit of its
    own: the nine layers of a period, and every ``extend`` shape of a lane
    bucket, share one trace of the kernel and one lowering a program (traced
    layer by layer the 20 shapes' set-up took a minute longer on the chip's host)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, all_heads, p = x.shape
    n = b.shape[-1]
    heads = min(heads, all_heads)
    blocks = all_heads // heads
    real = real.astype(jnp.int32)

    def by_block(v):                # [lanes, all_heads, w] -> [lanes, blocks, w, heads]
        return v.reshape(lanes, blocks, heads, -1).swapaxes(2, 3)

    def small(width):
        return pl.BlockSpec((None, None, width, heads), lambda i, h, *_: (i, h, 0, 0))

    row = pl.BlockSpec((None, 1, n), lambda i, h, *_: (i, 0, 0))
    state = pl.BlockSpec(
        (None, None, heads, p, n),
        lambda i, h, slots, real, fresh, at: (at[0], slots[i], real[i] * h, 0, 0))
    y, arena = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=heads),
        name="ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(lanes, blocks),
            in_specs=[small(p), small(1), row, row, state],
            out_specs=[small(p), state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((lanes, blocks, p, heads), jnp.float32),
            jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        jnp.where(real != 0, slots, 0).astype(jnp.int32), real, fresh.astype(jnp.int32),
        jnp.reshape(at, (1,)).astype(jnp.int32),
        by_block(dt[..., None] * x), by_block(jnp.exp(dt * a)[..., None]),
        b[:, None], c[:, None], arena)
    return y.swapaxes(2, 3).reshape(lanes, all_heads, p), arena


def ssm_chunked(state, x, dt, a, b, c, chunk: int, dtype):
    """The recurrence over ``t`` tokens in sub-chunks of ``chunk`` (``t`` a whole
    number of them), as matmuls: ``x`` [lanes, t, heads, p], ``dt`` [lanes, t,
    heads] float32 (0 for a padded token), ``b``, ``c`` [lanes, t, n], or in
    **groups** ``[lanes, t, groups, n]``: head ``h`` reads group ``h // (heads /
    groups)`` (``models/nemotron_h.py``: eight; this file's models have one and
    hand over no group axis). Inside a
    sub-chunk ``y_t = sum_{s<=t} exp(a_t - a_s) (c_t . b_s) dt_s x_s`` with ``a``
    the running sum of ``dt A``; from the state before it ``exp(a_t) S c_t``.
    The matmuls take ``dtype`` operands and sum in float32; the state's own
    read-out is float32 throughout. Returns ``y`` [lanes, t, heads, p] float32,
    the last state, and the state after each sub-chunk ``[t / chunk, lanes,
    ...]``. One ``lax.scan`` body: a sub-chunk's result does not depend on where
    in the call it lies. Differentiable (``jax.grad`` through the ``lax.scan``: a
    masked pair's weight is ``exp(-inf)``, whose gradient is 0 as its value is),
    with the state's path float32 in the backward as in the forward; plain
    autodiff keeps every sub-chunk's ``[lanes, heads, chunk, chunk]`` products, which
    a train step at full size has no room for: that is :func:`ssm_scan`'s."""
    lanes, t, heads, p = x.shape
    n, f32 = b.shape[-1], jnp.float32
    nc = t // chunk
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    if b.ndim == 4:
        # wherever the heads meet b or c they split [groups, heads a group]
        pairs, read, feed = "bqgn,bsgn->bgqs", "bgrpn,bqgn->bgrqp", "bgrsp,bsgn->bgrpn"

        def by_group(v):            # [lanes, heads, ...] -> [lanes, groups, heads a group, ...]
            return v.reshape((lanes, b.shape[2], -1) + v.shape[2:])

        def by_head(v):             # and back
            return v.reshape((lanes, heads) + v.shape[3:])

        def every_head(pair):       # a group's pairs [lanes, groups, q, s] beside its heads
            return pair[:, :, None]
    else:
        pairs, read, feed = "bqn,bsn->bqs", "bhpn,bqn->bhqp", "bhsp,bsn->bhpn"
        by_group = by_head = lambda v: v
        every_head = lambda pair: pair[:, None]

    def split(v):                   # [lanes, t, ...] -> [nc, lanes, chunk, ...]
        return jnp.moveaxis(v.reshape((lanes, nc, chunk) + v.shape[2:]), 1, 0)

    def one(state, xs):
        xq, dtq, bq, cq = xs        # [lanes, chunk, heads, p], [.., heads], [.., n], [.., n]
        run = jnp.cumsum(dtq * a, axis=1).transpose(0, 2, 1)        # [lanes, heads, chunk]
        fed = (dtq[..., None] * xq.astype(f32)).transpose(0, 2, 1, 3)   # [lanes, heads, chunk, p]
        pair = jnp.einsum(pairs, cq, bq, preferred_element_type=f32)
        # masked before the exponential: a later token's difference is positive
        span = jnp.where(causal, run[..., :, None] - run[..., None, :], -jnp.inf)
        weight = by_head(every_head(pair) * by_group(jnp.exp(span))).astype(dtype)  # [lanes, heads, q, s]
        y = jnp.einsum("bhqs,bhsp->bhqp", weight, fed.astype(dtype), preferred_element_type=f32)
        y = y + jnp.exp(run)[..., None] * by_head(jnp.einsum(
            read, by_group(state), cq.astype(f32), precision=jax.lax.Precision.HIGHEST))
        to_end = jnp.exp(run[..., -1:] - run)                       # [lanes, heads, chunk]
        state = jnp.exp(run[..., -1])[..., None, None] * state + by_head(jnp.einsum(
            feed, by_group((fed * to_end[..., None]).astype(dtype)), bq,
            preferred_element_type=f32))
        return state, (y.transpose(0, 2, 1, 3), state)

    state, (y, between) = jax.lax.scan(one, state, tuple(map(split, (x, dt, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(lanes, t, heads, p), state, between


SSM_SCAN_HEADS = 8      # heads of one B/C group that a grid step of the scan's kernels holds

_NT = (((1,), (1,)), ((), ()))      # [m, k] x [n, k] -> [m, n]
_TN = (((0,), (0,)), ((), ()))      # [k, m] x [k, n] -> [m, n]


def _scan_head_lanes(heads, width):
    """``[heads, width]`` float32: 1 where a lane of ``[.., heads x p]`` is the head's."""
    p = width // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    first = p * jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0)
    return ((lane >= first) & (lane < first + p)).astype(jnp.float32)


def _scan_spread(rows, mine):
    """``rows`` [heads, chunk] float32 -> ``[chunk, heads x p]``: what a token has of a
    head over the head's lanes, **exactly**, as one pass of the MXU: a float32 is the sum
    of three bfloat16s, the three stand side by side along the product's inner axis
    against ``mine`` three times over (0s and 1s: exact in bfloat16), and each sum has
    three terms that float32 adds without rounding. (At the highest precision the same
    product is six passes a 128 x 128 tile, as many as the state's read-out.)"""
    f32, bf16 = jnp.float32, jnp.bfloat16
    first = rows.astype(bf16).astype(f32)
    second = (rows - first).astype(bf16).astype(f32)
    third = rows - first - second
    parts = jnp.concatenate([first, second, third], axis=0).T.astype(bf16)   # [chunk, 3 x heads]
    return jnp.dot(
        parts, jnp.concatenate([mine] * 3, axis=0).astype(bf16), preferred_element_type=f32)


def _scan_tiles(heads, width):
    """The heads of ``[.., heads x p]`` by vector tile of 128 lanes: ``(the tile's lanes,
    its heads)``. A head's ``[.., p]`` is taken out of its tile by its 0s and 1s
    (:func:`_scan_head_lanes`), not by a slice: a product over the masked tile is the
    head's, and lands where the head lies."""
    tile = min(128, width)
    per = tile * heads // width
    assert per >= 1, f"a head of {width // heads} lanes spans several vector tiles"
    return [(slice(k * tile, (k + 1) * tile), range(k * per, (k + 1) * per))
            for k in range(width // tile)]


def _ssm_scan_fwd_kernel(state0, x, dt, run, b, c, y, last, *rest, heads, dtype):
    """One sub-chunk of one block of ``heads`` heads that share a B and a C: ``x``
    [chunk, heads x p], ``dt`` and ``run`` (the sub-chunk's running sum of ``dt A``)
    [heads, chunk] float32, ``b``, ``c`` [chunk, n]. The state, ``[n, heads x p]``
    float32 (a head's ``[p, n]`` transposed, the heads side by side), stays in
    ``state`` (scratch) from one sub-chunk to the next, ``state0`` at the first. Writes
    ``y`` [chunk, heads x p] float32, the state before the sub-chunk (``between``, where
    the caller keeps it) and the one after the last (``last``). The C.B pairs are made
    once for the block's heads and the state is read out for all of them in one
    product; what a token has of its head alone (``dt``, the decays) is spread over the
    head's lanes (:func:`_scan_spread`), so that everything ``[chunk, heads x p]`` is
    whole vectors; the masked decay and the weights ``[chunk, chunk]`` a head are
    made, used and dropped here."""
    from jax.experimental import pallas as pl

    between, state = rest if len(rest) == 2 else (None, *rest)
    i, f32, (chunk, width) = pl.program_id(2), jnp.float32, x.shape
    highest = jax.lax.Precision.HIGHEST

    @pl.when(i == 0)
    def _():
        state[...] = state0[...]

    before = state[...]
    if between is not None:
        between[...] = before
    bq, cq = b[...].astype(dtype), c[...].astype(dtype)
    pair = jax.lax.dot_general(cq, bq, _NT, preferred_element_type=f32)      # [q, s]
    read = jnp.dot(cq.astype(f32), before, precision=highest, preferred_element_type=f32)
    mine = _scan_head_lanes(heads, width)
    run_col = run[...].T                                                     # [chunk, heads]
    step, ran = _scan_spread(dt[...], mine), _scan_spread(run[...], mine)    # [chunk, width]
    fed, end = step * x[...].astype(f32), ran[chunk - 1:]                    # [chunk, width], [1, width]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    from_state = jnp.exp(ran) * read
    for lanes, tile in _scan_tiles(heads, width):
        out = from_state[:, lanes]
        for h in tile:
            # masked before the exponential: a later token's difference is positive
            weight = pair * jnp.exp(jnp.where(causal, run_col[:, h:h + 1] - run[h:h + 1, :], -jnp.inf))
            theirs = fed[:, lanes] * mine[h:h + 1, lanes]
            out = out + jnp.dot(
                weight.astype(dtype), theirs.astype(dtype), preferred_element_type=f32)
        y[:, lanes] = out
    state[...] = jnp.exp(end) * before + jax.lax.dot_general(
        bq, (fed * jnp.exp(end - ran)).astype(dtype), _TN, preferred_element_type=f32)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last[...] = state[...]


def _ssm_scan_bwd_kernel(x, dt, run, b, c, between, dy, dlast,
                         dx, ddt, drun, db, dc, dstate0, dstate, *, heads, dtype):
    """:func:`_ssm_scan_fwd_kernel`'s sub-chunk differentiated, the sub-chunks walked
    from the last to the first: ``between`` is the state before this one, ``dstate``
    (scratch) the gradient of the state after it (``dlast`` at the first step taken),
    ``dy`` [chunk, heads x p] float32. The sub-chunk's pairs, decays and weights are
    rebuilt here, both ways up (``[q, s]`` and ``[s, q]``, so that no ``[chunk, chunk]``
    is transposed and what they give ``run`` is summed down their rows, a head a row as
    it is written), and dropped. Writes ``dx``, the gradients of ``dt`` and ``run``
    [heads, chunk], ``db`` and ``dc`` [chunk, n] summed over the block's heads, and at
    the last step the gradient of the state the call began from. Operands as the
    forward's: ``dtype`` with float32 sums, the read-out's three products float32 at
    the highest precision."""
    from jax.experimental import pallas as pl

    i, f32, (chunk, width) = pl.program_id(2), jnp.float32, x.shape
    p, highest = width // heads, jax.lax.Precision.HIGHEST
    mine = _scan_head_lanes(heads, width)

    def a_head_a_row(v):            # [chunk, width] -> [heads, chunk]: a head's lanes summed
        return jnp.concatenate(
            [v[:, h * p:(h + 1) * p].sum(axis=1, keepdims=True) for h in range(heads)], axis=1).T

    @pl.when(i == 0)
    def _():
        dstate[...] = dlast[...]

    before, after = between[...], dstate[...]                                # [n, width]
    bq, cq = b[...].astype(dtype), c[...].astype(dtype)
    pair = jax.lax.dot_general(cq, bq, _NT, preferred_element_type=f32)      # [q, s]
    pair_t = jax.lax.dot_general(bq, cq, _NT, preferred_element_type=f32)    # [s, q]
    read = jnp.dot(cq.astype(f32), before, precision=highest, preferred_element_type=f32)
    run_col = run[...].T
    step, ran = _scan_spread(dt[...], mine), _scan_spread(run[...], mine)    # [chunk, width]
    xf, g = x[...].astype(f32), dy[...]
    fed, end = step * xf, ran[chunk - 1:]
    fedb = fed.astype(dtype)
    to_end, kept = jnp.exp(end - ran), jnp.exp(end)
    fed_to_end, read_grad = fed * to_end, jnp.exp(ran) * g
    # the gradient of what each token fed the state, ``fed x to_end``
    from_state = jnp.dot(bq, after.astype(dtype), preferred_element_type=f32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dpair, dpair_t = jnp.zeros((chunk, chunk), f32), jnp.zeros((chunk, chunk), f32)
    inside, dfed = [], []
    for lanes, tile in _scan_tiles(heads, width):
        into = from_state[:, lanes] * to_end[:, lanes]
        for h in tile:
            col, row = run_col[:, h:h + 1], run[h:h + 1, :]
            decay = jnp.exp(jnp.where(rows >= cols, col - row, -jnp.inf))       # [q, s]
            decay_t = jnp.exp(jnp.where(rows <= cols, row - col, -jnp.inf))     # [s, q]
            gb = (g[:, lanes] * mine[h:h + 1, lanes]).astype(dtype)
            dweight = jax.lax.dot_general(gb, fedb[:, lanes], _NT, preferred_element_type=f32)
            dweight_t = jax.lax.dot_general(fedb[:, lanes], gb, _NT, preferred_element_type=f32)
            weight_t = pair_t * decay_t
            into = into + jnp.dot(weight_t.astype(dtype), gb, preferred_element_type=f32)
            # what the decays give ``run``: + at the later token, - at the earlier one
            inside.append(
                (dweight_t * weight_t - dweight * (pair * decay)).sum(axis=0, keepdims=True))
            dpair, dpair_t = dpair + dweight * decay, dpair_t + dweight_t * decay_t
        dfed.append(into)
    dfed = jnp.concatenate(dfed, axis=1)
    dx[...] = (dfed * step).astype(dx.dtype)
    ddt[...] = a_head_a_row(dfed * xf)
    moved = from_state * fed_to_end
    # ``run`` at the sub-chunk's end decays the old state and weighs what was fed
    at_end = (mine * (
        moved.sum(axis=0, keepdims=True) + kept * (after * before).sum(axis=0, keepdims=True)
    )).sum(axis=1, keepdims=True)
    is_end = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 1) == chunk - 1
    drun[...] = (
        jnp.concatenate(inside, axis=0) + a_head_a_row(read_grad * read - moved)
        + jnp.where(is_end, at_end, 0.0))
    dc[...] = (
        jnp.dot(dpair.astype(dtype), bq, preferred_element_type=f32)
        + jax.lax.dot_general(read_grad, before, _NT, precision=highest, preferred_element_type=f32)
    ).astype(dc.dtype)
    db[...] = (
        jnp.dot(dpair_t.astype(dtype), cq, preferred_element_type=f32)
        + jax.lax.dot_general(
            fed_to_end.astype(dtype), after.astype(dtype), _NT, preferred_element_type=f32)
    ).astype(db.dtype)
    dstate[...] = kept * after + jax.lax.dot_general(
        cq.astype(f32), read_grad, _TN, precision=highest, preferred_element_type=f32)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dstate0[...] = dstate[...]


def _ssm_scan_call(state, x, dt, run, b, c, grads=None, *, chunk, dtype, keep=False,
                   interpret=False):
    """The scan's forward kernel (``y``, the last state and, with ``keep``, the states
    before each sub-chunk) or, given ``grads`` (those states, ``dy`` and the last
    state's gradient, which then stands where ``state`` does), its backward kernel (the
    gradients of ``x``, ``dt``, ``run``, ``b``, ``c`` and the first state), over the
    kernels' own layout: a state ``[lanes, blocks, n, heads x p]`` float32, ``x``
    [lanes, t, blocks x heads x p], ``dt``, ``run`` [lanes, blocks, heads, t] float32,
    ``b``, ``c`` [lanes, t, groups x n], a block of heads inside one group. Grid
    ``(lanes, blocks, sub-chunks)``, the last axis in turn (the backward's from the last
    sub-chunk to the first)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, blocks, n, width = state.shape
    heads, t = dt.shape[2:]
    nc, per_group, f32 = t // chunk, blocks * n // b.shape[-1], jnp.float32

    def at(i):
        return i if grads is None else nc - 1 - i

    tokens = pl.BlockSpec((None, chunk, width), lambda l, h, i: (l, at(i), h))
    steps = pl.BlockSpec((None, None, heads, chunk), lambda l, h, i: (l, h, 0, at(i)))
    group = pl.BlockSpec((None, chunk, n), lambda l, h, i: (l, at(i), h // per_group))
    whole = pl.BlockSpec((None, None, n, width), lambda l, h, i: (l, h, 0, 0))
    each = pl.BlockSpec((None, None, None, n, width), lambda l, h, i: (l, h, at(i), 0, 0))
    options = dict(
        grid=(lanes, blocks, nc),
        scratch_shapes=[pltpu.VMEM((n, width), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    shaped = jax.ShapeDtypeStruct
    if grads is None:
        return pl.pallas_call(
            functools.partial(_ssm_scan_fwd_kernel, heads=heads, dtype=dtype),
            name="ssm_scan_fwd",
            in_specs=[whole, tokens, steps, steps, group, group],
            out_specs=[tokens, whole] + [each] * keep,
            out_shape=[shaped(x.shape, f32), shaped(state.shape, f32)]
            + [shaped((lanes, blocks, nc, n, width), f32)] * keep,
            **options)(state, x, dt, run, b, c)
    # a block's share of its group's gradient: float32 where a group is several blocks,
    # which the caller sums
    block = pl.BlockSpec((None, chunk, n), lambda l, h, i: (l, at(i), h))
    shared = shaped((lanes, t, blocks * n), b.dtype if per_group == 1 else f32)
    return pl.pallas_call(
        functools.partial(_ssm_scan_bwd_kernel, heads=heads, dtype=dtype),
        name="ssm_scan_bwd",
        in_specs=[tokens, steps, steps, group, group, each, tokens, whole],
        out_specs=[tokens, steps, steps, block, block, whole],
        out_shape=[shaped(x.shape, x.dtype), shaped(dt.shape, f32), shaped(run.shape, f32),
                   shared, shared, shaped(state.shape, f32)],
        **options)(x, dt, run, b, c, *grads, state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssm_scan_blocks(state, x, dt, run, b, c, chunk, dtype, interpret):
    """``y`` and the last state (:func:`_ssm_scan_call`'s layout), differentiable in
    all six: the kernel pair. What the backward keeps is the operands and the states
    before each sub-chunk, ``[lanes, blocks, sub-chunks, n, heads x p]`` float32."""
    return tuple(_ssm_scan_call(
        state, x, dt, run, b, c, chunk=chunk, dtype=dtype, interpret=interpret))


def _ssm_scan_blocks_fwd(state, x, dt, run, b, c, chunk, dtype, interpret):
    y, last, between = _ssm_scan_call(
        state, x, dt, run, b, c, chunk=chunk, dtype=dtype, keep=True, interpret=interpret)
    return (y, last), (x, dt, run, b, c, between)


def _ssm_scan_blocks_bwd(chunk, dtype, interpret, kept, grads):
    x, dt, run, b, c, between = kept
    dy, dlast = grads
    dx, ddt, drun, db, dc, dstate = _ssm_scan_call(
        dlast, x, dt, run, b, c, (between, dy), chunk=chunk, dtype=dtype, interpret=interpret)
    if db.shape != b.shape:         # a group in several blocks: [lanes, t, groups x blocks x n]
        n = dlast.shape[2]
        db, dc = (
            v.reshape(v.shape[:2] + (b.shape[-1] // n, -1, n)).sum(3).reshape(b.shape).astype(b.dtype)
            for v in (db, dc))
    return dstate, dx, ddt, drun, db, dc


_ssm_scan_blocks.defvjp(_ssm_scan_blocks_fwd, _ssm_scan_blocks_bwd)


def _scan_blocks_of(state, heads):
    """A state ``[..., all heads, p, n]`` as the scan's kernels hold it: ``[..., blocks,
    n, heads x p]``, a block's heads side by side, each transposed."""
    *lead, all_heads, p, n = state.shape
    blocked = state.reshape(*lead, all_heads // heads, heads, p, n)
    return jnp.moveaxis(blocked, -1, -3).reshape(*lead, all_heads // heads, n, heads * p)


def _scan_heads_of(blocks, heads):
    """:func:`_scan_blocks_of` back: ``[..., blocks, n, heads x p]`` -> ``[..., all
    heads, p, n]``."""
    *lead, count, n, width = blocks.shape
    split = jnp.moveaxis(blocks.reshape(*lead, count, n, heads, width // heads), -3, -1)
    return split.reshape(*lead, count * heads, width // heads, n)


def _ssm_scan_operands(state, x, dt, a, b, c, chunk, heads):
    """What :func:`_ssm_scan_call` takes, from :func:`ssm_scan`'s arguments: the running
    sum of ``dt A`` inside each sub-chunk, ``dt`` and it a head a row, the state in
    blocks, the rest flat."""
    lanes, t, all_heads, _ = x.shape
    run = jnp.cumsum((dt * a).reshape(lanes, t // chunk, chunk, all_heads), axis=2)

    def a_head_a_row(v):            # [lanes, t, all heads] -> [lanes, blocks, heads, t]
        return v.reshape(lanes, t, -1, heads).transpose(0, 2, 3, 1)

    return (
        _scan_blocks_of(state, heads), x.reshape(lanes, t, -1), a_head_a_row(dt),
        a_head_a_row(run), b.reshape(lanes, t, -1), c.reshape(lanes, t, -1))


def ssm_scan(state, x, dt, a, b, c, chunk: int, dtype, *, heads: int = SSM_SCAN_HEADS,
             interpret: bool = False):
    """:func:`ssm_chunked` for a train step: the same recurrence in the same sub-chunks
    at the same precisions (``b``, ``c`` with their group axis, ``[lanes, t, groups,
    n]``), returning ``(y, last state)``, differentiable in ``state``, ``x``, ``dt``,
    ``a``, ``b`` and ``c``. On the TPU (``interpret`` reaches it elsewhere) it is a
    kernel pair under one ``jax.custom_vjp``: grid ``(lanes, blocks of heads,
    sub-chunks)``, a block the ``heads`` heads (all of a group's where they are no
    more) that share a B and a C, so that their ``C B^T`` is made once; the state
    stays in VMEM from one sub-chunk to the next, and a sub-chunk's ``[chunk, chunk]``
    pairs, decays and weights are made, used and dropped there, forward and backward:
    none is ever an array in HBM. The backward keeps the operands and the state before
    each sub-chunk (``heads x p x n`` float32 a sub-chunk and lane), walks the
    sub-chunks from the last to the first with the state's gradient in VMEM and
    rebuilds a sub-chunk's quantities from those. The running sum of ``dt A`` inside a
    sub-chunk and the kernels' layouts of ``dt`` (a head a row) and of the state are
    made outside them, on arrays of ``lanes x t x heads``, and ``jax.grad``
    differentiates that as it finds it (``a``'s gradient is summed there). Off the TPU
    it is :func:`ssm_chunked` under plain autodiff."""
    if not (backend.on_tpu() or interpret):
        return ssm_chunked(state, x, dt, a, b, c, chunk, dtype)[:2]
    heads = min(heads, x.shape[2] // b.shape[2])
    y, last = _ssm_scan_blocks(
        *_ssm_scan_operands(state, x, dt, a, b, c, chunk, heads), chunk, dtype, interpret)
    return y.reshape(x.shape), _scan_heads_of(last, heads)


SSM_STAGE_ROWS = 512    # tokens a grid step of the two pointwise stages' kernels holds,
SSM_STAGE_SUB = 32      # walked this many at a time: a chain of float32 values stays in registers


def _sublanes_summed(v):
    """``[rows, width]`` -> ``[8, width]``: the rows added eight apart, so whole vectors
    are added and no row of a vector to another; a grid step sums the eight once."""
    return sum(v[at:at + 8] for at in range(0, v.shape[0], 8))


def _silu_and_slope(v):
    """``silu(v)`` and its derivative from one logistic, float32."""
    sig = jax.nn.sigmoid(v)
    return v * sig, sig * (1.0 + v * (1.0 - sig))


def _conv_taps(taps, last, now):
    """The ``k`` shifted views of ``now`` [sub, width] behind the 8 rows before it
    (``last``) and their sum under ``taps`` (a list of ``[1, width]``): row ``t`` of
    view ``m`` is row ``t - (k - 1) + m``."""
    k, sub = len(taps), now.shape[0]
    seen = jnp.concatenate([last, now], axis=0)
    views = [seen[8 - (k - 1) + m:8 - (k - 1) + m + sub] for m in range(k)]
    return views, sum(taps[m] * views[m] for m in range(k))


def _ssm_conv_fwd_kernel(taps, bias, before, xbc, x, b, c, *, nx, nb, sub):
    """One block of rows of one block of channels of the causal depthwise convolution,
    its bias and ``silu``: ``xbc`` [rows, width], ``before`` the 16 rows before it (zeros
    at a sequence's start: the grid's middle axis counts one lane's blocks), ``taps`` [k,
    width] and ``bias`` [1, width] float32. The rows are walked ``sub`` at a time with the
    eight before them carried: the shifted products and the activation are float32 values
    that never leave VMEM. The grid's last axis walks ``x``'s blocks of channels, then
    ``b``'s, then ``c``'s; a block is written to the part it belongs to, and the other
    parts' blocks stay where they were (their index does not move)."""
    from jax.experimental import pallas as pl

    i, j, f32 = pl.program_id(1), pl.program_id(2), jnp.float32
    rows, weights, shift = xbc.shape[0], [taps[m:m + 1, :] for m in range(taps.shape[0])], bias[...]

    def write(out):
        def some(r, last):
            at = pl.ds(pl.multiple_of(r * sub, sub), sub)
            now = xbc[at, :].astype(f32)
            out[at, :] = jax.nn.silu(shift + _conv_taps(weights, last, now)[1]).astype(out.dtype)
            return now[sub - 8:]

        jax.lax.fori_loop(
            0, rows // sub, some, jnp.where(i == 0, 0.0, before[8:, :].astype(f32)))

    pl.when(j < nx)(lambda: write(x))
    pl.when((j >= nx) & (j < nx + nb))(lambda: write(b))
    pl.when(j >= nx + nb)(lambda: write(c))


def _ssm_conv_bwd_kernel(taps, bias, before, xbc, dx, db, dc, dxbc, sums, ahead, *, nx, nb,
                         sub):
    """:func:`_ssm_conv_fwd_kernel` differentiated, a lane's blocks of rows walked from
    the last to the first and a block's rows from its last ``sub`` to its first: the
    pre-activation is rebuilt from ``xbc`` and the rows before it, ``silu'`` times the
    part's gradient (``dx``, ``db`` or ``dc``, by the block of channels) is the
    pre-activation's, and ``dxbc`` is the taps' transposed sum of it over the rows
    **after** a row: the first eight rows' of the later rows are carried, inside a block
    by the loop and from a block to the one before it in ``ahead`` [blocks of channels,
    8, width] (scratch; zeros at a sequence's end). ``sums`` [blocks of channels, 8,
    width] float32 stays resident over the whole grid: rows ``0 .. k - 1`` the taps'
    gradients and row ``k`` the bias's, summed over lanes and rows."""
    from jax.experimental import pallas as pl

    l, i, j, f32 = pl.program_id(0), pl.program_id(1), pl.program_id(2), jnp.float32
    rows, k = xbc.shape[0], taps.shape[0]
    steps = rows // sub
    weights, shift = [taps[m:m + 1, :] for m in range(k)], bias[...]
    start_of_lane = i == pl.num_programs(1) - 1
    first = jnp.where(start_of_lane, 0.0, before[8:, :].astype(f32))

    @pl.when((l == 0) & (i == 0))
    def _():
        sums[j] = jnp.zeros(sums.shape[1:], f32)

    @pl.when(i == 0)
    def _():
        ahead[j] = jnp.zeros(ahead.shape[1:], f32)

    def some(q, carried):
        later, *totals = carried
        r = steps - 1 - q
        at = pl.ds(pl.multiple_of(r * sub, sub), sub)
        now = xbc[at, :].astype(f32)
        behind = xbc[pl.ds(pl.multiple_of(jnp.maximum(r * sub - 16, 0), 16), 16), :]
        last = jnp.where(r == 0, first, behind[8:, :].astype(f32))
        views, mixed = _conv_taps(weights, last, now)
        grad = jnp.where(j < nx, dx[at, :], jnp.where(j < nx + nb, db[at, :], dc[at, :]))
        dpre = grad.astype(f32) * _silu_and_slope(shift + mixed)[1]
        after = jnp.concatenate([dpre, later], axis=0)
        dxbc[at, :] = sum(
            weights[m] * after[k - 1 - m:k - 1 - m + sub] for m in range(k)).astype(dxbc.dtype)
        totals = [total + _sublanes_summed(dpre * view) for total, view in zip(totals, views)] + [
            totals[k] + _sublanes_summed(dpre)]
        return (dpre[:8], *totals)

    zeros = jnp.zeros(sums.shape[1:], f32)
    later, *totals = jax.lax.fori_loop(0, steps, some, (ahead[j], *[zeros] * (k + 1)))
    ahead[j] = later
    for m, total in enumerate(totals):
        sums[j, m:m + 1, :] += total.sum(axis=0, keepdims=True)


def _ssm_conv_call(within, taps, bias, grads=None, *, first, inner, rows, sub, interpret):
    """The convolution's forward kernel (the parts ``x`` [lanes, t, inner] and ``b``, ``c``
    [lanes, t, (channels - inner) / 2] of ``silu(conv(xbc) + bias)``) or, given ``grads``
    (the three parts' gradients), its backward kernel (``dxbc``, the taps' gradient and
    the bias's); ``xbc`` is columns ``first .. first + channels`` of ``within`` and is read
    there. Grid ``(lanes, blocks of rows, blocks of channels)``, all in turn."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (lanes, t, _), (k, channels) = within.shape, taps.shape
    part = (channels - inner) // 2
    width = math.gcd(inner, part, first, 512)
    nx, nb, blocks, first = inner // width, part // width, t // rows, first // width
    columns = nx + 2 * nb   # blocks of channels: x's, then b's, then c's
    assert t % rows == 0 and rows % sub == 0 and sub % 16 == 0 and k <= 8, (t, rows, sub, k)

    def at(i):
        return i if grads is None else blocks - 1 - i

    def parts_spec(first, count):   # a part's block, held where it was outside its own blocks
        return pl.BlockSpec(
            (None, rows, width), lambda l, i, j: (l, at(i), jnp.clip(j - first, 0, count - 1)))

    weights = pl.BlockSpec((k, width), lambda l, i, j: (0, j))
    shift = pl.BlockSpec((1, width), lambda l, i, j: (0, j))
    before = pl.BlockSpec(
        (None, 16, width), lambda l, i, j: (l, jnp.maximum(at(i) * (rows // 16) - 1, 0), first + j))
    source = pl.BlockSpec((None, rows, width), lambda l, i, j: (l, at(i), first + j))
    whole = pl.BlockSpec((None, rows, width), lambda l, i, j: (l, at(i), j))
    parts = [parts_spec(0, nx), parts_spec(nx, nb), parts_spec(nx + nb, nb)]
    options = dict(
        grid=(lanes, blocks, columns),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret)
    shaped = jax.ShapeDtypeStruct
    if grads is None:
        return pl.pallas_call(
            functools.partial(_ssm_conv_fwd_kernel, nx=nx, nb=nb, sub=sub), name="ssm_conv_fwd",
            in_specs=[weights, shift, before, source], out_specs=parts,
            out_shape=[shaped((lanes, t, inner), within.dtype)] + [shaped((lanes, t, part), within.dtype)] * 2,
            **options)(taps, bias, within, within)
    summed = pl.BlockSpec((columns, 8, width), lambda l, i, j: (0, 0, 0))
    dxbc, sums = pl.pallas_call(
        functools.partial(_ssm_conv_bwd_kernel, nx=nx, nb=nb, sub=sub), name="ssm_conv_bwd",
        in_specs=[weights, shift, before, source] + parts, out_specs=[whole, summed],
        out_shape=[shaped((lanes, t, channels), within.dtype), shaped((columns, 8, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((columns, 8, width), jnp.float32)],
        **options)(taps, bias, within, within, *grads)
    by_tap = sums.transpose(1, 0, 2).reshape(8, channels)
    return dxbc, by_tap[:k], by_tap[k:k + 1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ssm_conv_parts(xbc, within, taps, bias, first, inner, rows, sub, interpret):
    """``silu(conv(xbc) + bias)`` in its three parts: the kernel pair. ``xbc`` is read
    where it lies, as columns of ``within`` (no gradient goes that way: the caller hands
    it over under ``stop_gradient``), and its gradient is ``xbc``'s, which is otherwise
    not looked at: a slice nobody reads is never copied out. The backward keeps
    ``within``, the taps and the bias and rebuilds the pre-activation from them."""
    del xbc
    return tuple(_ssm_conv_call(
        within, taps, bias, first=first, inner=inner, rows=rows, sub=sub, interpret=interpret))


def _ssm_conv_parts_fwd(xbc, within, taps, bias, first, inner, rows, sub, interpret):
    parts = _ssm_conv_parts(xbc, within, taps, bias, first, inner, rows, sub, interpret)
    return parts, (within, taps, bias)


def _ssm_conv_parts_bwd(first, inner, rows, sub, interpret, kept, grads):
    dxbc, dtaps, dbias = _ssm_conv_call(
        *kept, grads, first=first, inner=inner, rows=rows, sub=sub, interpret=interpret)
    return dxbc, jnp.zeros_like(kept[0]), dtaps, dbias


_ssm_conv_parts.defvjp(_ssm_conv_parts_fwd, _ssm_conv_parts_bwd)


def ssm_conv(xbc, taps, bias, inner: int, *, within=None, rows: int = SSM_STAGE_ROWS,
             sub: int = SSM_STAGE_SUB, interpret: bool = False):
    """A trained Mamba-2 mixer's head: the causal depthwise convolution of ``xbc``
    [lanes, t, channels] (zeros before a sequence) under ``taps`` [k, channels] with
    ``bias`` [channels], both float32, then ``silu``, everything float32 and the result
    cast to ``xbc``'s dtype, in the three parts the scan takes: ``x`` [lanes, t, inner]
    and ``b``, ``c`` [lanes, t, (channels - inner) / 2]. Differentiable in all three
    arguments. On the TPU (``interpret`` reaches it elsewhere) a kernel pair under one
    ``jax.custom_vjp``: ``xbc`` is read once and each part written once where the scan
    reads it, forward; ``xbc`` and the parts' gradients read once and ``dxbc`` written
    once, backward, with the taps' and the bias's gradients summed in float32 in a block
    that stays in VMEM; no float32 array a token and channel wide exists in HBM. A caller
    that cut ``xbc`` out of a wider array says so, ``within=(array, first column)``: the
    kernels then read the columns where they lie (the first a whole number of their
    blocks of channels), and the cut is never copied out. Off the TPU the same lines in
    ``jax.numpy`` under plain autodiff."""
    t, k = xbc.shape[1], taps.shape[0]
    if not (backend.on_tpu() or interpret):
        f32 = jnp.float32
        seen = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        mixed = bias + sum(taps[j] * seen[:, j:j + t].astype(f32) for j in range(k))
        mixed = jax.nn.silu(mixed).astype(xbc.dtype)
        return (mixed[..., :inner], *jnp.split(mixed[..., inner:], 2, axis=-1))
    rows, (source, first) = min(rows, t), within or (xbc, 0)
    return _ssm_conv_parts(
        xbc, jax.lax.stop_gradient(source), taps, bias[None], first, inner, rows, min(sub, rows),
        interpret)


def _ssm_gate_norm_fwd_kernel(skip, scale, y, x, z, out, *, eps, sub):
    """One block of rows of one group's channels: ``(y + skip x) silu(z)``, float32,
    normed over the group's channels and scaled; ``y`` [rows, width] float32, ``x`` and
    ``z`` ``dtype``, ``skip`` (``D``, a head's over its channels) and ``scale`` [1, width]
    float32. Walked ``sub`` rows at a time."""
    from jax.experimental import pallas as pl

    f32, d, w = jnp.float32, skip[...], scale[...]

    def some(r, _):
        at = pl.ds(pl.multiple_of(r * sub, sub), sub)
        gated = (y[at, :] + d * x[at, :].astype(f32)) * jax.nn.silu(z[at, :].astype(f32))
        normed = gated * jax.lax.rsqrt((gated * gated).mean(-1, keepdims=True) + eps) * w
        out[at, :] = normed.astype(out.dtype)
        return _

    jax.lax.fori_loop(0, y.shape[0] // sub, some, None)


def _ssm_gate_norm_bwd_kernel(skip, scale, y, x, z, grad, dy, dx, dz, sums, *, eps, sub):
    """:func:`_ssm_gate_norm_fwd_kernel` differentiated: the forward's values are rebuilt
    from the same three blocks, ``dy`` (float32), ``dx`` and ``dz`` written once, and
    ``sums`` [8, width] float32 stays resident over the group's lanes and rows: row 0
    ``skip``'s gradient, row 1 ``scale``'s."""
    from jax.experimental import pallas as pl

    f32, d, w = jnp.float32, skip[...], scale[...]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        sums[...] = jnp.zeros(sums.shape, f32)

    def some(r, totals):
        at = pl.ds(pl.multiple_of(r * sub, sub), sub)
        xf, zf, g = x[at, :].astype(f32), z[at, :].astype(f32), grad[at, :].astype(f32)
        gate, slope = _silu_and_slope(zf)
        summed = y[at, :] + d * xf
        gated = summed * gate
        inverse = jax.lax.rsqrt((gated * gated).mean(-1, keepdims=True) + eps)
        normed = gated * inverse
        dnormed = g * w
        dgated = inverse * (dnormed - normed * (dnormed * normed).mean(-1, keepdims=True))
        dsummed = dgated * gate
        dy[at, :] = dsummed
        dx[at, :] = (dsummed * d).astype(dx.dtype)
        dz[at, :] = (dgated * summed * slope).astype(dz.dtype)
        return (totals[0] + _sublanes_summed(dsummed * xf), totals[1] + _sublanes_summed(g * normed))

    zeros = jnp.zeros(sums.shape, f32)
    for row, total in enumerate(jax.lax.fori_loop(0, y.shape[0] // sub, some, (zeros, zeros))):
        sums[row:row + 1, :] += total.sum(axis=0, keepdims=True)


def _ssm_gate_norm_call(y, x, within, skip, scale, grad=None, *, first, groups, eps, rows, sub,
                        interpret):
    """The tail's forward kernel (the normed, gated ``[lanes, t, inner]`` in ``x``'s
    dtype) or, given ``grad`` (its gradient), its backward kernel (the gradients of the
    five); ``z`` is columns ``first .. first + inner`` of ``within`` and is read there.
    Grid ``(groups, lanes, blocks of rows)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, t, inner = y.shape
    width = inner // groups
    assert t % rows == 0 and rows % sub == 0 and sub % 8 == 0 and first % width == 0, (t, rows, sub)
    tokens = pl.BlockSpec((None, rows, width), lambda g, l, i: (l, i, g))
    gates = pl.BlockSpec((None, rows, width), lambda g, l, i: (l, i, first // width + g))
    channels = pl.BlockSpec((1, width), lambda g, l, i: (0, g))
    options = dict(grid=(groups, lanes, t // rows), interpret=interpret)
    shaped = jax.ShapeDtypeStruct
    if grad is None:
        return pl.pallas_call(
            functools.partial(_ssm_gate_norm_fwd_kernel, eps=eps, sub=sub), name="ssm_gate_norm_fwd",
            in_specs=[channels, channels, tokens, tokens, gates], out_specs=tokens,
            out_shape=shaped(x.shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            **options)(skip, scale, y, x, within)
    dy, dx, dz, sums = pl.pallas_call(
        functools.partial(_ssm_gate_norm_bwd_kernel, eps=eps, sub=sub), name="ssm_gate_norm_bwd",
        in_specs=[channels, channels, tokens, tokens, gates, tokens],
        out_specs=[tokens, tokens, tokens, pl.BlockSpec((8, width), lambda g, l, i: (0, g))],
        out_shape=[shaped(y.shape, y.dtype), shaped(x.shape, x.dtype), shaped(x.shape, within.dtype),
                   shaped((8, inner), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        **options)(skip, scale, y, x, within, grad)
    return dy, dx, dz, sums[0:1], sums[1:2]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _ssm_gate_normed(y, x, z, within, skip, scale, first, groups, eps, rows, sub, interpret):
    """The mixer's tail: the kernel pair. ``z`` is read where it lies, as columns of
    ``within``, and its gradient is ``z``'s (:func:`_ssm_conv_parts` says why). The
    backward keeps the operands."""
    del z
    return _ssm_gate_norm_call(
        y, x, within, skip, scale, first=first, groups=groups, eps=eps, rows=rows, sub=sub,
        interpret=interpret)


def _ssm_gate_normed_fwd(y, x, z, within, skip, scale, first, groups, eps, rows, sub, interpret):
    out = _ssm_gate_normed(y, x, z, within, skip, scale, first, groups, eps, rows, sub, interpret)
    return out, (y, x, within, skip, scale)


def _ssm_gate_normed_bwd(first, groups, eps, rows, sub, interpret, kept, grad):
    dy, dx, dz, dskip, dscale = _ssm_gate_norm_call(
        *kept, grad, first=first, groups=groups, eps=eps, rows=rows, sub=sub, interpret=interpret)
    return dy, dx, dz, jnp.zeros_like(kept[2]), dskip, dscale


_ssm_gate_normed.defvjp(_ssm_gate_normed_fwd, _ssm_gate_normed_bwd)


def ssm_gate_norm(y, x, z, skip, scale, groups: int, eps: float, *, within=None,
                  rows: int = SSM_STAGE_ROWS, sub: int = SSM_STAGE_SUB, interpret: bool = False):
    """A trained Mamba-2 mixer's tail: ``RMSNorm_group((y + skip x) silu(z)) scale`` for
    the scan's ``y`` [lanes, t, inner] float32, ``x`` and ``z`` [lanes, t, inner] (the
    result is in ``x``'s dtype), ``skip`` [heads] float32 (``D``: a head's over its
    ``inner / heads`` channels), ``scale`` [inner], the norm over each of the ``groups``
    groups' channels; float32 throughout. Differentiable in all five. On the TPU
    (``interpret`` reaches it elsewhere) a kernel pair under one ``jax.custom_vjp``, a
    grid step a block of rows of one group's channels: forward reads the three and
    writes the result once; backward reads the three and the result's gradient, rebuilds
    the forward's values in VMEM and writes ``dy`` (float32), ``dx`` and ``dz`` once,
    with ``skip``'s and ``scale``'s gradients summed in float32 in a block that stays in
    VMEM; ``within=(array, first column)`` says where ``z`` was cut from, as
    :func:`ssm_conv`'s does. Off the TPU the same lines in ``jax.numpy`` under plain
    autodiff."""
    lanes, t, inner = y.shape
    f32 = jnp.float32
    if not (backend.on_tpu() or interpret):
        heads = skip.shape[0]
        summed = y.reshape(lanes, t, heads, -1) + skip[:, None] * x.reshape(lanes, t, heads, -1).astype(f32)
        gated = summed.reshape(lanes, t, inner) * jax.nn.silu(z.astype(f32))
        # a norm a group: over the channels of the heads that share a B and a C
        normed = layers.rms_norm(gated.reshape(lanes, t, groups, -1), scale.reshape(groups, -1), eps)
        return normed.reshape(lanes, t, inner).astype(x.dtype)
    rows, (source, first) = min(rows, t), within or (z, 0)
    return _ssm_gate_normed(
        y, x, z, jax.lax.stop_gradient(source), jnp.repeat(skip, inner // skip.shape[0])[None],
        scale.astype(f32)[None], first, groups, eps, rows, min(sub, rows), interpret)


def make_extend_fn(cfg: GraniteMoeHybridConfig):
    """A jitted ``extend(params, tokens, lengths, k_cache, v_cache, ssm, conv,
    slots, snap_at, snap_slots)``: the contract of ``gpt.make_extend_fn`` over
    the attention layers' caches (``[cache_layers, lanes, cache, 1, kv_heads x
    head_dim]``), and the pool's state arenas themselves (``cfg.state_arrays``:
    ``[ssm_layers, state slots, ...]``; a caller that keeps them donates them)
    with each lane's slot in them (``slots`` [lanes]). Returns ``(logits,
    hidden, k rows, v rows, ssm, conv, counters)``: the arenas with, in each
    lane's slot, the states after its last real token; a call of more than one
    token a lane also writes the states after ``snap_at[lane]`` tokens (a whole
    number of sub-chunks, at least one: 0 reads as one) to slot
    ``snap_slots[lane]`` (0, nobody's, where none is to be kept). No other slot
    is touched. A lane of length 0 starts from zeros whatever its slot holds; a
    negative token id is padding and changes no state; a lane of padding alone
    points at slot 0. ``counters`` (``cfg.counters``) over real lanes and tokens.
    With ``table=`` [lanes, n] (a call of one token a lane, from an engine that reads
    the keyword off the signature: ``serve/llm.reads_pages``) ``k_cache`` and
    ``v_cache`` are the pool's block arenas themselves, ``[cache_layers, blocks,
    block, 1, kv_heads x head_dim]``, and an attention layer attends over the lanes'
    pages where they lie (:func:`layers.paged_attend`: on the chip
    ``ops/attention.paged_attention``).

    Scopes: ``extend.embed``; ``extend.ssm`` (projections, convolution, gate,
    norm) with ``extend.ssm.scan`` inside it (the recurrence alone, with the
    state's read and its write; a decode call's is the kernel ``ssm_step`` on
    the chip); ``extend.attention``; ``extend.mlp``, or with experts
    ``extend.moe.route`` (the norm and the router), ``extend.moe.experts`` and
    ``extend.moe.shared``; ``extend.logits`` (the last norm and the head, of the rows
    that are read: ``last=``, ``layers.read_rows``; every row without it)."""
    dtype, f32 = cfg.dtype, jnp.float32
    heads, p_dim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner, tail = cfg.ssm_inner, cfg.conv_width - 1
    groups = cfg.num_heads // cfg.kv_heads
    scale, res = float(cfg.attention_multiplier), cfg.residual_multiplier

    def _normed(x, p):
        return layers.rms_norm(x, p["scale"], cfg.norm_eps)

    # An arena is read and written one slot at a time, with a dynamic slice and
    # an in-place dynamic update: indexed with the slots (``arena[at, slots]``)
    # the TPU compiler first copies all of it (3.75 GB: ``serve/llm.py``
    # ``_paging_programs``; ``tests/test_chip_compile_serve.py`` holds ``extend`` to this).

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

    @jax.named_scope("extend.ssm")
    def _mamba(p, hidden, valid, fresh, slots, snap_at, snap_slots, ssm, tails, at):
        """``ssm`` holds every sequence's state of every Mamba layer
        (``[ssm_layers, state slots, heads, p, n]``): lane ``i``'s of layer ``at``
        is read from slot ``slots[i]`` and, after the lane's last real token,
        written there, and into slot ``snap_slots[i]`` (a chunk) the one after
        ``snap_at[i]`` tokens. ``tails`` are the lanes' own convolution inputs
        (``[ssm_layers, lanes, tail, conv_dim]``: a 1/80 of a state, taken from
        their slots before the layers and put back behind them): layer ``at``'s
        are read and the new ones written, and for a chunk the kept ones beside
        them. Returns the mixer's output, ``ssm`` and ``tails``."""
        b, tc = valid.shape
        conv = jnp.where(fresh[:, None, None], 0, _layer(tails[0], at)).astype(dtype)
        z = hidden @ p["in_z"]["kernel"].astype(dtype)
        xbc = hidden @ p["in_xbc"]["kernel"].astype(dtype)
        dt = jnp.dot(hidden, p["in_dt"]["kernel"].astype(dtype), preferred_element_type=f32)
        dt = jnp.where(valid[..., None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
        # the convolution over the lane's last inputs and this call's
        seen = jnp.concatenate([conv, xbc], axis=1)                 # [b, tail + tc, conv_dim]
        kernel = p["conv"]["kernel"].astype(f32)
        mixed = p["conv"]["bias"].astype(f32) + sum(
            kernel[k] * seen[:, k:k + tc].astype(f32) for k in range(cfg.conv_width))
        mixed = jax.nn.silu(mixed).astype(dtype)
        # what the next call's first tokens need: the inputs of the last real ones
        lane = jnp.arange(b)[:, None]
        after = jnp.arange(tail)[None, :]
        tails = tuple(
            _set_layer(stack, seen[lane, upto[:, None] + after], at)
            for stack, upto in zip(tails, (
                valid.sum(1, dtype=jnp.int32), jnp.maximum(snap_at, cfg.ssm_chunk))))
        x = mixed[..., :inner].reshape(b, tc, heads, p_dim)
        bm, cm = mixed[..., inner:inner + n], mixed[..., inner + n:]
        a = -jnp.exp(p["A_log"])
        with jax.named_scope("extend.ssm.scan"):
            # the state's read and its write are the recurrence's own traffic
            def before():
                return jnp.where(fresh[:, None, None, None], 0.0, _take(ssm, slots, at)[0])

            if tc == 1:
                step = (
                    x[:, 0].astype(f32), dt[:, 0], a, bm[:, 0].astype(f32), cm[:, 0].astype(f32))
                if backend.on_tpu():
                    y, ssm = ssm_step_slots(ssm, at, slots, valid[:, 0], fresh, *step)
                else:
                    y, state = ssm_step(before(), *step)
                    ssm = _put(ssm, slots, state[None], at)
                y = y[:, None]
            else:
                y, state, between = ssm_chunked(before(), x, dt, a, bm, cm, cfg.ssm_chunk, dtype)
                chunk = jnp.clip(snap_at // cfg.ssm_chunk - 1, 0, tc // cfg.ssm_chunk - 1)
                ssm = _put(ssm, snap_slots, jnp.take_along_axis(
                    between, chunk[None, :, None, None, None], axis=0), at)
                ssm = _put(ssm, slots, state[None], at)
        y = y + p["D"][:, None] * x.astype(f32)
        y = y.reshape(b, tc, inner) * jax.nn.silu(z.astype(f32))
        out = jnp.dot(
            _normed(y, p["norm"]).astype(dtype), p["out"]["kernel"].astype(dtype),
            preferred_element_type=f32)
        return out, ssm, tails

    @jax.named_scope("extend.attention")
    def _attend(p, hidden, positions, visible, live, kc, vc, paged=None):
        """``kc``, ``vc`` the layer's slab of the padded caches; or, with ``paged`` (the
        layer's index and the lanes' block table), the pool's arenas themselves."""
        b, tc = positions.shape
        q = (hidden @ p["q"]["kernel"].astype(dtype)).reshape(
            b, tc, cfg.kv_heads, groups, cfg.head_dim)
        k = (hidden @ p["k"]["kernel"].astype(dtype))[:, :, None]   # one row for all K/V heads
        v = (hidden @ p["v"]["kernel"].astype(dtype))[:, :, None]
        if paged is not None:
            out = layers.paged_attend(q, k, v, kc, vc, *paged, positions, visible, scale)
        else:
            cap = kc.shape[1]
            lane = jnp.arange(b)[:, None]
            kc = layers.write_rows(kc, lane, positions, k).reshape(b, cap, cfg.kv_heads, -1)
            vc = layers.write_rows(vc, lane, positions, v).reshape(b, cap, cfg.kv_heads, -1)

            def attend_block(qb, mask):
                return layers.plain_attend(qb, kc, vc, mask, scale)

            if tc > 1 and backend.on_tpu():
                out = attention.masked_attention(q, kc, vc, visible, live, scale=scale)
            else:
                out = (
                    attend_block(q, visible) if tc == 1
                    else layers.by_query_block(attend_block, q, visible))
        out = jnp.dot(
            out.reshape(b, tc, -1), p["o"]["kernel"].astype(dtype), preferred_element_type=f32)
        return out, (k, v)

    @jax.named_scope("extend.mlp")
    def _mlp(x, p):
        return layers.gated_mlp(_normed(x, p["ln"]).astype(dtype), p["wi"], p["wo"])

    def _routed(x, p, held, period, valid):
        """The second half with experts: the shared MLP and this chip's part of
        the routed one, of ``held`` (a layer of a period's stack) the ``period``-th
        in place. Returns it and what the expert layer counted."""
        b, tc, d = x.shape
        with jax.named_scope("extend.moe.route"):
            flat = _normed(x, p["ln"]).reshape(b * tc, d)
            weights, chosen = moe.softmax_top_k(flat, p["router"], cfg.experts_per_token)
            flat = flat.astype(dtype)
        with jax.named_scope("extend.moe.experts"):
            routed, counted = moe.held_experts_ffn(
                flat, weights, chosen, valid.reshape(b * tc), held["wi"], held["wo"],
                cfg.expert_offset, period)
        with jax.named_scope("extend.moe.shared"):
            shared = layers.gated_mlp(flat, p["wi"], p["wo"])
        return (routed + shared).reshape(b, tc, d), counted

    def _add(x, out):
        return x + (res * out).astype(dtype)

    @jax.jit
    def extend(params, tokens, lengths, k_cache, v_cache, ssm, conv, slots, snap_at, snap_slots, *,
               last=None, table=None):
        tc = tokens.shape[1]
        (positions, valid), fresh = layers.frame(tokens, lengths), lengths == 0
        paged = table is not None
        visible = layers.visible_keys(positions, valid, layers.cache_slots(k_cache, table))
        live = layers.live_keys(positions, valid)
        with jax.named_scope("extend.embed"):
            x = layers.look_up(params["wte"]["embedding"].astype(dtype), tokens)
            x = x * jnp.asarray(cfg.embedding_multiplier, dtype)

        def body(carry, xs):
            # the state arena is carried whole and each layer's slots are read
            # and written where they lie
            x, ssm, tails = carry
            p, kc, vc, period = xs
            if paged:       # the arenas themselves, this period's layer found by the kernel
                kc, vc = k_cache, v_cache
            rows, m, counted = None, 0, ()
            for i in range(cfg.period):
                if i == cfg.attention_at:
                    out, rows = _attend(
                        p["attn"], _normed(x, p["attn"]["ln"]).astype(dtype), positions,
                        visible, live, kc, vc, (period, table) if paged else None)
                else:
                    layer = p["mamba"][m]
                    out, ssm, tails = _mamba(
                        layer, _normed(x, layer["ln"]).astype(dtype), valid, fresh, slots,
                        snap_at, snap_slots, ssm, tails, period * (cfg.period - 1) + m)
                    m += 1
                x = _add(x, out)
                if cfg.router_experts:
                    out, pairs = _routed(x, p["mlp"][i], params["experts"][i], period, valid)
                    x, counted = _add(x, out), counted + (pairs,)
                else:
                    x = _add(x, _mlp(x, p["mlp"][i]))
            return (x, ssm, tails), (rows, counted)

        # the convolution's inputs are small (26 KB a lane and layer, where the
        # state is 2 MB): every layer's leave their slots in one slice a lane and
        # go back in one update a lane; layer by layer the compiler would move
        # that whole arena to VMEM and back around the scan
        own = _take(conv, slots, layers=conv.shape[0])
        tails = (own, own) if tc > 1 else (own,)    # the new ones; a chunk's kept ones
        (x, ssm, tails), (rows, counted) = jax.lax.scan(
            body, (x, ssm, tails), (
                params["periods"], *((None, None) if paged else (k_cache, v_cache)),
                jnp.arange(cfg.periods, dtype=jnp.int32)))
        if tc > 1:
            conv = _put(conv, snap_slots, tails[1])
        conv = _put(conv, slots, tails[0])

        def head(rows):
            rows = _normed(rows, params["ln_f"])
            return jnp.dot(
                rows.astype(dtype), params["wte"]["embedding"].astype(dtype).T,
                preferred_element_type=f32) / cfg.logits_scaling, rows

        with jax.named_scope("extend.logits"):
            logits, x = layers.read_rows(x, last, head)
        counters = cfg.ssm_layers * jnp.stack([
            valid.sum(dtype=jnp.int32), valid.any(1).sum(dtype=jnp.int32)])
        if cfg.router_experts:
            counters = jnp.concatenate([counters, sum(counted).sum(0)])
        return (logits, x, *rows, ssm, conv, counters)

    return extend
