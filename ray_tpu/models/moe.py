"""Expert layers: a capacity-based one for the ``ep`` mesh axis, and the dropless
share of an expert-parallel deployment that the served and trained models run.

**The capacity-based layer** (:class:`MoeMlp`; the reference has no MoE at all,
SURVEY.md §2.6 EP row: absent) is a TPU-first implementation of the GShard/Switch
dispatch: top-k routing with a STATIC per-expert capacity (XLA-friendly — no dynamic
shapes), dispatch and combine as einsums whose expert dimension is sharded over ``ep``
so XLA inserts the all-to-all, and a load-balancing auxiliary loss sown into the
``losses`` collection (summed per layer by the scanned block stack). Expert weights
carry the ("expert", "embed", "mlp") logical axes: ep shards the expert dim, tp can
still shard the mlp dim inside each expert.

**The dropless layer**, for one chip's share of an expert-parallel deployment, or every
expert on one chip. A router scores every routed expert; there are four, by what the
models publish:

* :func:`sigmoid_top_k` — sigmoid scores, the ``k`` largest over their sum
  (``models/cohere2_moe.py``);
* :func:`softmax_top_k` — a softmax over all experts, the ``k`` largest over their sum
  (``norm_topk_prob``: ``models/keye_vl2.py``, ``models/qwen3_next.py``, and
  ``models/granitemoehybrid.py`` beside a shared MLP in every layer of a period of
  Mamba-2 and attention mixers);
* :func:`sigmoid_bias_top_k` — sigmoid scores, a bias that chooses and does not weigh,
  the chosen scores over their sum times a scale (``noaux_tc``: ``models/kimi_k2.py``,
  ``models/glm_moe_dsa.py``, ``models/mimo_v2_flash.py``, and under ``jax.grad``
  ``models/lfm2_moe.py`` and ``models/nemotron_h.py``, whose experts have **no gate**:
  ``W_down relu(W_up x)^2``, two matrices an expert, :func:`relu_squared` for
  :func:`trained_experts_ffn`'s ``activation``);
* :func:`softmax_bias_top_k` — a softmax over all outputs, a bias that chooses and does
  not weigh, the chosen probabilities themselves times a scale, **not** over their sum
  (``models/longcat_flash.py``), over a router whose last outputs are **zero-compute
  experts**: experts with no weights that give the token back
  (:func:`zero_experts_part`).

:func:`held_experts_ffn` is told which experts live here and computes their part of the
result for the tokens routed to them (:func:`trained_experts_ffn` under ``jax.grad``):
the token-expert pairs are sorted by expert and run through a grouped matmul
(:func:`grouped_matmul`: one kernel that walks the groups and reads an expert's weights
only if it has rows). No capacity, no dropped token; shapes are static from the worst
case, in which every choice of every token is held here. What the absent experts would
add is left out. A pair on a zero-compute expert is of no group, held or absent: every
chip adds that part for its own tokens (:func:`zero_experts_part`), once.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import backend


#: what :func:`held_experts_ffn` counts over the real tokens of a device call,
#: in the order it hands them over: tokens, token-expert pairs computed here,
#: held experts with at least one token, the busiest held expert's pairs. A
#: model sums them over its expert layers and names them to the serving engine
#: (``counters`` of its configuration), which sums them over its calls.
COUNTERS = ("moe_tokens", "moe_assignments", "moe_experts_hit", "moe_load_max")


def _logits(h, router):
    """Every expert's logit for the tokens ``h`` [n, d] under the ``router``
    [d, R], in float32 at the highest matmul precision: the TPU's default
    would round ``h`` and the router to bfloat16 and move the k-th choice."""
    return jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)


#: the name :func:`sigmoid_bias_top_k` gives the experts it chose, for a remat that
#: asks for it (``kept=True``) to keep: a layer's replay that chose again could choose
#: otherwise where two experts lie an ulp apart, and would then sort the pairs into
#: other rows than the kept results of :func:`trained_experts_ffn` lie in
ROUTED = "moe_chosen"


def _top_k_of(scores, k: int, bias=None, kept: bool = False):
    """The ``k`` largest of ``scores`` [n, R], normalised over themselves; with
    a ``bias`` [R], the ``k`` whose ``scores + bias`` are largest, which the
    bias chooses and does not weigh. ``kept``: the chosen experts carry
    :data:`ROUTED`'s name, and the weights are read at the named ones."""
    if bias is None:
        top, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
        experts = checkpoint_name(experts, ROUTED) if kept else experts
        top = jnp.take_along_axis(scores, experts, axis=-1)
    return top / top.sum(-1, keepdims=True), experts.astype(jnp.int32)


def softmax_top_k(h: jax.Array, router: jax.Array, k: int):
    """As :func:`sigmoid_top_k` with a softmax over all ``R`` experts for the
    scores (``norm_topk_prob``: the ``k`` largest probabilities, divided by
    their sum)."""
    return _top_k_of(jax.nn.softmax(_logits(h, router), axis=-1), k)


def sigmoid_top_k(h: jax.Array, router: jax.Array, k: int):
    """Route tokens ``h`` [n, d] over every expert the ``router`` [d, R]
    scores: sigmoid scores in float32, the ``k`` largest, normalised over the
    chosen ``k``. Returns ``(weights [n, k] float32, experts [n, k] int32)``."""
    return _top_k_of(jax.nn.sigmoid(_logits(h, router)), k)


def sigmoid_bias_top_k(h: jax.Array, router: jax.Array, bias: jax.Array, k: int, scale: float,
                       kept: bool = False):
    """As :func:`sigmoid_top_k`, but a ``bias`` [R] on the scores decides which
    ``k`` are chosen and weighs nothing (``topk_method`` ``noaux_tc``: the
    correction that balances the experts' load without a loss): the weights
    are the chosen experts' own scores over their sum, times ``scale``
    (``routed_scaling_factor``). ``kept``: the chosen experts are named
    :data:`ROUTED` for the layer's remat to keep."""
    weights, experts = _top_k_of(jax.nn.sigmoid(_logits(h, router)), k, bias, kept)
    return weights * scale, experts


def softmax_bias_top_k(h: jax.Array, router: jax.Array, bias: jax.Array, k: int, scale: float):
    """Route tokens ``h`` [n, d] over every output of the ``router`` [d, R]: a float32
    softmax over all ``R``, the ``k`` with the largest ``p + bias`` chosen (``bias`` [R],
    ``e_score_correction_bias``: it chooses and does not weigh), and the chosen
    probabilities **themselves** times ``scale`` (``routed_scaling_factor``) for weights:
    not divided by their sum, so a token's weights add up to ``scale`` times the mass its
    choices hold, not to ``scale`` (``models/longcat_flash.py``, whose router's last
    outputs are experts with no weights: :func:`zero_experts_part`). Returns ``(weights
    [n, k] float32, experts [n, k] int32)``."""
    scores = jax.nn.softmax(_logits(h, router), axis=-1)
    _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
    return jnp.take_along_axis(scores, experts, axis=-1) * scale, experts.astype(jnp.int32)


def zero_experts_part(x, weights, experts, valid, routed: int):
    """The part of a routed layer that its **zero-compute experts** give: a pick whose
    index is ``routed`` or more (``experts`` [n, k] over the router's ``R > routed``
    outputs) names an expert with no weights, the identity, whose result is the token
    ``x`` [n, d] itself times the pick's weight. Such an expert lives on no chip and on
    every chip: each computes this part for the tokens it owns, so of the shares of an
    expert-parallel layer it is counted once, like a shared expert.
    :func:`held_experts_ffn` sorts such a pair into no group, whatever the offset: it is
    neither held nor absent. Returns ``(y [n, d] float32, the pairs of real tokens that
    fell on such an expert, int32)``; ``valid`` [n] marks the real tokens."""
    zero = (experts >= routed) & valid[:, None]
    weight = jnp.where(zero, weights, 0.0).sum(-1, keepdims=True)
    return weight * x.astype(jnp.float32), zero.sum(dtype=jnp.int32)


#: (rows, contraction, columns) tile of the TPU's grouped matmul. Measured on a
#: v5e at Command A+'s widths, one layer's 16 held experts (PERF.md, PR 28):
#: 2.7 ms for a 256-token chunk and 1.6 ms for 8 decode tokens, where
#: ``jax.lax.ragged_dot``'s own kernel took 5.3 and 1.8 ms; row tiles of 16
#: lose at 256 tokens (4.4 ms), of 256 and more too (3.4 to 4.8 ms).
GMM_TILING = (32, 4096, 512)


#: the tile of a train step's grouped matmuls and of their two transposes
#: (``gmm``'s ``custom_vjp``: the input's gradient is a ``gmm`` against the
#: transposed weights, the weights' a ``tgmm``, both with the forward's tile), at
#: about a thousand rows an expert: row tiles of 512, an expert's whole
#: contraction (its ``[k, 512]`` block of weights stays in VMEM from one row
#: tile of the group to the next). Measured on a v5e, one layer of 32 held
#: experts of LFM2's widths, 16,384 tokens, forward and backward (PERF.md, PR
#: 43): 34.2 ms, (512, 1024, 1024) 33.9; on an earlier layout of the layer
#: (256, 2048, 512) 0.4 ms more, row tiles of 128 or 1024 and column tiles of
#: 256 3 to 5 ms more; 1024-wide tiles of both kinds run out of VMEM
GMM_TRAIN_TILING = (512, 2048, 512)


def _whole_lanes(tile: int, size: int) -> int:
    """``tile``, or for a smaller dimension the dimension in whole 128-lane tiles: the
    kernel's transposes (its ``custom_vjp``) reuse a tile on the other side of the
    weights, where a width that is no whole number of lanes (Nemotron-3-Nano's 1856)
    is no legal block of a wider dimension; the kernel masks what overhangs."""
    return min(tile, -(-size // 128) * 128)


def grouped_matmul(rows, w, group_sizes, interpret: bool = False, tiling=GMM_TILING):
    """``rows`` [m, k], sorted by group, times ``w`` [groups, k, n]: row ``i``
    is multiplied with the matrix of its group, the first ``group_sizes[0]``
    rows with ``w[0]`` and so on; rows past the last group are undefined. On
    the TPU this is the megablox kernel (``jax.experimental``), which visits
    only the row tiles of groups that have rows; elsewhere XLA's ragged dot.

    Differentiable: the kernel is a ``custom_vjp`` whose gradient of ``rows``
    is a grouped matmul against the transposed weights, with the rows past the
    last group **undefined again**, and whose gradient of ``w`` (``tgmm``) reads
    the rows of a group alone. A caller that hands the gradient of ``rows`` on
    masks those rows (:func:`trained_experts_ffn`)."""
    if not (backend.on_tpu() or interpret):
        return jax.lax.ragged_dot(rows, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, (tm, tk, tn) = rows.shape[0], tiling
    padded = jnp.pad(rows, ((0, -m % tm), (0, 0)))
    out = gmm(
        padded, w, group_sizes, preferred_element_type=rows.dtype,
        tiling=(tm, _whole_lanes(tk, w.shape[1]), _whole_lanes(tn, w.shape[2])),
        interpret=interpret)
    return out[:m]


def held_experts_ffn(x, weights, experts, valid, wi, wo, offset: int = 0, layer=None):
    """The part of a routed expert layer that the experts held here give.

    ``x`` [n, d] are the tokens, ``weights`` / ``experts`` [n, k] what
    :func:`sigmoid_top_k` chose for each, ``valid`` [n] marks real tokens
    (padding computes nothing and reads no expert). ``wi`` [E, d, 2f] holds,
    for each of the ``E`` experts ``offset .. offset + E - 1``, the gate and
    the up projection side by side, ``wo`` [E, f, d] the down projection; an
    expert is ``wo(silu(gate x) * up x)``. Returns ``(y [n, d] float32,
    counters int32 [4])``: the weighted sum over each token's held choices,
    and (real tokens, token-expert pairs computed here, held experts with at
    least one token, the busiest held expert's pairs).

    With ``layer`` (an index, traced or not) ``wi`` and ``wo`` are a stack of
    layers ``[L, E, ...]`` of which that one is used **in place**: the grouped
    matmul gets the whole stack as ``L x E`` groups, all empty but this
    layer's. Sliced out of the stack, as a scan over layers would hand them
    over, the TPU compiler copies the layer's experts (1.6 GB at Command A+'s
    widths) on every call, because the kernel wants a whole operand.

    Every pair whose expert is held is computed: the pairs are sorted by
    expert, those of absent experts and of padding last and in no group, so
    the grouped matmul neither computes them nor reads weights for them. A pair
    that names a zero-compute expert (an index past the routed experts, which no
    share's ``offset .. offset + E - 1`` reaches: :func:`zero_experts_part`) sorts
    there too, behind every group: its part is not this function's."""
    n, k = experts.shape
    num_held, f = wo.shape[-3], wo.shape[-2]
    local = experts - offset
    held = (local >= 0) & (local < num_held) & valid[:, None]
    key = jnp.where(held, local, num_held).reshape(n * k)
    order = jnp.argsort(key, stable=True)
    group_sizes = (
        key[:, None] == jnp.arange(num_held, dtype=key.dtype)[None, :]
    ).sum(0, dtype=jnp.int32)
    counted = group_sizes
    if layer is not None:
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((wi.shape[0] * num_held,), jnp.int32), group_sizes,
            (layer * num_held,))
        wi, wo = (w.reshape((-1,) + w.shape[2:]) for w in (wi, wo))
    rows = x[order // k]                                         # [n k, d]
    gate_up = grouped_matmul(rows, wi.astype(x.dtype), group_sizes)
    act = jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]
    out = grouped_matmul(act, wo.astype(x.dtype), group_sizes)
    # back to (token, choice) order; rows of no group hold nothing defined
    place = jnp.zeros_like(order).at[order].set(jnp.arange(n * k, dtype=order.dtype))
    per_pair = jnp.where(
        held[:, :, None], out[place].reshape(n, k, -1).astype(jnp.float32), 0.0)
    y = jnp.einsum("nk,nkd->nd", jnp.where(held, weights, 0.0), per_pair)
    counters = jnp.stack([
        valid.sum(dtype=jnp.int32), counted.sum(dtype=jnp.int32),
        (counted > 0).sum(dtype=jnp.int32), counted.max(),
    ])
    return y, counters


# A trained layer's passes between the sort and the un-sort. Pair ``c n + t`` is token
# ``t``'s choice ``c``; ``held`` [k, n] marks the pairs whose expert is held here;
# ``order`` sorts the ``k n`` pairs by expert, those not held last, and ``place`` is its
# inverse. The grouped matmul leaves the rows of the pairs not held undefined, in its
# result and in the gradient of its rows alike, and nothing here lets such a row reach a
# token: in the result or in a gradient.
#
# **The work follows the pairs held.** The shapes are static for the worst case, ``k n``
# rows, but the held pairs sort first, and a pass over sorted rows can be a loop over
# blocks of ``row_block`` rows whose trip count is read from ``group_sizes.sum()`` on
# the device: it stops at the last block that holds a pair. What a loop needs is
# somewhere to write, and there are four answers:
#
# * a pass that makes one row a **token** (the combine, the sort's gradient) runs in the
#   sorted rows' own order: a block's rows are added, weighted, into an ``[n, d]``
#   float32 result at the block's tokens. No ``k n`` buffer, no mask over the pairs not
#   held, nothing cleared but ``[n, d]``;
# * a pass that makes one row a **sorted row** from that row alone (the activation and
#   its gradient, the casts that keep a result as its bits) is :func:`_held_rows`: on
#   the TPU a kernel whose grid is the row tiles of the held blocks, whose result is
#   undefined past them as the grouped matmul's is past the last group;
# * the gather of the tokens into sorted rows fills a buffer that XLA made
#   (:func:`_unfilled`), and XLA clears what it makes: one streamed write of ``k n``
#   rows;
# * a backward pass whose value is dead by then writes a block's gradient where the
#   block lay (the un-sort's gradient).
#
# **Two forms, chosen by the share of the routed experts that is held here**
# (:data:`WALKED_SHARE`, read from the shapes and ``routed``: what a configuration
# states, nothing a user sets). At a small share **every pass** outside the kernels,
# forward and backward, stops at the last held block (:func:`_sorted_rows`,
# :func:`_activated`, :func:`_combined`). At a large one the loops would walk most
# blocks, a cleared buffer costs what it saves, and a replay merged with its forward
# (``prevent_cse=False`` round an unrolled period: ``models/lfm2_moe.py``) holds what
# the loops and kernels made from the forward to the backward, where XLA makes a whole
# pass again instead (LFM2's step: 17.5 GB for 15.8): there the forward's passes stay
# whole passes (:func:`_all_sorted_rows`, :func:`_all_activated`,
# :func:`_all_combined`, :func:`_named`) and the backward's alone are loops, each
# writing a block's gradient where the value it is the gradient of lay.


#: blocks a trained layer's sorted rows are walked in: sixteenths of the ``k n`` pairs
ROW_BLOCKS = 16


#: the largest share of the routed experts held here at which every pass of a trained
#: layer walks the held blocks alone: a quarter (Nemotron-3-Nano's chip of ep8 holds an
#: eighth, 3 blocks of 16 walked; LFM2's of ep2 a half, 8 to 9 of 16)
WALKED_SHARE = 0.25


def row_block(pairs: int, tile: int) -> int:
    """Rows of one block: a :data:`ROW_BLOCKS`-th of ``pairs`` in whole row tiles."""
    return min(pairs, -(-pairs // (ROW_BLOCKS * tile)) * tile)


def _held_blocks(block: int, held_rows):
    """Blocks of ``block`` sorted rows that hold a pair: the first ones."""
    return -(-held_rows // block)


def _over_held_blocks(block: int, held_rows, body, *buffers):
    """``body(start, *buffers) -> buffers`` for every block of ``block`` sorted rows
    that holds a pair."""
    return jax.lax.fori_loop(
        0, _held_blocks(block, held_rows), lambda b, buffers: body(b * block, *buffers), buffers)


def _rows_at(rows, start, block: int):
    """``rows[start:start + block]``; a last block that would pass the end starts
    earlier (what a dynamic slice does)."""
    return jax.lax.dynamic_slice_in_dim(rows, start, block, axis=0)


def _set_rows(rows, start, values):
    """``rows`` with the block at ``start`` set to ``values``. The rows that a last
    block which started earlier shares with the block before it keep what that
    block wrote: they no longer hold what ``values`` was computed from."""
    block, total = values.shape[0], rows.shape[0]
    if total % block:
        ahead = jnp.minimum(start, total - block) + jnp.arange(block) >= start
        values = jnp.where(
            ahead.reshape((block,) + (1,) * (values.ndim - 1)), values, _rows_at(rows, start, block))
    return jax.lax.dynamic_update_slice_in_dim(rows, values, start, axis=0)


def _own_rows(start, block: int, total: int, held_rows):
    """Which rows of the block at ``start`` are a held pair's and this block's to add:
    not the rows past the last pair, which hold nothing defined, nor those a last block
    that starts earlier shares with the block before it."""
    at = jnp.minimum(start, total - block) + jnp.arange(block)
    return (at >= start) & (at < held_rows)


def _unfilled(shape, dtype):
    """A buffer for a loop over the held blocks to fill: cleared, because XLA makes no
    other. No pass reads a row of it that no loop filled. (A kernel that writes nothing
    hands one out as it lies, and the gather into it takes 0.45 ms a layer for 1.32: but
    Nemotron-3-Nano's step then packs into 15.01 GB where the configuration states
    14.59, though nothing lives longer; with the parent's whole gather, 0.81 ms, 15.16:
    ``PERF.md``, PR 64.)"""
    return jnp.zeros(shape, dtype)


#: rows of one grid step of :func:`_held_rows`' kernel
HELD_ROWS_TILE = 256


def _held_rows(fn, held_rows, block: int, *rows, interpret: bool = False):
    """``fn`` of the sorted ``rows`` (each [k n, w]), a function of each row's own values
    (``[rows, w], ... -> [rows, v]``), for the blocks of ``block`` rows that hold a pair:
    [k n, v], **undefined past the last held block**. On the TPU one kernel whose grid
    is the row tiles of those blocks, so it reads and writes nothing else and needs no
    buffer cleared for it; elsewhere a loop over the blocks."""
    total = rows[0].shape[0]
    made = jax.eval_shape(
        fn, *(jax.ShapeDtypeStruct((block,) + r.shape[1:], r.dtype) for r in rows))
    if not (backend.on_tpu() or interpret):
        return _over_held_blocks(
            block, held_rows,
            lambda start, filled: (jax.lax.dynamic_update_slice_in_dim(
                filled, fn(*(_rows_at(r, start, block) for r in rows)), start, axis=0),),
            _unfilled((total,) + made.shape[1:], made.dtype))[0]
    from jax.experimental import pallas as pl

    tile = math.gcd(block, HELD_ROWS_TILE)
    tiles = jnp.minimum(_held_blocks(block, held_rows) * (block // tile), -(-total // tile))

    def moe_held_rows(*refs):
        refs[-1][...] = fn(*(ref[...] for ref in refs[:-1]))

    return pl.pallas_call(
        moe_held_rows, grid=(tiles,),
        in_specs=[pl.BlockSpec((tile,) + r.shape[1:], lambda i: (i, 0)) for r in rows],
        out_specs=pl.BlockSpec((tile,) + made.shape[1:], lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total,) + made.shape[1:], made.dtype),
        interpret=interpret, name="moe_held_rows")(*rows)


#: what :func:`trained_experts_ffn` names for a remat round it: the first grouped
#: matmul's result ``gate_up`` [k n, 2f] (an un-gated expert's: ``up`` [k n, f], under
#: the same name) and the second's ``out`` [k n, d], both **as their bits**: the
#: unsigned integers of their width, every value as it was, NaN and -0.0 too. JAX's
#: remat puts a ``reduce_precision`` to the value's own type on the producer of every
#: floating-point value it keeps (against XLA's excess precision between the forward
#: and the replay): on a kernel's bfloat16 result that rounds nothing, and XLA, which
#: cannot alias through it, copies the value to keep it. Integers get none. A remat
#: that keeps both (``models/lfm2_moe.py`` and ``models/nemotron_h.py`` ``forward``)
#: hands them to the backward; one that keeps neither runs both kernels again for them
TRAINED_RESIDUALS = ("moe_gate_up", "moe_out")


def _bits_of(x):
    """``x`` as the unsigned integers of its width: every value's own bits."""
    return jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


def _kept_bits(x, name: str, held_rows, block: int):
    """The sorted rows ``x`` for the backward pass, under ``name``, as their bits
    (:data:`TRAINED_RESIDUALS`), which ``jax.lax.bitcast_convert_type`` reads back.
    Outside a scan over layers the cast is no free view to XLA but a pass, so it is
    made over the held blocks alone (:func:`_held_rows`), and what is kept is undefined
    past them as ``x`` was past the last group. Who reads the bits reads them block by
    block where it computes (:func:`_activated`, :func:`_combined`): nothing casts all
    ``k n`` rows back."""
    return checkpoint_name(_held_rows(_bits_of, held_rows, block, x), name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_rows(block, n, x, tokens, held_rows):
    """The ``n`` tokens ``x`` [n, d] as the rows of their pairs, sorted (``tokens``
    [k n]: the token of each sorted row): [k n, d], filled as far as the last held
    block."""
    def gather(start, rows):
        return (jax.lax.dynamic_update_slice_in_dim(
            rows, x[_rows_at(tokens, start, block)], start, axis=0),)

    return _over_held_blocks(
        block, held_rows, gather, _unfilled(tokens.shape + x.shape[1:], x.dtype))[0]


def _sorted_rows_fwd(block, n, x, tokens, held_rows):
    return _sorted_rows(block, n, x, tokens, held_rows), (tokens, held_rows)


def _sorted_rows_bwd(block, n, kept, g):
    """In the sorted rows' own order: a held pair's row of ``g`` is added to its token's
    gradient, in float32."""
    tokens, held_rows = kept

    def add(start, dx):
        own = _own_rows(start, block, g.shape[0], held_rows)
        rows = jnp.where(own[:, None], _rows_at(g, start, block), 0).astype(jnp.float32)
        return (dx.at[_rows_at(tokens, start, block)].add(rows),)

    dx, = _over_held_blocks(block, held_rows, add, jnp.zeros((n,) + g.shape[1:], jnp.float32))
    return dx.astype(g.dtype), None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


def gated_silu(gate_up):
    """``silu(gate) * up`` of ``gate_up`` [rows, 2f], gate and up side by side: [rows, f]."""
    f = gate_up.shape[1] // 2
    return jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]


def relu_squared(up):
    """``relu(up)^2`` of ``up`` [rows, f]: an expert with no gate (``mlp_hidden_act``
    ``relu2``: ``models/nemotron_h.py``), whose ``wi`` is one matrix of width f."""
    return jnp.square(jax.nn.relu(up))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _activated(activation, block, first, held_rows):
    """``activation`` of the sorted rows ``first`` [k n, ...], the first grouped
    matmul's result: [k n, f], as far as the last held block. ``first`` is kept for the
    backward pass under :data:`TRAINED_RESIDUALS`' first name."""
    return _activated_fwd(activation, block, first, held_rows)[0]


def _activated_fwd(activation, block, first, held_rows):
    bits = _kept_bits(first, TRAINED_RESIDUALS[0], held_rows, block)

    def activated(bits):        # in float32: what a fusion of XLA's computes in, and a kernel has to
        first_rows = jax.lax.bitcast_convert_type(bits, first.dtype)
        return activation(first_rows.astype(jnp.float32)).astype(first.dtype)

    return _held_rows(activated, held_rows, block, bits), (bits, held_rows)


def _activated_bwd(activation, block, kept, g):
    bits, held_rows = kept

    def gradient(bits, g):
        _, vjp = jax.vjp(activation, jax.lax.bitcast_convert_type(bits, g.dtype).astype(jnp.float32))
        return vjp(g.astype(jnp.float32))[0].astype(g.dtype)

    return _held_rows(gradient, held_rows, block, bits, g), None


_activated.defvjp(_activated_fwd, _activated_bwd)


def _unsorted_gradient(block, out, weights, held, order, place, held_rows, g):
    """The gradient of the un-sort and the combine, in the sorted rows' own order: a
    row's gradient is its pair's weight times its token's ``g``, a pair's weight's
    gradient the row's product with that ``g``; a row of no group is read by neither
    of the grouped matmul's gradients. A block's gradient is written where the block
    of ``out`` lay: ``(d out, d weights)``."""
    by_pair = weights.T.reshape(-1)

    def unsort(start, out, d_by_row):
        pairs = _rows_at(order, start, block)
        g_rows = g[pairs % g.shape[0]]
        d_rows = (by_pair[pairs][:, None] * g_rows).astype(out.dtype)
        d_weight = (g_rows * _rows_at(out, start, block).astype(jnp.float32)).sum(-1)
        return _set_rows(out, start, d_rows), _set_rows(d_by_row, start, d_weight)

    d_out, d_by_row = _over_held_blocks(
        block, held_rows, unsort, out, jnp.zeros(out.shape[:1], jnp.float32))
    d_weights = jnp.where(held, d_by_row[place].reshape(held.shape), 0).T
    return d_out, d_weights.astype(weights.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combined(block, out, weights, held, order, place, held_rows):
    """The sorted rows ``out`` [k n, d] summed token by token under the ``weights``
    [n, k] of their pairs, in the rows' own order: a held pair's row is added, weighted,
    to its token's: [n, d] float32, zeros for a token none of whose choices is held.
    ``out`` is kept for the backward pass under :data:`TRAINED_RESIDUALS`' second name."""
    return _combined_fwd(block, out, weights, held, order, place, held_rows)[0]


def _combined_fwd(block, out, weights, held, order, place, held_rows):
    n = weights.shape[0]
    by_pair = weights.T.reshape(-1)
    bits = _kept_bits(out, TRAINED_RESIDUALS[1], held_rows, block)

    def add(start, y):
        pairs = _rows_at(order, start, block)
        own = _own_rows(start, block, out.shape[0], held_rows)
        rows = jax.lax.bitcast_convert_type(_rows_at(bits, start, block), out.dtype)
        rows = jnp.where(own[:, None], rows, 0).astype(jnp.float32)
        return (y.at[pairs % n].add(jnp.where(own, by_pair[pairs], 0)[:, None] * rows),)

    y, = _over_held_blocks(block, held_rows, add, jnp.zeros((n,) + out.shape[1:], jnp.float32))
    # (a scalar of ``out``'s type: the bits' width alone does not say which float they are)
    return y, (bits, jnp.zeros((), out.dtype), weights, held, order, place, held_rows)


def _combined_bwd(block, kept, g):
    bits, like, weights, held, order, place, held_rows = kept
    out = _held_rows(
        lambda bits: jax.lax.bitcast_convert_type(bits, like.dtype), held_rows, block, bits)
    return _unsorted_gradient(
        block, out, weights, held, order, place, held_rows, g) + (None, None, None, None)


_combined.defvjp(_combined_fwd, _combined_bwd)


# -- the form whose forward passes are whole passes (above: a large held share) ------


@jax.custom_vjp
def _all_sorted_rows(x, held, order, place):
    """The tokens ``x`` [n, d] as the rows of their pairs, sorted: [k n, d]."""
    return x[order % x.shape[0]]


def _all_sorted_rows_fwd(x, held, order, place):
    return x[order % x.shape[0]], (held, place)


def _all_sorted_rows_bwd(kept, g):
    held, place = kept
    g = jnp.where(held[:, :, None], g[place].reshape(held.shape + g.shape[1:]), 0)
    return g.sum(0).astype(g.dtype), None, None, None


_all_sorted_rows.defvjp(_all_sorted_rows_fwd, _all_sorted_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _all_activated(activation, block, first, held_rows):
    """``activation`` of the sorted rows ``first`` [k n, ...], the first grouped
    matmul's result: [k n, f]."""
    return activation(first)


def _all_activated_fwd(activation, block, first, held_rows):
    return activation(first), (first, held_rows)


def _all_activated_bwd(activation, block, kept, g):
    first, held_rows = kept

    def one(start, first):
        _, vjp = jax.vjp(activation, _rows_at(first, start, block))
        return (_set_rows(first, start, vjp(_rows_at(g, start, block))[0]),)

    return _over_held_blocks(block, held_rows, one, first)[0], None


_all_activated.defvjp(_all_activated_fwd, _all_activated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _all_combined(block, out, weights, held, order, place, held_rows):
    """The sorted rows ``out`` [k n, d] back pair by pair, zeros for the pairs not
    held, and their sum over a token's choices under its ``weights`` [n, k]: [n, d]
    float32."""
    per_pair = jnp.where(held[:, :, None], out[place].reshape(held.shape + out.shape[1:]), 0)
    # one pass over the bfloat16 rows: the cast, the weight and the sum over the choices fuse
    return (weights.T[:, :, None] * per_pair.astype(jnp.float32)).sum(0)


def _all_combined_fwd(block, out, weights, held, order, place, held_rows):
    return _all_combined(block, out, weights, held, order, place, held_rows), (
        out, weights, held, order, place, held_rows)


def _all_combined_bwd(block, kept, g):
    return _unsorted_gradient(block, *kept, g) + (None, None, None, None)


_all_combined.defvjp(_all_combined_fwd, _all_combined_bwd)


def _bits_named(x, name: str):
    """:func:`_named`'s value, and what its tangent rule traces: the name has to be an
    equation of the layer's own jaxpr for a policy to see it."""
    return jax.lax.bitcast_convert_type(checkpoint_name(_bits_of(x), name), x.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _named(x, name: str):
    """``x`` under ``name`` for a remat's policy, named by its bits
    (:data:`TRAINED_RESIDUALS`): cast, named, cast back, all ``k n`` rows. The identity
    on every value and on its tangent (a cast to integers alone would pass none).
    Inside a scan over layers XLA aliases through both casts; outside one each is a
    copy (:func:`_kept_bits`)."""
    return _bits_named(x, name)


@_named.defjvp
def _named_jvp(name, primals, tangents):
    return _bits_named(primals[0], name), tangents[0]


#: what :func:`trained_experts_ffn` counts: :data:`COUNTERS`' four and the sorted rows
#: its loops walk (the blocks that hold a pair, in rows): every pass outside the
#: kernels, forward and backward, at a held share of :data:`WALKED_SHARE` or less, the
#: backward's passes at a larger one
TRAINED_COUNTERS = COUNTERS + ("moe_rows_visited",)


def trained_experts_ffn(x, weights, experts, wi, wo, offset: int = 0, tiling=GMM_TRAIN_TILING,
                        activation=gated_silu, routed=None):
    """:func:`held_experts_ffn` for a train step: the same part of the layer, the
    same counters and one more (:data:`TRAINED_COUNTERS`), every pair whose expert
    is held computed, and a gradient for ``x``, ``weights`` (through which the
    router is trained), ``wi`` and ``wo``; ``experts`` are integers and pass none.
    Every token is real. ``activation`` stands between the two grouped matmuls, a
    function of the first one's rows: :func:`gated_silu` (``wi`` [E, d, 2f]: gate and
    up side by side) or :func:`relu_squared` (``wi`` [E, d, f]: no gate). ``routed`` is
    the number of experts the router scores, of which ``E`` are held here (None: not
    said, as many as are held).

    What differs is what a backward pass and some thousand rows an expert ask
    for. The grouped matmuls run with ``tiling``. The pairs are laid out
    **choice by choice** (pair ``c n + t`` is token ``t``'s choice ``c``), so the
    ``[k n, d]`` rows split into ``[k, n, d]`` without a copy (``[n, k, d]`` pads
    its ``k`` to a whole tile of 8). The pairs of absent experts are sorted last
    and lie in no group: the kernel leaves their rows undefined, in its result
    and in the gradient of its rows alike; between the sort and the un-sort a row
    meets only its own values, and a row of no group reaches no token. The passes
    outside the kernels stop at the last block of :func:`row_block` rows that holds a
    pair (above): **all of them, forward and backward, where the experts held are**
    :data:`WALKED_SHARE` **of the routed ones or fewer**, the backward's where they are
    more; with every pair held that is all ``k n`` rows, with none the result is zeros
    and so is every gradient. Nothing is dropped. The two grouped matmuls' results
    carry :data:`TRAINED_RESIDUALS`' names."""
    n, k = experts.shape
    num_held = wo.shape[-3]
    local = experts.T - offset
    held = (local >= 0) & (local < num_held)                      # [k, n]
    key = jnp.where(held, local, num_held).reshape(k * n)
    order = jnp.argsort(key, stable=True)
    group_sizes = (
        key[:, None] == jnp.arange(num_held, dtype=key.dtype)[None, :]
    ).sum(0, dtype=jnp.int32)
    place = jnp.zeros_like(order).at[order].set(jnp.arange(k * n, dtype=order.dtype))
    held_rows, block = group_sizes.sum(dtype=jnp.int32), row_block(k * n, tiling[0])
    if num_held <= WALKED_SHARE * (routed or num_held):
        rows = _sorted_rows(block, n, x, order % n, held_rows)    # [k n, d]
        gate_up = grouped_matmul(rows, wi.astype(x.dtype), group_sizes, tiling=tiling)
        act = _activated(activation, block, gate_up, held_rows)
        out = grouped_matmul(act, wo.astype(x.dtype), group_sizes, tiling=tiling)
        y = _combined(block, out, weights, held, order, place, held_rows)
    else:
        rows = _all_sorted_rows(x, held, order, place)
        gate_up = _named(
            grouped_matmul(rows, wi.astype(x.dtype), group_sizes, tiling=tiling), TRAINED_RESIDUALS[0])
        act = _all_activated(activation, block, gate_up, held_rows)
        out = _named(
            grouped_matmul(act, wo.astype(x.dtype), group_sizes, tiling=tiling), TRAINED_RESIDUALS[1])
        y = _all_combined(block, out, weights, held, order, place, held_rows)
    counters = jnp.stack([
        jnp.int32(n), held_rows, (group_sizes > 0).sum(dtype=jnp.int32), group_sizes.max(),
        jnp.minimum(_held_blocks(block, held_rows) * block, k * n),
    ])
    return y, counters


class MoeMlp(nn.Module):
    """Drop-in replacement for the dense Mlp block when
    ``cfg.moe_num_experts > 0``."""

    cfg: Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        E, k = cfg.moe_num_experts, cfg.moe_top_k
        b, t, d = x.shape
        s = b * t
        xs = x.reshape(s, d)

        # -- routing (f32 numerics) ---------------------------------------
        w_router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", "expert")
            ),
            (d, E),
            cfg.param_dtype,
        )
        logits = xs.astype(jnp.float32) @ w_router.astype(jnp.float32)  # [s, E]
        probs = jax.nn.softmax(logits, axis=-1)

        # static capacity: k*s assignments spread over E experts, padded by
        # the capacity factor; never data-dependent
        capacity = max(1, int(math.ceil(k * s / E * cfg.moe_capacity_factor)))

        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [s, k]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )

        # position-in-expert: slot 0 (first choice) of every token gets
        # priority over slot 1, matching the GShard assignment order
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [s, k, E]
        flat = onehot.transpose(1, 0, 2).reshape(k * s, E)       # slot-major
        pos = ((jnp.cumsum(flat, axis=0) - flat) * flat).sum(-1)  # [k*s]
        assigned = flat.sum(-1)                                   # 0/1
        keep = (pos < capacity) * assigned
        slot_oh = jax.nn.one_hot(
            pos.astype(jnp.int32), capacity, dtype=jnp.float32
        )  # [k*s, C]
        # [k*s, E, C] -> [k, s, E, C] -> sum over k -> [s, E, C]
        disp_flat = flat[:, :, None] * slot_oh[:, None, :] * keep[:, None, None]
        dispatch = disp_flat.reshape(k, s, E, capacity).sum(0)
        gates_flat = gate_vals.transpose(1, 0).reshape(k * s)
        combine = (disp_flat * gates_flat[:, None, None]).reshape(
            k, s, E, capacity
        ).sum(0)

        # -- expert computation (all-to-all via ep sharding) --------------
        wi = self.param(
            "wi",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("expert", "embed", "mlp")
            ),
            (E, d, cfg.mlp_dim),
            cfg.param_dtype,
        )
        wo = self.param(
            "wo",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("expert", "mlp", "embed")
            ),
            (E, cfg.mlp_dim, d),
            cfg.param_dtype,
        )
        expert_in = jnp.einsum(
            "sec,sd->ecd", dispatch.astype(cfg.dtype), xs.astype(cfg.dtype)
        )
        expert_in = nn.with_logical_constraint(expert_in, ("expert", None, None))
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(cfg.dtype))
        h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("expert", None, "act_mlp"))
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo.astype(cfg.dtype))
        y = jnp.einsum(
            "sec,ecd->sd", combine.astype(cfg.dtype), expert_out
        )

        # -- load-balance aux loss (Switch §2.2 form) ---------------------
        # f_e: fraction of tokens whose FIRST choice is e; P_e: mean router
        # prob. Perfectly uniform routing gives aux == 1.
        f = onehot[:, 0, :].mean(0)
        p = probs.mean(0)
        aux = (f * p).sum() * E
        self.sow("losses", "moe_aux", aux.astype(jnp.float32))

        return y.reshape(b, t, d)
