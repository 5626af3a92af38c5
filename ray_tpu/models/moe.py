"""Mixture-of-experts MLP with expert parallelism over the ``ep`` mesh axis.

The reference has no MoE at all (SURVEY.md §2.6 EP row: absent); this is a
TPU-first implementation of the GShard/Switch dispatch: top-k routing with a
STATIC per-expert capacity (XLA-friendly — no dynamic shapes), dispatch and
combine as einsums whose expert dimension is sharded over ``ep`` so XLA
inserts the all-to-all, and a load-balancing auxiliary loss sown into the
``losses`` collection (summed per layer by the scanned block stack).

Expert weights carry the ("expert", "embed", "mlp") logical axes: ep shards
the expert dim, tp can still shard the mlp dim inside each expert.

Beside it, for serving (``models/cohere2_moe.py``): a **dropless** layer for
one chip's share of an expert-parallel deployment (``models/kimi_k2.py`` too) or every expert on
one chip (``models/keye_vl2.py``). :func:`sigmoid_top_k`, :func:`softmax_top_k` or
:func:`sigmoid_bias_top_k` scores every routed expert, :func:`held_experts_ffn` is told which experts
live here and computes their part of the result for the tokens routed to
them: the token-expert pairs are sorted by expert and run through a grouped
matmul (:func:`grouped_matmul`: one kernel that walks the groups and reads an
expert's weights only if it has rows). No capacity, no dropped token; shapes
are static from the worst case, in which every choice of every token is held
here. What the absent experts would add is left out.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

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


def _top_k_of(scores, k: int, bias=None):
    """The ``k`` largest of ``scores`` [n, R], normalised over themselves; with
    a ``bias`` [R], the ``k`` whose ``scores + bias`` are largest, which the
    bias chooses and does not weigh."""
    if bias is None:
        top, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
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


def sigmoid_bias_top_k(h: jax.Array, router: jax.Array, bias: jax.Array, k: int, scale: float):
    """As :func:`sigmoid_top_k`, but a ``bias`` [R] on the scores decides which
    ``k`` are chosen and weighs nothing (``topk_method`` ``noaux_tc``: the
    correction that balances the experts' load without a loss): the weights
    are the chosen experts' own scores over their sum, times ``scale``
    (``routed_scaling_factor``)."""
    weights, experts = _top_k_of(jax.nn.sigmoid(_logits(h, router)), k, bias)
    return weights * scale, experts


#: (rows, contraction, columns) tile of the TPU's grouped matmul. Measured on a
#: v5e at Command A+'s widths, one layer's 16 held experts (PERF.md, PR 28):
#: 2.7 ms for a 256-token chunk and 1.6 ms for 8 decode tokens, where
#: ``jax.lax.ragged_dot``'s own kernel took 5.3 and 1.8 ms; row tiles of 16
#: lose at 256 tokens (4.4 ms), of 256 and more too (3.4 to 4.8 ms).
GMM_TILING = (32, 4096, 512)


def grouped_matmul(rows, w, group_sizes, interpret: bool = False):
    """``rows`` [m, k], sorted by group, times ``w`` [groups, k, n]: row ``i``
    is multiplied with the matrix of its group, the first ``group_sizes[0]``
    rows with ``w[0]`` and so on; rows past the last group are undefined. On
    the TPU this is the megablox kernel (``jax.experimental``), which visits
    only the row tiles of groups that have rows; elsewhere XLA's ragged dot."""
    if not (backend.on_tpu() or interpret):
        return jax.lax.ragged_dot(rows, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, (tm, tk, tn) = rows.shape[0], GMM_TILING
    padded = jnp.pad(rows, ((0, -m % tm), (0, 0)))
    out = gmm(
        padded, w, group_sizes, preferred_element_type=rows.dtype,
        tiling=(tm, min(tk, w.shape[1]), min(tn, w.shape[2])), interpret=interpret)
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
    the grouped matmul neither computes them nor reads weights for them."""
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
