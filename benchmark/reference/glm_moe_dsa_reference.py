"""GLM-5 (``glm_moe_dsa``: latent attention under DeepSeek-V3.2's learned sparse
selection): the forward pass in plain ``jax.numpy`` and float32 at the highest matmul
precision, in the **expanded** form of its latent attention: no kernels, no cache, no
absorption, no chunking, no scan, no sort but ``jax.lax.top_k``; a loop over layers,
over blocks of queries, over heads and over experts. The yardstick the serving path is
compared with, at a small size on the CPU (``tests/benchmark/test_bench_glm_moe_dsa.py``)
and, at the published widths on the chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (zai-org/GLM-5) and, where that has no key
(the indexer's norm, which of its features rotate, its weights' scale), the published
inference code of DeepSeek-V3.2-Exp (``inference/model.py``, ``Indexer``), after which
the ``model_type`` names itself. With ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``
and ``n = N(h)``, a block is ``h += Attn(N(h))``, then ``h += FFN(N(h))``:

* latents: ``c_q = N(n W_qa)`` (``q_lora_rank``); ``[c_kv ; k_r] = n W_kva``
  (``kv_lora_rank + qk_rope_head_dim``), ``c_kv = N(c_kv)``, ``k_r = rot(k_r)``: one
  rotary key for all heads. A head ``j``: ``[q_nope ; q_r] = c_q W_qb[j]``
  (``qk_nope_head_dim + qk_rope_head_dim``), ``q_r = rot(q_r)``; ``[k_nope ; v] = c_kv
  W_kvb[j]`` (``qk_nope_head_dim + v_head_dim``). ``rot`` turns the pairs ``(2i, 2i +
  1)`` (``rope_interleave``) at ``rope_theta^(-2i / dim)``, ``rope_type`` default;
* the indexer: ``qI[t, i] = c_q(t) W_Iq[i]`` (``index_n_heads`` of ``index_head_dim``),
  its first ``index_rope_head_dim`` features rotated the same way
  (``indexer_rope_interleave``); ``kI[s] = LayerNorm(n(s) W_Ik)`` with scale and bias,
  eps ``index_norm_eps``, its first ``index_rope_head_dim`` rotated; ``w[t] = n(t) W_Iw
  * index_n_heads^-0.5 * index_head_dim^-0.5``; ``I(t, s) = sum_i w[t, i] relu(qI[t, i]
  . kI[s])`` for ``s <= t``. Query ``t`` attends the ``min(index_topk, t + 1)``
  positions with the largest ``I(t, .)`` and no other (``jax.lax.top_k``: among equals
  the lower position), all its heads alike;
* attention over those: ``softmax((q_nope . k_nope + q_r . k_r) * qk_head_dim^-0.5)``
  over ``v``, then ``W_o``;
* layer ``l < first_k_dense_replace``: a gated MLP, ``W_d (silu(W_g n) * W_u n)``, of
  width ``intermediate_size``; every other layer: ``s = sigmoid(n W_r)`` over all routed
  experts; the ``num_experts_per_tok`` with the largest ``s + b`` chosen (``noaux_tc``:
  ``b`` chooses only; ``n_group`` 1 and ``topk_group`` 1 limit nothing); weights ``s_e /
  (sum of the chosen s + 1e-20) * routed_scaling_factor``; plus ``n_shared_experts``
  experts of width ``moe_intermediate_size`` that every token passes, unweighted;
* a final ``N`` and an untied head.

It is given the share the chip holds: the routed experts ``expert_offset ..
expert_offset + n_routed_experts - 1`` of the ``router_experts`` the router scores (what
the absent ones would add is left out, as in the program) and the first ``vocab_size``
rows of the vocabulary.

Departures, which the comparison accounts for or the configuration's file lists:

* the program rotates *half-split* pairs ``(i, i + dim / 2)``: the published rotation
  under a fixed permutation of the rotated features, applied alike to both sides of
  every product (``W_qb``'s and ``W_kva``'s rotary columns; the first
  ``index_rope_head_dim`` features of ``qI`` and of the normed ``kI``), which leaves
  every product as it is; ``_rotary_order`` applies it where they are read;
* the Hadamard rotation and the float8 rounding the published inference code puts in
  front of the indexer's products are not here (an orthogonal transform of both sides
  changes no product; float8 is a precision under the one the configuration states);
* an exact zero of ``I`` counts as one value whatever its sign (+0 and -0 tie);
* the program stores the two halves of ``W_kvb`` apart (``k_up``, ``v_up``) and an
  expert's gate and up projections side by side; they are read as they lie;
* no multi-token-prediction layer.

``wrong`` names one omission at a time, to show what the limit of the comparison
catches: ``"dense"`` (no selection: causal attention), ``"topk_half"`` (half of
``index_topk`` rows a query), ``"no_relu"`` (``I`` without its ReLU), ``"no_w"`` (``I``
summed over the indexer's heads without ``w``), ``"index_key_unnormed"`` (``kI``
without its LayerNorm), ``"index_unrotated"`` (neither ``qI`` nor ``kI`` rotated),
``"index_reads_unnormed"`` (``qI`` from ``n W_qa`` before its norm), ``"latent_unnormed"``
(``c_kv`` without its norm), ``"scale_192"`` (``qk_nope_head_dim^-0.5`` for the softmax
scale), ``"no_shared"`` (no shared expert), ``"no_routed_scale"``
(``routed_scaling_factor`` left out), and ``"fp8_weights"``: every weight matrix rounded
to float8 (e4m3) as it is read, the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256         # queries selected and attended at a time: [ROWS, heads, seq] index dots
COLUMNS = 2048     # of the dense layer's width at a time: one expert's worth
LOWER = "fp8_weights"
WRONG = (
    "dense", "topk_half", "no_relu", "no_w", "index_key_unnormed", "index_unrotated",
    "index_reads_unnormed", "latent_unnormed", "scale_192", "no_shared", "no_routed_scale")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _w(a, lower: bool):
    """A piece of the program's weights in float32; ``lower`` rounds it to
    float8 (e4m3) first."""
    return jnp.asarray(a.astype(jnp.float8_e4m3fn) if lower else a, F32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rotate_interleaved(x, base: float):
    """``x`` [seq, ..., dim] at positions 0 .. seq - 1, feature ``2i`` rotated
    with ``2i + 1`` at ``base^(-2i / dim)``."""
    dim = x.shape[-1]
    freqs = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs[None, :]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _rotary_order(dim: int) -> np.ndarray:
    """published feature 2i <- program feature i; 2i + 1 <- program feature i + dim / 2"""
    order = np.arange(dim)
    order[0::2], order[1::2] = np.arange(dim // 2), np.arange(dim // 2) + dim // 2
    return order


def expert(n, wi, wo):
    """``W_d (silu(W_g n) * W_u n)``, gate and up side by side in ``wi``."""
    f = wo.shape[0]
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ wo


def route(n, router, bias, k: int, scaling: float, wrong: Optional[str] = None):
    """``(weights [seq, k], chosen [seq, k])``: the ``k`` experts with the largest
    ``sigmoid + bias``, weighed by their sigmoid alone over the chosen's sum."""
    scores = jax.nn.sigmoid(n @ router)
    _, chosen = jax.lax.top_k(scores + bias, k)
    top = jnp.take_along_axis(scores, chosen, -1)
    top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return (top if wrong == "no_routed_scale" else top * scaling), chosen


# -- the pieces, jitted one at a time ----------------------------------------------


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
@_highest
def _latents(n, attn, rope, base, eps, normed, lower):
    """q [seq, heads, nope + rope] and k_rope [seq, rope], both rotated; c_kv [seq,
    rank]; the query latent after and before its norm [seq, q_rank]."""
    order = _rotary_order(rope)
    before = n @ _w(attn["q_a"]["kernel"], lower)
    c_q = rms_norm(before, _w(attn["q_norm"]["scale"], False), eps)
    q = jnp.einsum("tr,rhk->thk", c_q, _w(attn["q_b"]["kernel"], lower))
    nope = q.shape[-1] - rope
    q_rope = rotate_interleaved(q[..., nope:][..., order], base)
    both = n @ _w(attn["kv_a"]["kernel"], lower)
    c_kv, k_r = both[:, :-rope], both[:, -rope:][:, order]
    if normed:
        c_kv = rms_norm(c_kv, _w(attn["kv_norm"]["scale"], False), eps)
    return (
        jnp.concatenate([q[..., :nope], q_rope], -1), rotate_interleaved(k_r, base), c_kv,
        c_q, before)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
@_highest
def _indexer(n, c_q, index, rope, base, eps, wrong, scale, lower):
    """qI [seq, heads, dim], kI [seq, dim], w [seq, heads]."""
    order = _rotary_order(rope)

    def rotated(x):                             # [seq, ..., dim]: its first ``rope``
        if wrong == "index_unrotated":
            return x
        return jnp.concatenate(
            [rotate_interleaved(x[..., :rope][..., order], base), x[..., rope:]], -1)

    qi = jnp.einsum("tr,rhk->thk", c_q, _w(index["q"]["kernel"], lower))
    ki = n @ _w(index["k"]["kernel"], lower)
    if wrong != "index_key_unnormed":
        norm = index["k_norm"]
        ki = layer_norm(ki, jnp.asarray(norm["scale"], F32), jnp.asarray(norm["bias"], F32), eps)
    w = n @ _w(index["w"]["kernel"], lower) * scale
    return rotated(qi), rotated(ki), w


@functools.partial(jax.jit, static_argnums=(4, 5))
@_highest
def _select(qi, w, rows, ki, k, wrong):
    """For the queries at positions ``rows``: what each selects, bool [r, seq]: the
    ``k`` positions with the largest index score of those it sees."""
    seq = ki.shape[0]
    causal = jnp.arange(seq)[None, :] <= rows[:, None]
    if wrong == "dense":
        return causal
    dots = jnp.einsum("qhd,kd->qhk", qi, ki)
    if wrong != "no_relu":
        dots = jax.nn.relu(dots)
    score = dots.sum(1) if wrong == "no_w" else (dots * w[:, :, None]).sum(1)
    score = jnp.where(score == 0, 0.0, score)
    top, positions = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), k)
    at = jnp.broadcast_to(jnp.arange(len(rows))[:, None], positions.shape)
    return jnp.zeros((len(rows), seq), bool).at[at, positions].set(top > -jnp.inf)


@functools.partial(jax.jit, static_argnums=(6, 7))
@_highest
def _attend_head(q, c_kv, k_rope, k_up, v_up, selected, scale, lower):
    """One head, expanded: ``q`` [seq, nope + rope] over its own keys and values
    under ``selected`` [seq, seq], ``ROWS`` queries at a time."""
    seq = q.shape[0]
    k = jnp.concatenate([c_kv @ _w(k_up, lower), k_rope], -1)       # [seq, nope + rope]
    v = c_kv @ _w(v_up, lower)                                      # [seq, v]
    out = []
    for a in range(0, seq, ROWS):
        scores = jnp.where(selected[a:a + ROWS], (q[a:a + ROWS] @ k.T) * scale, -jnp.inf)
        out.append(jax.nn.softmax(scores, -1) @ v)
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _out(attended, o, lower):
    return jnp.einsum("thv,hvd->td", attended, _w(o, lower))


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _expert(n, wi, wo, lower):
    return expert(n, _w(wi, lower), _w(wo, lower))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
@_highest
def _route(n, router, bias, k, scaling, wrong, lower):
    return route(n, _w(router, lower), jnp.asarray(bias, F32), k, scaling, wrong)


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _head(x, ln_f, head, eps, lower):
    return rms_norm(x, jnp.asarray(ln_f, F32), eps) @ _w(head, lower)


# -- a layer -----------------------------------------------------------------------


def _attention(n, attn, index, config: Dict[str, Any], wrong, selections=None):
    lower, seq = wrong == LOWER, n.shape[0]
    rope, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    q, k_rope, c_kv, c_q, before = _latents(
        n, attn, rope, base, config["rms_norm_eps"], wrong != "latent_unnormed", lower)
    qi, ki, w = _indexer(
        n, before if wrong == "index_reads_unnormed" else c_q, index,
        config["index_rope_head_dim"], base, config["index_norm_eps"], wrong,
        config["index_n_heads"] ** -0.5 * config["index_head_dim"] ** -0.5, lower)
    topk = config["index_topk"] // (2 if wrong == "topk_half" else 1)
    selected = jnp.concatenate([
        _select(qi[a:a + ROWS], w[a:a + ROWS], jnp.arange(a, min(a + ROWS, seq)), ki,
                min(topk, seq), wrong)
        for a in range(0, seq, ROWS)])
    if selections is not None:
        selections.append(np.asarray(selected))
    width = config["qk_nope_head_dim"] if wrong == "scale_192" else config["qk_head_dim"]
    heads = [
        _attend_head(
            q[:, h], c_kv, k_rope, attn["k_up"]["kernel"][:, h], attn["v_up"]["kernel"][:, h],
            selected, width ** -0.5, lower)
        for h in range(q.shape[1])
    ]
    return _out(jnp.stack(heads, 1), attn["o"]["kernel"], lower)


def _dense(n, mlp, lower):
    """The leading layers' gated MLP, ``COLUMNS`` of its width at a time."""
    wi, wo = mlp["wi"], mlp["wo"]
    f = wo.shape[0]
    out = 0.0
    for a in range(0, f, COLUMNS):
        b = min(a + COLUMNS, f)
        piece = jnp.concatenate([wi[:, a:b], wi[:, f + a:f + b]], 1)
        out = jax.block_until_ready(out + _expert(n, piece, wo[a:b], lower))
    return out


def _experts(n, moe, shared, config, wrong):
    """The held experts' part of the routed sum, plus the shared expert."""
    lower = wrong == LOWER
    top, chosen = _route(
        n, moe["router"], moe["bias"], config["num_experts_per_tok"],
        float(config["routed_scaling_factor"]), wrong, lower)
    out = 0.0
    for e in range(moe["wi"].shape[0]):
        weight = jnp.where(chosen == config.get("expert_offset", 0) + e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(
            out + weight[:, None] * _expert(n, moe["wi"][e], moe["wo"][e], lower))
    if wrong != "no_shared":
        out = out + _expert(n, shared["wi"], shared["wo"], lower)
    return out


def _layer_of(tree, at: int):
    return jax.tree.map(lambda a: a[at], tree)


def _hidden(program, tokens, config, wrong, selections=None):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    eps = config["rms_norm_eps"]
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    dense = config["first_k_dense_replace"]
    for at in range(config["num_hidden_layers"]):
        p = _layer_of(program["first"], at) if at < dense else _layer_of(
            program["blocks"]["layers"], at - dense)
        h = x + _attention(
            _norm(x, p["ln_1"]["scale"], eps), p["attn"], p["index"], config, wrong, selections)
        n = _norm(h, p["ln_2"]["scale"], eps)
        x = h + (_dense(n, p["mlp"], wrong == LOWER) if at < dense else _experts(
            n, p["moe"], p["shared"], config, wrong))
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    x = _hidden(program, tokens, config, wrong)
    return _head(
        x[-last:], program["ln_f"]["scale"], program["head"]["kernel"],
        config["rms_norm_eps"], wrong == LOWER)


def program_selection(program, tokens, config) -> np.ndarray:
    """What every query of ``tokens`` selected in every layer, bool [layers,
    seq, seq]: the reference's own sets, from its own hidden states."""
    selections: List[Any] = []
    _hidden(program, tokens, config, None, selections)
    return np.stack(selections)


def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.take_along_axis(logp, jnp.asarray(tokens)[1:, None], -1)[..., 0].mean()


def program_loss(program, tokens, config) -> float:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq] from the
    program's own weights, one sequence at a time. The benchmark trains no
    such model; the harness's contract lists the entry point."""
    rows = [
        float(next_token_loss(program_logits(program, row, config, len(row)), row))
        for row in tokens
    ]
    return sum(rows) / len(rows)
