"""LongCat-Flash-Chat (``longcat_flash``): the forward pass in plain ``jax.numpy`` and
float32 at the highest matmul precision, in the **expanded** form of its latent
attention: no kernels, no cache, no absorption, no scan, no sort but ``jax.lax.top_k``; a
loop over layers, over a layer's two sub-blocks, over heads, over blocks of queries and
over experts. The yardstick the serving path is compared with, at a small size on the CPU
(``tests/benchmark/test_bench_longcat_flash.py``) and, at the published widths on the
chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (meituan-longcat/LongCat-Flash-Chat) and the
layer as the published modeling code writes it. With ``N(x) = x / sqrt(mean(x^2) +
rms_norm_eps) * g`` and ``x`` the residual stream, a layer is::

    for i in (0, 1):
        h = x + MLA[i](N_in[i](x))
        u = N_post[i](h)
        if i == 0:  s = MoE(u)              # the shortcut: read here ...
        x = h + MLP[i](u)                   # W_d (silu(W_g u) * W_u u), ffn_hidden_size
    x = x + s                               # ... added after the second sub-block

* ``MLA``: ``q = W_qb N_q(W_qa n)``, ``num_attention_heads`` heads of ``qk_nope_head_dim +
  qk_rope_head_dim``, **both parts times** ``(hidden_size / q_lora_rank)^0.5``
  (``mla_scale_q_lora``); ``[c ; k_r] = W_kva n``, ``c = N_kv(c)`` **times** ``(hidden_size
  / kv_lora_rank)^0.5`` (``mla_scale_kv_lora``) before ``W_kvb``, which gives a head's
  ``k_nope`` and ``v``; ``q_rope`` and the one ``k_r`` rotated over the pairs ``(2i, 2i +
  1)`` at ``rope_theta^(-2i / dim)``, no scaling; causal softmax at ``(qk_nope_head_dim +
  qk_rope_head_dim)^-0.5``; ``W_o``;
* ``MoE``: ``p = softmax(W_r u)`` over all ``router_experts`` outputs (the routed experts,
  then ``zero_expert_num`` zero-compute ones); the ``moe_topk`` with the largest ``p + b``
  chosen (``e_score_correction_bias``: chooses only); ``w_c = routed_scaling_factor x
  p_c``, **not** divided by their sum; ``MoE(u) = sum over chosen c that are routed
  experts of w_c E_c(u) + (sum over chosen c that are zero-compute of w_c) u`` (identity
  experts), ``E_c`` a gated MLP of ``expert_ffn_hidden_size``. No shared expert;
* a final ``N`` and an untied head.

It is given the share the chip holds: the routed experts ``expert_offset .. expert_offset
+ n_routed_experts - 1`` (what the absent ones would add is left out, as in the program),
**every** zero-compute pick (each chip's own, for its own tokens) and the first
``vocab_size`` rows of the vocabulary.

Departures of the program under test, which the comparison accounts for:

* the program rotates *half-split* pairs ``(i, i + dim / 2)``: the published rotation
  under a fixed permutation of the rotary features, applied alike to q's and to the
  latent's, which leaves every ``q_rope . k_rope`` unchanged; ``_rotary_order`` applies it
  to the rotary columns of ``W_qb`` and ``W_kva``;
* the program stores the two halves of ``W_kvb`` apart (``k_up``, ``v_up``) and an
  expert's or an MLP's gate and up projections side by side; they are read as they lie.

``wrong`` names one omission at a time, to show what the limit of the comparison
catches: ``"no_zero_experts"`` (the zero-compute picks' part left out),
``"shortcut_early"`` (``s`` added before the second sub-block instead of after it),
``"shortcut_reads_h"`` (the expert layer fed ``h`` and not ``u = N_post(h)``),
``"no_q_scale"`` and ``"no_kv_scale"`` (either ``mla_scale`` constant left out),
``"norm_topk"`` (the chosen weights divided by their sum), ``"no_routed_scale"``
(``routed_scaling_factor`` left out), ``"bias_weighs"`` (the bias weighs as well as
chooses) and ``"fp8_weights"``: every weight matrix rounded to float8 (e4m3) as it is
read, the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 1024        # queries attended at a time: [ROWS, seq] scores a head
COLUMNS = 2048     # of a dense MLP's width at a time: one expert's worth
LOWER = "fp8_weights"
WRONG = (
    "no_zero_experts", "shortcut_early", "shortcut_reads_h", "no_q_scale", "no_kv_scale",
    "norm_topk", "no_routed_scale", "bias_weighs")
SUB_BLOCKS = ("first", "second")


def mla_scales(config: Dict[str, Any], wrong: Optional[str] = None):
    """``(what q is multiplied with, what the normed latent is)``, as the published
    modeling code reads ``mla_scale_q_lora`` and ``mla_scale_kv_lora``."""
    q = (config["hidden_size"] / config["q_lora_rank"]) ** 0.5 if (
        config.get("mla_scale_q_lora") and wrong != "no_q_scale") else 1.0
    kv = (config["hidden_size"] / config["kv_lora_rank"]) ** 0.5 if (
        config.get("mla_scale_kv_lora") and wrong != "no_kv_scale") else 1.0
    return q, kv


def rotate_interleaved(x, freqs):
    """``x`` [seq, ..., dim] at positions 0 .. seq - 1, feature ``2i`` rotated
    with ``2i + 1`` at ``freqs[i]``."""
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _rotary_order(dim: int) -> np.ndarray:
    """published feature 2i <- program feature i; 2i + 1 <- program feature i + dim / 2"""
    order = np.arange(dim)
    order[0::2], order[1::2] = np.arange(dim // 2), np.arange(dim // 2) + dim // 2
    return order


# -- the pieces --------------------------------------------------------------------


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


def expert(n, wi, wo):
    """``W_d (silu(W_g n) * W_u n)``, gate and up side by side in ``wi``."""
    f = wo.shape[0]
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ wo


def route(n, router, bias, k: int, scaling: float, wrong: Optional[str] = None):
    """``(weights [seq, k], chosen [seq, k])``: the ``k`` outputs with the largest
    ``softmax + bias``, weighed by ``scaling`` times their own probability."""
    scores = jax.nn.softmax(n @ router, axis=-1)
    biased = scores + bias
    _, chosen = jax.lax.top_k(biased, k)
    top = jnp.take_along_axis(biased if wrong == "bias_weighs" else scores, chosen, -1)
    if wrong == "norm_topk":
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return (top if wrong == "no_routed_scale" else top * scaling), chosen


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
@_highest
def _latents(n, attn, rope, freqs, eps, q_scale, kv_scale, lower):
    """q [seq, heads, nope + rope], the scaled normed c_kv [seq, rank] and k_rope [seq,
    rope], both rotated; ``freqs`` a tuple."""
    order = _rotary_order(rope)
    c_q = rms_norm(n @ _w(attn["q_a"]["kernel"], lower), _w(attn["q_norm"]["scale"], False), eps)
    q = jnp.einsum("tr,rhk->thk", c_q, _w(attn["q_b"]["kernel"], lower)) * q_scale
    nope = q.shape[-1] - rope
    q_rope = rotate_interleaved(q[..., nope:][..., order], freqs)
    both = n @ _w(attn["kv_a"]["kernel"], lower)
    c_kv, k_r = both[:, :-rope], both[:, -rope:][:, order]
    c_kv = rms_norm(c_kv, _w(attn["kv_norm"]["scale"], False), eps) * kv_scale
    return jnp.concatenate([q[..., :nope], q_rope], -1), c_kv, rotate_interleaved(k_r, freqs)


@functools.partial(jax.jit, static_argnums=(5, 6))
@_highest
def _attend_head(q, c_kv, k_rope, k_up, v_up, scale, lower):
    """One head, expanded: ``q`` [seq, nope + rope] over its own keys and
    values, ``ROWS`` queries at a time."""
    seq = q.shape[0]
    k = jnp.concatenate([c_kv @ _w(k_up, lower), k_rope], -1)       # [seq, nope + rope]
    v = c_kv @ _w(v_up, lower)                                      # [seq, v]
    out = []
    for a in range(0, seq, ROWS):
        rows = jnp.arange(a, min(a + ROWS, seq))
        scores = (q[a:a + ROWS] @ k.T) * scale
        scores = jnp.where(jnp.arange(seq)[None, :] <= rows[:, None], scores, -jnp.inf)
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


def _attention(n, attn, config, wrong):
    lower = wrong == LOWER
    rope = config["qk_rope_head_dim"]
    freqs = tuple(
        float(f) for f in 1.0 / float(config["rope_theta"]) ** (
            np.arange(0, rope, 2, dtype=np.float64) / rope))
    q, c_kv, k_rope = _latents(
        n, attn, rope, freqs, config["rms_norm_eps"], *mla_scales(config, wrong), lower)
    scale = (config["qk_nope_head_dim"] + rope) ** -0.5
    heads = [
        _attend_head(
            q[:, h], c_kv, k_rope, attn["k_up"]["kernel"][:, h], attn["v_up"]["kernel"][:, h],
            scale, lower)
        for h in range(q.shape[1])
    ]
    return _out(jnp.stack(heads, 1), attn["o"]["kernel"], lower)


def _dense(n, mlp, lower):
    """A sub-block's gated MLP, ``COLUMNS`` of its width at a time."""
    wi, wo = mlp["wi"], mlp["wo"]
    f = wo.shape[0]
    out = 0.0
    for a in range(0, f, COLUMNS):
        b = min(a + COLUMNS, f)
        piece = jnp.concatenate([wi[:, a:b], wi[:, f + a:f + b]], 1)
        out = jax.block_until_ready(out + _expert(n, piece, wo[a:b], lower))
    return out


def _experts(n, moe, config, wrong, at: Optional[int] = None):
    """The held experts' part of the routed sum, plus every zero-compute pick's: the
    token itself under the pick's weight. ``moe`` holds one layer's router, bias and
    held experts, or with ``at`` every layer's, stacked, of which that one is read an
    expert at a time (a layer's experts sliced out whole are 0.8 GB beside the served
    weights)."""
    lower = wrong == LOWER

    def of_layer(a):
        return a if at is None else a[at]

    top, chosen = _route(
        n, of_layer(moe["router"]), of_layer(moe["bias"]), config["moe_topk"],
        float(config["routed_scaling_factor"]), wrong, lower)
    out = 0.0
    for e in range(moe["wi"].shape[-3]):
        held = (e,) if at is None else (at, e)
        weight = jnp.where(chosen == config.get("expert_offset", 0) + e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(
            out + weight[:, None] * _expert(n, moe["wi"][held], moe["wo"][held], lower))
    if wrong != "no_zero_experts":
        routed = moe["router"].shape[-1] - config["zero_expert_num"]
        out = out + jnp.where(chosen >= routed, top, 0.0).sum(-1)[:, None] * n
    return out


def _layer_of(tree, at: int):
    return jax.tree.map(lambda a: a[at], tree)


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    eps, lower = config["rms_norm_eps"], wrong == LOWER
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], lower)
    stacked = program["blocks"]["layers"]
    for at in range(config["num_layers"]):
        for i, sub in enumerate(SUB_BLOCKS):
            b = _layer_of(stacked[sub], at)         # a sub-block at a time: 0.63 GB
            h = x + _attention(_norm(x, b["ln_in"]["scale"], eps), b["attn"], config, wrong)
            u = _norm(h, b["ln_post"]["scale"], eps)
            if i == 0:
                s = _experts(
                    h if wrong == "shortcut_reads_h" else u, stacked["moe"], config, wrong, at)
            x = h + _dense(u, b["mlp"], lower)
            if i == 0 and wrong == "shortcut_early":
                x = x + s
        if wrong != "shortcut_early":
            x = x + s
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    x = _hidden(program, tokens, config, wrong)
    return _head(
        x[-last:], program["ln_f"]["scale"], program["head"]["kernel"],
        config["rms_norm_eps"], wrong == LOWER)


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
