"""Kimi-K2-Instruct (``kimi_k2``, DeepSeek-V3's block): the forward pass in plain
``jax.numpy`` and float32 at the highest matmul precision, in the **expanded**
form of its latent attention: no kernels, no cache, no absorption, no scan, no
sort but ``jax.lax.top_k``; a loop over layers, over heads, over blocks of
queries and over experts. The yardstick the serving path is compared with, at
a small size on the CPU (``tests/benchmark/test_bench_kimi_k2.py``) and, at the
published widths on the chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (moonshotai/Kimi-K2-Instruct). With
``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``, a block is ``h = x +
Attn(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``:

* queries: ``c_q = RMSNorm(n W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``,
  ``num_attention_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``; the
  last ``qk_rope_head_dim`` features of a head are rotated;
* the latent: ``[c_kv ; k_r] = n W_kva`` (``kv_lora_rank + qk_rope_head_dim``);
  ``c_kv = RMSNorm(c_kv)``; ``k_rope = RoPE(k_r)``, one rotary key for all heads;
* expanded: ``k_nope = c_kv W_kvb^K[h]``, ``v = c_kv W_kvb^V[h]`` per head; ``k =
  [k_nope ; k_rope]``; scores ``q . k * s`` under the causal mask, softmax,
  ``sum p v``, ``W_o``;
* the rotation is YaRN's (``rope_scaling``): over the pairs ``(2i, 2i + 1)`` of
  the rotary features, frequency ``i`` between ``theta_i = rope_theta^(-2i /
  dim)`` and ``theta_i / factor`` by the linear ramp between the two correction
  dimensions (:func:`yarn_frequencies`); ``s = (qk_nope_head_dim +
  qk_rope_head_dim)^-0.5 * mscale^2`` with ``mscale = 0.1 * mscale_all_dim *
  ln(factor) + 1``; the factor on cos and sin is ``yarn_get_mscale(factor,
  mscale) / yarn_get_mscale(factor, mscale_all_dim)``;
* layer ``l < first_k_dense_replace``: a gated MLP, ``W_d (silu(W_g n) * W_u
  n)``, of width ``intermediate_size``;
* every other layer: ``s = sigmoid(n W_r)`` over all routed experts; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` are chosen
  (``topk_method`` ``noaux_tc``: ``b`` is ``e_score_correction_bias``, and
  chooses only; ``n_group`` 1 and ``topk_group`` 1 limit nothing); weights
  ``s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor``; plus
  ``n_shared_experts`` experts of width ``moe_intermediate_size`` that every
  token passes through, added unweighted;
* a final RMSNorm and an untied head.

It is given the share the chip holds: the routed experts ``expert_offset ..
expert_offset + n_routed_experts - 1`` of the ``router_experts`` the router
scores (what the absent ones would add is left out, as in the program) and the
first ``vocab_size`` rows of the vocabulary.

Departures of the program under test, which the comparison accounts for:

* the program rotates *half-split* pairs ``(i, i + dim / 2)``: the published
  rotation under a fixed permutation of the rotary features, applied alike to
  q's and to the latent's, which leaves every ``q_rope . k_rope`` unchanged;
  ``_rotary_order`` applies it to the rotary columns of ``W_qb`` and ``W_kva``;
* the program stores the two halves of ``W_kvb`` apart (``k_up``, ``v_up``) and
  an expert's gate and up projections side by side; they are read as they lie.

``wrong`` names one omission at a time, to show what the limit of the
comparison catches: ``"no_mscale"`` (``mscale^2`` left out of ``s``),
``"plain_rope"`` (``rope_theta``'s own frequencies, no YaRN), ``"bias_weighs"``
(the bias weighs as well as chooses), ``"no_routed_scale"``
(``routed_scaling_factor`` left out), ``"no_shared"`` (no shared expert),
``"latent_unnormed"`` (``c_kv`` without its RMSNorm) and ``"fp8_weights"``:
every weight matrix rounded to float8 (e4m3) as it is read, the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 1024        # queries attended at a time: [ROWS, seq] scores a head
COLUMNS = 2048     # of the dense layer's width at a time: one expert's worth
LOWER = "fp8_weights"
WRONG = (
    "no_mscale", "plain_rope", "bias_weighs", "no_routed_scale", "no_shared",
    "latent_unnormed")


# -- YaRN -------------------------------------------------------------------------


def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, base: float, scaling: Optional[Dict[str, Any]]) -> np.ndarray:
    """The ``dim / 2`` rotation frequencies (float64): ``base^(-2i / dim)``
    without ``scaling``; with it each lies between that (extrapolation, ramp 0)
    and that over ``factor`` (interpolation, ramp 1)."""
    theta = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return theta

    def correction_dim(rotations):
        return dim * math.log(
            scaling["original_max_position_embeddings"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return theta / scaling["factor"] * ramp + theta * (1.0 - ramp)


def softmax_scale(config: Dict[str, Any], with_mscale: bool = True) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    scaling = config.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim") and with_mscale:
        scale *= yarn_get_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rotation_factor(config: Dict[str, Any]) -> float:
    """What cos and sin are multiplied with."""
    scaling = config.get("rope_scaling")
    if not scaling:
        return 1.0
    return yarn_get_mscale(scaling["factor"], scaling["mscale"]) / yarn_get_mscale(
        scaling["factor"], scaling["mscale_all_dim"])


def rotate_interleaved(x, freqs, factor: float = 1.0):
    """``x`` [seq, ..., dim] at positions 0 .. seq - 1, feature ``2i`` rotated
    with ``2i + 1`` at ``freqs[i]``."""
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    sin, cos = jnp.sin(angles) * factor, jnp.cos(angles) * factor
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
    """``(weights [seq, k], chosen [seq, k])``: the ``k`` experts with the largest
    ``sigmoid + bias``, weighed by their sigmoid alone over the chosen's sum."""
    scores = jax.nn.sigmoid(n @ router)
    biased = scores + bias
    _, chosen = jax.lax.top_k(biased, k)
    top = jnp.take_along_axis(biased if wrong == "bias_weighs" else scores, chosen, -1)
    top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return (top if wrong == "no_routed_scale" else top * scaling), chosen


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
@_highest
def _latents(n, attn, rope, freqs, factor, eps, normed, lower):
    """q [seq, heads, nope + rope], c_kv [seq, rank] and k_rope [seq, rope],
    both rotated; ``freqs`` a tuple."""
    order = _rotary_order(rope)
    c_q = rms_norm(n @ _w(attn["q_a"]["kernel"], lower), _w(attn["q_norm"]["scale"], False), eps)
    q = jnp.einsum("tr,rhk->thk", c_q, _w(attn["q_b"]["kernel"], lower))
    nope = q.shape[-1] - rope
    q_rope = rotate_interleaved(q[..., nope:][..., order], freqs, factor)
    both = n @ _w(attn["kv_a"]["kernel"], lower)
    c_kv, k_r = both[:, :-rope], both[:, -rope:][:, order]
    if normed:
        c_kv = rms_norm(c_kv, _w(attn["kv_norm"]["scale"], False), eps)
    return (
        jnp.concatenate([q[..., :nope], q_rope], -1), c_kv,
        rotate_interleaved(k_r, freqs, factor))


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
    scaling = None if wrong == "plain_rope" else config.get("rope_scaling")
    freqs = tuple(float(f) for f in yarn_frequencies(rope, float(config["rope_theta"]), scaling))
    q, c_kv, k_rope = _latents(
        n, attn, rope, freqs, rotation_factor(config), config["rms_norm_eps"],
        wrong != "latent_unnormed", lower)
    scale = softmax_scale(config, wrong != "no_mscale")
    heads = [
        _attend_head(
            q[:, h], c_kv, k_rope, attn["k_up"]["kernel"][:, h], attn["v_up"]["kernel"][:, h],
            scale, lower)
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


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    eps = config["rms_norm_eps"]
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    dense = config["first_k_dense_replace"]
    for at in range(config["num_hidden_layers"]):
        p = _layer_of(program["first"], at) if at < dense else _layer_of(
            program["blocks"]["layers"], at - dense)
        h = x + _attention(_norm(x, p["ln_1"]["scale"], eps), p["attn"], config, wrong)
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
