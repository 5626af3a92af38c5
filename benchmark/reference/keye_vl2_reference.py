"""Keye-VL-2.0-30B-A3B's language model (``keye_vl2``): the forward pass in plain
``jax.numpy`` and float32 at the highest matmul precision: no kernels, no
cache, no batching, no scan, no sort but ``jax.lax.top_k``; a loop over layers,
over blocks of queries, over K/V heads and over experts. The yardstick the
serving path is compared with, at a small size on the CPU
(``tests/benchmark/test_bench_keye_vl2.py``) and, at the published widths on the
chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (Kwai-Keye/Keye-VL-2.0-30B-A3B, the
language model's keys and ``sa_config``). With ``n = RMSNorm(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * g``, a block is ``h = x + Attn(RMSNorm_1(x))``,
``y = h + MoE(RMSNorm_2(h))``:

* ``Attn``: ``q = n Wq`` (``num_attention_heads`` x ``head_dim``), ``k = n Wk``,
  ``v = n Wv`` (``num_key_value_heads`` x ``head_dim``), no bias; q and k
  RMS-normed per head, then rotated over all ``head_dim`` features, feature ``i``
  paired with ``i + head_dim / 2`` (``rotate_half``), base ``rope_theta``; query
  head ``i`` reads K/V head ``i // (heads / kv heads)``.
* the indexer (``sa_config``): ``qI = n WqI`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``kI = n WkI`` (one head), ``w = n Ww``
  (``indexer_num_heads``); qI and kI rotated the same way over their own
  features; ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
  Query ``t`` attends over the ``min(topk, t + 1)`` positions with the largest
  ``I(t, .)`` and no other (``jax.lax.top_k``: among equals the lower position),
  all its heads alike: their K and V rows are gathered, scores ``q k^T /
  sqrt(head_dim)``, softmax, ``Wo``.
* ``MoE``: ``p = softmax(n Wr)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest, divided by their sum (``norm_topk_prob``);
  ``sum_e p_e Wdown_e (silu(Wgate_e n) * Wup_e n)``; no shared expert.
* a final RMSNorm and an untied head ``hidden_size -> vocab_size``.

Assumed (no key of the source says; the configuration's file lists them with
their reasons): the per-head RMSNorm of q and k; that the indexer's three
projections read the normed hidden state; no norm on ``kI``; no scale factor on
``I``; ``q_chunk_size`` / ``kv_chunk_size`` are tile sizes and change no result.

Departures of this file from the published description:

* text only: one position stream (``mrope_section``'s three carry the same
  position for a text token), no vision tower;
* an exact zero of ``I`` counts as one value whatever its sign (+0 and -0 tie);
* the program stores an expert's gate and up projections side by side in one
  array; they are read apart here.

``wrong`` names one omission at a time, to show what the limit of the
comparison catches: ``"dense"`` (no selection: causal attention), ``"topk_half"``
(half of ``topk`` keys a query), ``"no_relu"`` (``I`` without its ReLU),
``"no_w"`` (``I`` summed over the indexer's heads without ``w``),
``"router_unnormalised"`` (the chosen experts' probabilities not divided by
their sum) and ``"fp8_weights"``: every weight matrix rounded to float8 (e4m3)
as it is read, the nearest precision below the bfloat16 the configuration
states.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256         # queries at a time: their gathered rows are [ROWS, topk, head_dim] a K/V head
LOWER = "fp8_weights"
WRONG = ("dense", "topk_half", "no_relu", "no_w", "router_unnormalised")


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


def rotate_half(x, base):
    """x: [seq, heads, dim] at positions 0..seq-1, every feature rotated,
    feature ``i`` with ``i + dim / 2``."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=F32) / half))
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _qkv(n, attn, at, base, eps, lower):
    """q [seq, heads, hd], k, v [seq, kv heads, hd] of layer ``at``."""
    q = jnp.einsum("td,dhk->thk", n, _w(attn["q"]["kernel"][at], lower))
    k = jnp.einsum("td,dhk->thk", n, _w(attn["k"]["kernel"][at], lower))
    v = jnp.einsum("td,dhk->thk", n, _w(attn["v"]["kernel"][at], lower))
    q = rms_norm(q, jnp.asarray(attn["q_norm"]["scale"][at], F32), eps)
    k = rms_norm(k, jnp.asarray(attn["k_norm"]["scale"][at], F32), eps)
    return rotate_half(q, base), rotate_half(k, base), v


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _indexer(n, index, at, base, lower):
    """qI [seq, heads, dim], kI [seq, dim], w [seq, heads] of layer ``at``."""
    qi = jnp.einsum("td,dhk->thk", n, _w(index["q"]["kernel"][at], lower))
    ki = n @ _w(index["k"]["kernel"][at], lower)
    w = n @ _w(index["w"]["kernel"][at], lower)
    return rotate_half(qi, base), rotate_half(ki[:, None], base)[:, 0], w


@functools.partial(jax.jit, static_argnums=(4, 5))
@_highest
def _select(qi, w, rows, ki, k, wrong):
    """For the queries at positions ``rows``: the ``k`` positions with the
    largest index score, and which of them are real (a query early in the
    sequence sees fewer than ``k``)."""
    dots = jnp.einsum("qhd,kd->qhk", qi, ki)
    if wrong != "no_relu":
        dots = jax.nn.relu(dots)
    score = dots.sum(1) if wrong == "no_w" else (dots * w[:, :, None]).sum(1)
    score = jnp.where(score == 0, 0.0, score)
    causal = jnp.arange(ki.shape[0])[None, :] <= rows[:, None]
    top, positions = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), k)
    return positions, top > -jnp.inf


@jax.jit
@_highest
def _attend_selected(q, k, v, positions, real):
    """Queries ``q`` [r, group, hd] over the rows ``positions`` [r, n] of one
    K/V head ``k``, ``v`` [seq, hd]."""
    ks, vs = k[positions], v[positions]                              # [r, n, hd]
    scores = jnp.einsum("qgd,qkd->qgk", q, ks) / np.sqrt(q.shape[-1])
    scores = jnp.where(real[:, None, :], scores, -jnp.inf)
    return jnp.einsum("qgk,qkd->qgd", jax.nn.softmax(scores, -1), vs)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _out(attended, attn, at, lower):
    return jnp.einsum("thk,hkd->td", attended, _w(attn["o"]["kernel"][at], lower))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _route(n, moe, at, k, normalise, lower):
    top, chosen = jax.lax.top_k(jax.nn.softmax(n @ _w(moe["router"][at], lower), -1), k)
    return (top / top.sum(-1, keepdims=True) if normalise else top), chosen


@functools.partial(jax.jit, static_argnums=(4,))
@_highest
def _expert(n, moe, at, e, lower):
    wi, wo = _w(moe["wi"][at, e], lower), _w(moe["wo"][at, e], lower)
    f = wo.shape[0]
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ wo


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _head(x, ln_f, head, eps, lower):
    return rms_norm(x, jnp.asarray(ln_f, F32), eps) @ _w(head, lower)


def _selection(n, layers, at: int, config, wrong) -> List[Any]:
    """``(positions [r, k], real [r, k])`` for each block of ``ROWS`` queries."""
    lower, seq = wrong == LOWER, n.shape[0]
    topk = config["sa_config"]["topk"] // (2 if wrong == "topk_half" else 1)
    k = seq if wrong == "dense" else min(topk, seq)
    qi, ki, w = _indexer(n, layers["index"], at, float(config["rope_theta"]), lower)
    return [
        _select(qi[a:a + ROWS], w[a:a + ROWS], jnp.arange(a, min(a + ROWS, seq)), ki, k, wrong)
        for a in range(0, seq, ROWS)
    ]


def _block(x, layers, at: int, config, wrong, selections=None):
    lower = wrong == LOWER
    eps = config["rms_norm_eps"]
    n = _norm(x, layers["ln_1"]["scale"][at], eps)
    q, k, v = _qkv(n, layers["attn"], at, float(config["rope_theta"]), eps, lower)
    chosen = _selection(n, layers, at, config, wrong)
    if selections is not None:
        selections.append(chosen)
    kv_heads = k.shape[1]
    group = q.shape[1] // kv_heads
    heads = []
    for j in range(kv_heads):
        qj = q[:, j * group:(j + 1) * group]
        heads.append(jnp.concatenate([
            _attend_selected(qj[i * ROWS:(i + 1) * ROWS], k[:, j], v[:, j], positions, real)
            for i, (positions, real) in enumerate(chosen)
        ], axis=0))
    h = x + _out(jnp.concatenate(heads, axis=1), layers["attn"], at, lower)
    n = _norm(h, layers["ln_2"]["scale"][at], eps)
    top, picked = _route(
        n, layers["moe"], at, config["num_experts_per_tok"],
        bool(config["norm_topk_prob"]) and wrong != "router_unnormalised", lower)
    out = h
    for e in range(config["num_experts"]):
        weight = jnp.where(picked == e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(
            out + weight[:, None] * _expert(n, layers["moe"], at, e, lower))
    return out


def _hidden(program, tokens, config, wrong, selections=None):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    for at in range(config["num_hidden_layers"]):
        x = _block(x, program["blocks"]["layers"], at, config, wrong, selections)
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
    seq = len(tokens)
    out = np.zeros((len(selections), seq, seq), bool)
    for at, blocks in enumerate(selections):
        for i, (positions, real) in enumerate(blocks):
            positions, real = np.asarray(positions), np.asarray(real)
            rows = np.broadcast_to(np.arange(len(positions))[:, None] + i * ROWS, positions.shape)
            out[at, rows[real], positions[real]] = True
    return out


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
