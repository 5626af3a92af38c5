"""LFM2-24B-A2B (``lfm2_moe``): the forward pass and the loss in plain
``jax.numpy`` and float32 at the highest matmul precision: no kernel, no scan,
no remat, no sort and no grouped matmul; a loop over layers, over blocks of
queries and over the held experts. The yardstick the train path is compared
with: at a small size on the CPU, loss and every parameter's gradient
(``tests/test_lfm2_moe.py``, ``tests/benchmark/test_bench_lfm2_moe.py``), and at
the published widths on the chip in every run's set-up, the first step's loss
(:func:`program_loss`).

It follows the published ``config.json`` (LiquidAI/LFM2-24B-A2B) and the
family's description. With ``RMSNorm(x) = x * rsqrt(mean(x^2) + norm_eps) * w``,
every layer is ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``:

* mixer ``conv``: ``[B, C, x] = W_in r``; ``u = B * x``; ``v_t = sum_j w_j *
  u_(t - (L - 1) + j)``, ``j = 0 .. L - 1`` (``conv_L_cache`` taps, a causal
  depthwise convolution: zeros before the sequence, ``conv_bias`` false, no
  activation), written as ``L`` shifted products; ``W_out (C * v)``;
* mixer ``full_attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads of ``hidden_size / num_attention_heads``
  features; q and k RMS-normed over a head's features (one learned scale each);
  rotary over the whole head, pairs ``(i, i + dim / 2)`` (rotate-half),
  ``rope_theta``; a full causal softmax of ``q.k / sqrt(dim)``, a block of
  queries at a time; ``W_o``;
* layer ``l < num_dense_layers``: ``W_2 (silu(W_1 r) * W_3 r)`` of width
  ``intermediate_size``;
* every other layer: ``s = sigmoid(r W_g)`` over all ``router_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``use_expert_bias``:
  ``b`` chooses and weighs nothing); weights ``s_e / sum of the chosen s``
  (``norm_topk_prob``; **no epsilon in the denominator**: it would move a weight
  by under 1e-6 of itself) times ``routed_scaling_factor``; expert ``e`` is
  ``W_2^e (silu(W_1^e r) * W_3^e r)`` of width ``moe_intermediate_size``. The
  layer is **a loop over the held experts**, each applied to every token and
  weighed by a dense 0-or-weight mask a token;
* ``embedding_norm`` behind the last layer, the head tied to the embedding; the
  loss is the mean next-token cross-entropy, from full logits.

It is given the share the chip holds: the experts ``expert_offset ..
expert_offset + num_experts - 1`` of the ``router_experts`` the router scores
(what the absent ones would add is left out, as in the program) and the first
``vocab_size`` rows of the vocabulary.

Departures of the program under test, which the comparison accounts for: none
in the mathematics. The program stores a matrix as ``[in, out]``, the taps as
``[L, hidden]`` and an MLP's or an expert's ``W_1`` and ``W_3`` side by side in
one array (``wi``); they are read as they lie.

``wrong`` names one omission at a time, to show what the comparison's limit
catches: ``"no_bias"`` (the bias ignored), ``"unnormalised"`` (the chosen
scores not divided by their sum), ``"capacity"`` (an expert's pairs past 1.25 x
the mean pairs an expert dropped, in token order), ``"no_conv_gate"`` (``B *``
left out of the first conv layer), ``"no_k_norm"`` (k not normed),
and ``"fp8_weights"``: every weight matrix rounded to float8 (e4m3) as it is
read, the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024        # queries attended, and rows of logits made, at a time
EXPERTS = 4        # experts of the program's stack turned to float32 at a time
LOWER = "fp8_weights"
WRONG = ("no_bias", "unnormalised", "capacity", "no_conv_gate", "no_k_norm")
CAPACITY_FACTOR = 1.25


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """``x`` [batch, seq, heads, dim], positions 0 .. seq - 1, rotate-half."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def conv_mixer(r, p, gated: bool = True):
    """``r`` [batch, seq, hidden]; the convolution as shifted products."""
    gate_in, gate_out, x = jnp.split(r @ p["in"], 3, axis=-1)
    u = gate_in * x if gated else x
    taps, seq = p["conv"], r.shape[1]
    v = jnp.zeros_like(u)
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j          # tap j reads the input ``back`` steps ago
        v = v + taps[j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :seq]
    return (gate_out * v) @ p["out"]


def attention_mixer(r, p, model: Dict[str, Any], k_normed: bool = True):
    batch, seq, _ = r.shape
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    dim, eps = model["hidden_size"] // heads, model["norm_eps"]
    q = (r @ p["q"]).reshape(batch, seq, kv, heads // kv, dim)
    k = (r @ p["k"]).reshape(batch, seq, kv, dim)
    v = (r @ p["v"]).reshape(batch, seq, kv, dim)
    q = rms_norm(q, p["q_norm"], eps)
    k = rms_norm(k, p["k_norm"], eps) if k_normed else k
    q = rope(q.reshape(batch, seq, heads, dim), model["rope_theta"]).reshape(q.shape)
    k = rope(k, model["rope_theta"])
    at = jnp.arange(seq)
    blocks = []
    for start in range(0, seq, ROWS):
        rows = slice(start, start + ROWS)
        scores = jnp.einsum("bqgnd,bkgd->bgnqk", q[:, rows], k) / math.sqrt(dim)
        scores = jnp.where(at[rows, None] >= at[None, :], scores, -jnp.inf)
        blocks.append(jnp.einsum("bgnqk,bkgd->bqgnd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(blocks, 1).reshape(batch, seq, heads * dim) @ p["o"]


def gated_mlp(r, wi, wo):
    """``W_2 (silu(W_1 r) * W_3 r)``, ``W_1`` and ``W_3`` side by side in ``wi``."""
    width = wo.shape[0]
    both = r @ wi
    return (jax.nn.silu(both[..., :width]) * both[..., width:]) @ wo


def route(r, router, bias, model: Dict[str, Any], wrong: Optional[str] = None):
    """``(weights, chosen)`` [..., k] over all the experts the router scores."""
    scores = jax.nn.sigmoid(r @ router)
    _, chosen = jax.lax.top_k(scores if wrong == "no_bias" else scores + bias,
                              model["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, -1)
    if wrong != "unnormalised":
        top = top / top.sum(-1, keepdims=True)
    return top * model["routed_scaling_factor"], chosen


def expert_mask(weights, chosen, expert: int, model, wrong: Optional[str] = None):
    """The dense mask of one expert: a token's weight for it, 0 where it did not
    choose it. ``"capacity"`` drops the expert's pairs past 1.25 x the mean."""
    hit = chosen == expert
    if wrong == "capacity":
        pairs = hit.size
        capacity = math.ceil(CAPACITY_FACTOR * pairs / model["router_experts"])
        nth = jnp.cumsum(hit.reshape(-1)).reshape(hit.shape)
        hit = hit & (nth <= capacity)
    return (weights * hit).sum(-1)


def held_experts(r, weights, chosen, wi, wo, first: int, model, wrong=None):
    """What the experts ``first .. first + len(wi) - 1`` (``wi`` [n, hidden, 2 x
    width], ``wo`` [n, width, hidden]) add for the tokens that chose them."""
    out = jnp.zeros_like(r)
    for i in range(wi.shape[0]):
        mask = expert_mask(weights, chosen, first + i, model, wrong)
        out = out + mask[..., None] * gated_mlp(r, wi[i], wo[i])
    return out


def mixed(x, p, model: Dict[str, Any], wrong: Optional[str] = None, first: bool = False):
    """``x + Mixer(RMSNorm(x))``; ``first`` says the model's first layer, whose
    convolution ``"no_conv_gate"`` leaves ungated."""
    r = rms_norm(x, p["ln_1"], model["norm_eps"])
    if "conv" in p:
        return x + conv_mixer(r, p, gated=not (wrong == "no_conv_gate" and first))
    return x + attention_mixer(r, p, model, k_normed=wrong != "no_k_norm")


def layer(x, p, bias, at: int, model: Dict[str, Any], wrong: Optional[str] = None):
    """Layer ``at`` on ``x`` [batch, seq, hidden]; ``bias`` None says a dense layer."""
    x = mixed(x, p, model, wrong, first=at == 0)
    r = rms_norm(x, p["ln_2"], model["norm_eps"])
    if bias is None:
        return x + gated_mlp(r, p["wi"], p["wo"])
    weights, chosen = route(r, p["router"], bias, model, wrong)
    return x + held_experts(
        r, weights, chosen, p["wi"], p["wo"], model["expert_offset"], model, wrong)


def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].mean()


def hidden(params, tokens, model, wrong=None):
    """The final hidden rows [batch, seq, hidden], behind ``embedding_norm``."""
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens]
        for at, (p, bias) in enumerate(params["layers"]):
            x = layer(x, p, bias, at, model, wrong)
        return rms_norm(x, params["ln_f"], model["norm_eps"])


def forward(params, tokens, model, wrong=None):
    """Logits [batch, seq, vocab]; ``params`` as :func:`from_program_params` gives them."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, model, wrong) @ params["wte"].T


def loss(params, tokens, model, wrong=None):
    return next_token_loss(forward(params, tokens, model, wrong), tokens)


# -- the program's weights, read as they lie -----------------------------------------


def _f32(a, lower: bool = False):
    a = jnp.asarray(a)
    if lower and a.ndim >= 2:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(F32)


def program_layers(program) -> Iterator[Tuple[Any, Any]]:
    """``(layer, bias)`` of every layer in order, from the program's tree: the
    dense layers (``first``), then period by period (``periods``: one stacked
    tree a layer of the period), then ``tail``; bias None for a dense layer."""
    for p in program["first"]:
        yield p, None
    stacked, biases = program["periods"], program["expert_bias"]
    for n in range(jax.tree.leaves(stacked)[0].shape[0] if stacked else 0):
        for p, bias in zip(stacked, biases["periods"]):
            yield jax.tree.map(lambda a: a[n], p), bias[n]
    yield from zip(program["tail"], biases["tail"])


def from_program_params(program, lower: bool = False) -> Dict[str, Any]:
    """The program's parameter tree as the reference's: float32, one entry a layer."""
    cast = functools.partial(_f32, lower=lower)
    return {
        "wte": cast(program["wte"]), "ln_f": cast(program["ln_f"]),
        "layers": [
            (jax.tree.map(cast, p), None if bias is None else _f32(bias))
            for p, bias in program_layers(program)],
    }


# -- at published widths, beside the step's state on the chip -----------------------
#
# The same functions, a piece at a time: each jitted call turns one layer's
# weights without its experts, or ``EXPERTS`` experts, into float32 and applies
# them, so no float32 copy of a whole expert layer (1.2 GB) exists on the chip.


def _model(config: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in config.items() if not isinstance(v, (dict, list))}


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _mixer_and_route(x, p, bias, first, model_items, wrong, lower):
    """The layer's mixer, and for an expert layer its routing: ``(h, r, weights,
    chosen)``; for a dense layer the whole layer and Nones."""
    model = dict(model_items)
    p = jax.tree.map(functools.partial(_f32, lower=lower), p)
    with jax.default_matmul_precision("highest"):
        if bias is None:
            return layer(x, p, None, 0 if first else 1, model, wrong), None, None, None
        x = mixed(x, p, model, wrong, first)
        r = rms_norm(x, p["ln_2"], model["norm_eps"])
        return (x, r) + route(r, p["router"], _f32(bias), model, wrong)


@functools.partial(jax.jit, static_argnums=(7, 8, 9), donate_argnums=(0,))
def _add_experts(x, r, weights, chosen, wi, wo, first, model_items, wrong, lower):
    """``first`` (traced: one program for every chunk) is the chunk's first expert."""
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        return x + held_experts(
            r, weights, chosen, _f32(wi, lower), _f32(wo, lower),
            model["expert_offset"] + first, model, wrong)


@functools.partial(jax.jit, static_argnums=(3,))
def _rows_nll(x, wte, targets, lower):
    """Summed cross-entropy of the rows ``x`` [n, hidden] against ``targets`` [n]."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ _f32(wte, lower).T, -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1).sum()


def program_hidden(program, tokens, config, wrong=None, lower=False):
    """The final hidden rows [batch, seq, hidden] from the program's own weights."""
    model = _model(config)
    items = tuple(sorted(model.items()))
    x = _f32(program["wte"][jnp.asarray(tokens)], lower)
    for at, (p, bias) in enumerate(program_layers(program)):
        if bias is None:
            x = _mixer_and_route(x, p, None, at == 0, items, wrong, lower)[0]
            continue
        p = dict(p)
        wi, wo = p.pop("wi"), p.pop("wo")
        x, r, weights, chosen = _mixer_and_route(x, p, bias, at == 0, items, wrong, lower)
        for first in range(0, wi.shape[0], EXPERTS):
            rows = slice(first, first + EXPERTS)
            x = _add_experts(x, r, weights, chosen, wi[rows], wo[rows], first, items, wrong, lower)
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(program["ln_f"]), model["norm_eps"])


def program_logits(program, tokens, config, last: int, wrong=None, lower=False):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights."""
    x = program_hidden(program, jnp.asarray(tokens)[None], config, wrong, lower)[0, -last:]
    with jax.default_matmul_precision("highest"):
        return x @ _f32(program["wte"], lower).T


def program_loss(program, tokens, config, wrong=None, lower=False) -> float:
    """:func:`loss` of ``tokens`` [batch, seq] from the program's own weights, one
    sequence and ``ROWS`` rows of logits at a time; ``config`` is the
    configuration's file."""
    total, count = 0.0, 0
    for row in tokens:
        x = program_hidden(program, row[None], config, wrong, lower)[0, :-1]
        for start in range(0, x.shape[0], ROWS):
            rows = slice(start, start + ROWS)
            total += float(_rows_nll(x[rows], program["wte"], row[1:][rows], lower))
        count += x.shape[0]
    return total / count
