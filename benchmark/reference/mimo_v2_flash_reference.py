"""MiMo-V2-Flash (``mimo_v2_flash``): the forward pass in plain ``jax.numpy`` and
float32 at the highest matmul precision: no kernels, no cache, no window store, no
scan, no sort but ``jax.lax.top_k``; a loop over layers, over heads, over blocks of
queries and over experts, each freed before the next. The yardstick the serving path
is compared with, at a small size on the CPU
(``tests/benchmark/test_bench_mimo_v2_flash.py``) and, at the published widths on the
chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (XiaomiMiMo/MiMo-V2-Flash). With ``RMSNorm(x)
= x / sqrt(mean(x^2) + layernorm_epsilon) * g``, a layer is ``h = x +
Attn(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``:

* ``hybrid_layer_pattern[l]`` 1, a **sliding** layer: ``q = W_q n``
  (``swa_num_attention_heads`` heads of ``swa_head_dim``), ``k = W_k n``
  (``swa_num_key_value_heads`` of ``swa_head_dim``), ``v = W_v n`` (of
  ``swa_v_head_dim``); the first ``int(partial_rotary_factor x head_dim)`` features of
  every q and k head rotate at ``swa_rope_theta`` (``rotate_half`` over those features:
  feature ``i`` with ``i + half``), the others do not; query ``t`` of head ``h`` sees
  the keys ``t - sliding_window < s <= t`` with ``p_ts = exp(q_t . k_s / sqrt(head_dim))
  / (exp(b_h) + sum_s' exp(q_t . k_s' / sqrt(head_dim)))``, ``b_h`` the layer's
  ``attention_sink_bias`` (``add_swa_attention_sink_bias``); ``a_t =
  attention_value_scale x sum_s p_ts v_s``; ``W_o [a_1 .. a_H]``;
* pattern 0, a **full** layer: the same with ``num_key_value_heads`` K/V heads,
  ``rope_theta``, every ``s <= t`` and no sink (``add_full_attention_sink_bias``
  false);
* ``moe_layer_freq[l]`` 0 (layer 0): ``W_d (silu(W_g n) * W_u n)`` of
  ``intermediate_size``; 1: ``s = sigmoid(n W_r)`` over all routed experts, the
  ``num_experts_per_tok`` with the largest ``s + e_score_correction_bias`` chosen
  (``noaux_tc``; ``n_group`` 1 limits nothing), weights the chosen ``s`` over their
  sum (``norm_topk_prob``), times ``routed_scaling_factor`` (null: 1); each expert a
  gated-SiLU MLP of ``moe_intermediate_size``; no shared expert;
* a final RMSNorm and an untied head.

It is given the share the chip holds: the routed experts ``expert_offset ..
expert_offset + n_routed_experts - 1`` of the ``router_experts`` the router scores
(what the absent ones would add is left out, as in the program) and the first
``vocab_size`` rows of the vocabulary; and the program's own weights, read as they
lie (layer 0 under ``first``, the layers behind it by period under ``periods``, the
experts in one stack; an MLP's or an expert's gate and up projection side by side).

``wrong`` names one omission at a time, to show what the limit of the comparison
catches: ``"no_sink"`` (the sink left out of the denominator), ``"no_value_scale"``,
``"window_one_short"`` (a window of ``sliding_window - 1`` keys), ``"bases_swapped"``
(each kind of layer rotates at the other's base), ``"rotate_all"`` (rotation over all
``head_dim`` features), ``"no_router_bias"`` (the eight largest scores, unbiased),
``"bf16_scores"`` (a head's logits, their exponentials and the normalised weights
each rounded to bfloat16) and ``"fp8_weights"``: every weight matrix rounded to
float8 (e4m3) as it is read, the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 1024         # queries attended at a time: [ROWS, seq] scores a head
COLUMNS = 2048      # of the dense layer's width at a time: one expert's worth
LOWER = "fp8_weights"
WRONG = (
    "no_sink", "no_value_scale", "window_one_short", "bases_swapped", "rotate_all",
    "no_router_bias", "bf16_scores")


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _w(a, lower: bool):
    """A piece of the program's weights in float32; ``lower`` rounds it to float8
    (e4m3) first."""
    return jnp.asarray(a.astype(jnp.float8_e4m3fn) if lower else a, F32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * jnp.asarray(scale, F32)


def rotate(x, theta: float, features: int):
    """``x`` [seq, heads, d] at positions 0, 1, ...: of its first ``features``
    features, feature ``i`` of the first half turns with feature ``i + features / 2``
    by ``position x theta^(-2i / features)``; the others are left as they are."""
    half = features // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * (
        theta ** (-jnp.arange(half, dtype=F32) / half))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:features]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., features:]], -1)


def expert(n, wi, wo):
    """``W_d (silu(W_g n) * W_u n)``, gate and up side by side in ``wi``."""
    f = wo.shape[0]
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ wo


def route(n, router, bias, k: int, scaling: float, wrong: Optional[str] = None):
    """``(weights [seq, k], chosen [seq, k])``: the ``k`` experts with the largest
    ``sigmoid + bias``, weighed by their sigmoid alone over the chosen's sum."""
    scores = jax.nn.sigmoid(n @ router)
    _, chosen = jax.lax.top_k(scores if wrong == "no_router_bias" else scores + bias, k)
    top = jnp.take_along_axis(scores, chosen, -1)
    return top / (top.sum(-1, keepdims=True) + 1e-20) * scaling, chosen


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, scale, eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
@_highest
def _projected(n, attn, theta, features, lower):
    """q [seq, heads, d] and k [seq, kv, d], both rotated, and v [seq, kv, dv]."""
    q, k, v = (jnp.einsum("td,dhk->thk", n, _w(attn[name]["kernel"], lower)) for name in "qkv")
    return rotate(q, theta, features), rotate(k, theta, features), v


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
@_highest
def _attend_head(q, k, v, sink, window, use_sink, bf16):
    """One query head: ``q`` [seq, d] over its K/V head's ``k`` [seq, d] and ``v``
    [seq, dv], ``ROWS`` queries at a time; ``window`` 0 is every key up to the
    query's own."""
    seq, d = q.shape
    at = jnp.arange(seq)
    out = []
    for a in range(0, seq, ROWS):
        rows = jnp.arange(a, min(a + ROWS, seq))
        scores = (q[a:a + ROWS] @ k.T) / np.sqrt(d)
        seen = at[None, :] <= rows[:, None]
        if window:
            seen &= at[None, :] > rows[:, None] - window
        if bf16:
            scores = scores.astype(jnp.bfloat16).astype(F32)
        scores = jnp.where(seen, scores, -jnp.inf)
        top = scores.max(-1, keepdims=True)
        top = jnp.maximum(top, sink) if use_sink else top
        weight = jnp.exp(scores - top)
        if bf16:
            weight = weight.astype(jnp.bfloat16).astype(F32)
        total = weight.sum(-1, keepdims=True) + (jnp.exp(sink - top) if use_sink else 0.0)
        weight = weight / total
        if bf16:
            weight = weight.astype(jnp.bfloat16).astype(F32)
        out.append(weight @ v)
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
@_highest
def _out(attended, o, value_scale, lower):
    return jnp.einsum("thv,hvd->td", value_scale * attended, _w(o, lower))


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
    return rms_norm(x, ln_f, eps) @ _w(head, lower)


def rotary_features(config) -> int:
    return int(config["partial_rotary_factor"] * config["head_dim"])


def _attention(n, attn, config, sliding: bool, wrong):
    lower = wrong == LOWER
    bases = (float(config["swa_rope_theta"]), float(config["rope_theta"]))
    theta = bases[sliding if wrong == "bases_swapped" else not sliding]
    features = config["head_dim"] if wrong == "rotate_all" else rotary_features(config)
    q, k, v = _projected(n, attn, theta, features, lower)
    window = config["sliding_window"] - (wrong == "window_one_short") if sliding else 0
    use_sink = sliding and wrong != "no_sink" and config["add_swa_attention_sink_bias"]
    groups = q.shape[1] // k.shape[1]
    heads = []
    for h in range(q.shape[1]):
        sink = jnp.asarray(attn["sinks"][h], F32) if sliding else jnp.zeros((), F32)
        # one head's scores at a time: dispatched ahead, each holds its buffers
        heads.append(jax.block_until_ready(_attend_head(
            q[:, h], k[:, h // groups], v[:, h // groups], sink, window, use_sink,
            wrong == "bf16_scores")))
    scale = 1.0 if wrong == "no_value_scale" else float(config["attention_value_scale"])
    return _out(jnp.stack(heads, 1), attn["o"]["kernel"], scale, lower)


def _dense(n, mlp, lower):
    """Layer 0's gated MLP, ``COLUMNS`` of its width at a time."""
    wi, wo = mlp["wi"], mlp["wo"]
    f = wo.shape[0]
    out = 0.0
    for a in range(0, f, COLUMNS):
        b = min(a + COLUMNS, f)
        piece = jnp.concatenate([wi[:, a:b], wi[:, f + a:f + b]], 1)
        out = jax.block_until_ready(out + _expert(n, piece, wo[a:b], lower))
    return out


def _experts(n, moe, wi, wo, config, wrong):
    """The held experts' part of the routed sum."""
    lower = wrong == LOWER
    top, chosen = _route(
        n, moe["router"], moe["bias"], config["num_experts_per_tok"],
        float(config["routed_scaling_factor"] or 1.0), wrong, lower)
    out = 0.0
    for e in range(wi.shape[0]):
        weight = jnp.where(chosen == config.get("expert_offset", 0) + e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(out + weight[:, None] * _expert(n, wi[e], wo[e], lower))
    return out


def layer_weights(program, config, at: int):
    """Layer ``at``'s piece of the program's tree: ``(its block, whether it slides,
    its experts' (wi, wo) or None)``. Behind layer 0 the layers lie by period, a
    period's sliding layers stacked and its full layer beside them."""
    if at == 0:
        return program["first"], False, None
    pattern = config["hybrid_layer_pattern"]
    period = pattern[1:].index(0) + 1
    of, i = divmod(at - 1, period)
    sliding = bool(pattern[at])
    assert sliding == (i < period - 1), (pattern, at)
    stack = program["periods"]["sliding" if sliding else "full"]
    block = jax.tree.map(lambda a: a[of, i] if sliding else a[of], stack)
    return block, sliding, tuple(program["experts"][name][at - 1] for name in ("wi", "wo"))


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    eps = config["layernorm_epsilon"]
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    for at in range(config["num_hidden_layers"]):
        p, sliding, experts = layer_weights(program, config, at)
        h = x + _attention(_norm(x, p["ln_1"]["scale"], eps), p["attn"], config, sliding, wrong)
        n = _norm(h, p["ln_2"]["scale"], eps)
        assert bool(config["moe_layer_freq"][at]) == (experts is not None), at
        x = h + (_dense(n, p["mlp"], wrong == LOWER) if experts is None else _experts(
            n, p["moe"], *experts, config, wrong))
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one sequence
    ``tokens`` [seq], from the program's own weights; ``config`` is the
    configuration's file."""
    x = _hidden(program, tokens, config, wrong)
    return _head(
        x[-last:], program["ln_f"]["scale"], program["head"]["kernel"],
        config["layernorm_epsilon"], wrong == LOWER)


def expert_layer(n, moe, wi, wo, config):
    """One expert layer's share for ``n`` [seq, hidden] in float32: what the experts
    ``wi``, ``wo`` [held, ...] from ``config["expert_offset"]`` on give. For the test
    that adds the sixteen shares up to the uncut layer."""
    return _experts(jnp.asarray(n, F32), moe, wi, wo, config, None)


def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.take_along_axis(logp, jnp.asarray(tokens)[1:, None], -1)[..., 0].mean()


def program_loss(program, tokens, config) -> float:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq] from the program's
    own weights, one sequence at a time. The benchmark trains no such model; the
    harness's contract lists the entry point."""
    rows = [
        float(next_token_loss(program_logits(program, row, config, len(row)), row))
        for row in tokens
    ]
    return sum(rows) / len(rows)
