"""Command A+ (``cohere2_moe``): the language model's forward pass in plain
``jax.numpy`` and float32 at the highest matmul precision: no kernels, no
cache, no scan, no sort; a loop over layers, over K/V heads and over experts.
The yardstick the serving path is compared with, at a small size on the CPU
(``tests/benchmark/test_bench_cohere2_moe.py``) and, at the published widths on
the chip, in every run's set-up (``program_logits``, below).

It follows the published ``config.json`` (CohereLabs/command-a-plus-05-2026).
With ``h = LN(x)`` (mean subtracted, a scale, no bias, ``layer_norm_eps``), a
block is ``x + Attn(h) + FFN(h)`` (``use_parallel_block``):

* ``Attn``: ``q = h Wq`` (``num_attention_heads`` x ``head_dim``), ``k = h Wk``,
  ``v = h Wv`` (``num_key_value_heads`` x ``head_dim``), no bias, no QK norm;
  query head ``i`` reads K/V head ``i // (heads / kv heads)``; scores
  ``q k^T / sqrt(head_dim)``, softmax, ``Wo``. Layer ``i`` is a *full* layer
  where ``(i + 1) % layer_switch == 0`` and a *sliding* layer otherwise
  (``order_of_interleaved_layers`` ``local_attn_first``). A sliding layer
  rotates q and k by RoPE over *interleaved* pairs ``(2i, 2i + 1)`` of all
  ``head_dim`` features (``position_embedding_type`` ``rope_gptj``,
  ``rotary_pct`` 1, base ``rope_theta``) and masks ``j <= i and i - j <
  sliding_window``; a full layer applies **no** position embedding and masks
  ``j <= i``.
* ``FFN``: ``s = sigmoid(h Wr)`` over all ``router_experts``; the
  ``num_experts_per_tok`` largest are chosen; ``w_e = s_e /`` (sum of the
  chosen); ``routed = sum_e w_e E_e(h)`` with ``E(h) = Wdown (silu(Wgate h) *
  Wup h)``; ``shared`` = the mean of ``num_shared_experts`` experts of the same
  form that every token passes through; ``FFN = routed + shared``.
* a final LayerNorm, ``logits = logit_scale x h Wemb^T`` (embedding tied).

It is given the share the chip holds: ``num_experts`` routed experts from
``expert_offset`` on (what the absent experts would add is left out, as in the
program: that partial sum is what goes on to the next layer) and the first
``vocab_size`` rows of the vocabulary.

Assumed (the source's keys do not say; the configuration's file lists them):
that full layers have no position embedding (the Cohere2 family's, and the
catalog's description); that a shared expert is ``intermediate_size`` wide; that
``shared_expert_combination_strategy`` ``average`` is the mean over the shared
experts, added to the routed sum.

Departures of the program under test, which the comparison accounts for:

* the program rotates *half-split* pairs ``(i, i + head_dim / 2)``: the
  published rotation under a fixed permutation of each head's features,
  applied alike to q and k, which leaves every q.k product unchanged;
  :func:`layer_from_program` applies that permutation to the q and k kernels;
* the program stores an expert's gate and up projections side by side in one
  array; they are read apart here.

``wrong`` names one omission at a time, to show what the limit of the
comparison catches: ``"window_off"`` (sliding layers see everything before
them), ``"rope_on_full"`` (full layers rotate too), ``"shared_summed"`` (the
shared experts are summed, not averaged). ``program_logits`` knows one more,
``"fp8_weights"``: every weight matrix rounded to float8 (e4m3) as it is read,
the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 1024        # queries attended at a time: [heads of a group, ROWS, seq] scores
WRONG = ("window_off", "rope_on_full", "shared_summed")
LOWER = "fp8_weights"


def _f32(a):
    return jnp.asarray(a, F32)


def layer_norm(x, scale, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def rotary_interleaved(x, base):
    """x: [seq, heads, head_dim], positions 0..seq-1, every feature rotated."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=F32) / half))
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]     # [seq, half]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attend_rows(q, rows, k, v, window):
    """Queries ``q`` [r, group, head_dim] at positions ``rows`` [r] over one
    K/V head ``k``, ``v`` [seq, head_dim]; ``window`` None sees all before."""
    scores = jnp.einsum("qgd,kd->gqk", q, k) / np.sqrt(q.shape[-1])
    behind = rows[:, None] - jnp.arange(k.shape[0])[None, :]
    mask = behind >= 0 if window is None else (behind >= 0) & (behind < window)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, -1), v)


def attention(h, layer, model, sliding: bool, wrong: Optional[str] = None):
    """``h`` [seq, hidden] -> [seq, hidden], one K/V head and ``ROWS`` queries
    at a time."""
    seq = h.shape[0]
    q = jnp.einsum("td,dhk->thk", h, layer["q"])
    k = jnp.einsum("td,dhk->thk", h, layer["k"])
    v = jnp.einsum("td,dhk->thk", h, layer["v"])
    if sliding or wrong == "rope_on_full":
        q, k = (rotary_interleaved(x, float(model["rope_theta"])) for x in (q, k))
    window = model["sliding_window"] if sliding and wrong != "window_off" else None
    kv_heads = k.shape[1]
    group = q.shape[1] // kv_heads
    heads = []
    for j in range(kv_heads):
        qj = q[:, j * group:(j + 1) * group]
        blocks = [
            attend_rows(qj[a:a + ROWS], jnp.arange(a, min(a + ROWS, seq)), k[:, j], v[:, j], window)
            for a in range(0, seq, ROWS)
        ]
        heads.append(jnp.concatenate(blocks, axis=0))
    return jnp.einsum("thk,hkd->td", jnp.concatenate(heads, axis=1), layer["o"])


def expert(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def ffn(h, layer, model, wrong: Optional[str] = None):
    """``h`` [seq, hidden]: the held experts' part of the routed sum, plus the
    mean of the shared experts."""
    scores = jax.nn.sigmoid(h @ layer["router"])                 # [seq, router_experts]
    top, chosen = jax.lax.top_k(scores, model["num_experts_per_tok"])
    top = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e, (gate, up, down) in enumerate(layer["experts"]):
        weight = jnp.where(chosen == model["expert_offset"] + e, top, 0.0).sum(-1)
        out = out + weight[:, None] * expert(h, gate, up, down)
    shared = sum(expert(h, *w) for w in layer["shared"])
    return out + (shared if wrong == "shared_summed" else shared / len(layer["shared"]))


def block(x, layer, model, sliding: bool, wrong: Optional[str] = None):
    h = layer_norm(x, layer["ln"], model["layer_norm_eps"])
    return x + attention(h, layer, model, sliding, wrong) + ffn(h, layer, model, wrong)


def is_sliding(model, i: int) -> bool:
    return (i + 1) % model["layer_switch"] != 0


def head(x, ln_f, wte, model):
    return model["logit_scale"] * (layer_norm(x, ln_f, model["layer_norm_eps"]) @ wte.T)


def forward(params, tokens, model, wrong: Optional[str] = None):
    """Logits [seq, vocab] for one sequence ``tokens`` [seq]; ``params`` as
    :func:`from_program_params` returns them, ``model`` the configuration's
    keys as run."""
    assert wrong is None or wrong in WRONG, wrong
    with jax.default_matmul_precision("highest"):
        x = params["wte"][jnp.asarray(tokens)]
        for i, layer in enumerate(params["layers"]):
            x = block(x, layer, model, is_sliding(model, i), wrong)
        return head(x, params["ln_f"], params["wte"], model)


def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.take_along_axis(logp, jnp.asarray(tokens)[1:, None], -1)[..., 0].mean()


# -- the program's own weights as the reference's ----------------------------


def _rotary_order(head_dim: int):
    """published feature 2i <- program feature i; 2i+1 <- program feature i + half"""
    order = np.arange(head_dim)
    order[0::2], order[1::2] = np.arange(head_dim // 2), np.arange(head_dim // 2) + head_dim // 2
    return order


def layer_from_program(p) -> Dict[str, Any]:
    """One layer of the program's tree (its leaves without the layer axis):
    float32, q and k in the published feature order, gate and up apart."""
    order = _rotary_order(p["attn"]["q"]["kernel"].shape[-1])
    f = p["moe"]["wo"].shape[-2]

    def experts(group):
        wi, wo = _f32(group["wi"]), _f32(group["wo"])
        return [(wi[e, :, :f], wi[e, :, f:], wo[e]) for e in range(wi.shape[0])]

    return {
        "ln": _f32(p["ln"]["scale"]),
        "q": _f32(p["attn"]["q"]["kernel"])[..., order],
        "k": _f32(p["attn"]["k"]["kernel"])[..., order],
        "v": _f32(p["attn"]["v"]["kernel"]), "o": _f32(p["attn"]["o"]["kernel"]),
        "router": _f32(p["moe"]["router"]),
        "experts": experts(p["moe"]), "shared": experts(p["shared"]),
    }


def from_program_params(program, model) -> Dict[str, Any]:
    layers = program["blocks"]["layers"]
    return {
        "wte": _f32(program["wte"]["embedding"]),
        "layers": [
            layer_from_program(jax.tree.map(lambda a: a[i], layers))
            for i in range(model["num_hidden_layers"])
        ],
        "ln_f": _f32(program["ln_f"]["scale"]),
    }


# -- at published widths, beside the system on the chip ----------------------
#
# The same functions a piece at a time: each jitted call turns one piece of the
# program's weights (as served, bfloat16) into float32 and applies it, so that
# neither a float32 layer (4.6 GB) nor a whole [heads, seq, seq] score tensor
# ever exists on the chip.


def _w(a, lower: bool):
    """A piece of the program's weights in float32; ``lower`` rounds it to
    float8 (e4m3) first."""
    return _f32(a.astype(jnp.float8_e4m3fn)) if lower else _f32(a)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _p_norm(x, scale, eps):
    return layer_norm(x, _f32(scale), eps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
@_highest
def _p_qkv(h, attn, at, j, rotate, base, lower):
    """q [seq, group, hd], k, v [seq, hd] of K/V head ``j`` of layer ``at``."""
    wq, wk, wv = (attn[n]["kernel"][at] for n in ("q", "k", "v"))
    d, kv_heads, hd = wk.shape
    order = _rotary_order(hd)
    wq = _w(wq.reshape(d, kv_heads, wq.shape[1] // kv_heads, hd)[:, j], lower)[..., order]
    q = jnp.einsum("td,dhk->thk", h, wq)
    k = jnp.einsum("td,dhk->thk", h, _w(wk[:, j], lower)[:, None, order])
    v = jnp.einsum("td,dk->tk", h, _w(wv[:, j], lower))
    if rotate:
        q, k = rotary_interleaved(q, base), rotary_interleaved(k, base)
    return q, k[:, 0], v


@functools.partial(jax.jit, static_argnums=(4,))
@_highest
def _p_attend_rows(q, rows, k, v, window):
    return attend_rows(q, rows, k, v, window)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _p_out(attended, attn, at, lower):
    return jnp.einsum("thk,hkd->td", attended, _w(attn["o"]["kernel"][at], lower))


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _p_route(h, moe, at, k, lower):
    top, chosen = jax.lax.top_k(jax.nn.sigmoid(h @ _w(moe["router"][at], lower)), k)
    return top / top.sum(-1, keepdims=True), chosen


@functools.partial(jax.jit, static_argnums=(4,))
@_highest
def _p_expert(h, group, at, e, lower):
    wi, wo = _w(group["wi"][at, e], lower), _w(group["wo"][at, e], lower)
    f = wo.shape[0]
    return expert(h, wi[:, :f], wi[:, f:], wo)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _p_head(x, ln_f, wte, eps, logit_scale, lower):
    return logit_scale * (layer_norm(x, _f32(ln_f), eps) @ _w(wte, lower).T)


def _program_block(x, layers, at: int, model, wrong):
    lower = wrong == LOWER
    sliding = is_sliding(model, at)
    seq = x.shape[0]
    h = _p_norm(x, layers["ln"]["scale"][at], model["layer_norm_eps"])
    rotate = sliding or wrong == "rope_on_full"
    window = model["sliding_window"] if sliding and wrong != "window_off" else None
    heads = []
    for j in range(model["num_key_value_heads"]):
        q, k, v = _p_qkv(h, layers["attn"], at, j, rotate, float(model["rope_theta"]), lower)
        heads.append(jnp.concatenate([
            _p_attend_rows(q[a:a + ROWS], jnp.arange(a, min(a + ROWS, seq)), k, v, window)
            for a in range(0, seq, ROWS)
        ], axis=0))
    out = x + _p_out(jnp.concatenate(heads, axis=1), layers["attn"], at, lower)
    top, chosen = _p_route(h, layers["moe"], at, model["num_experts_per_tok"], lower)
    for e in range(model["num_experts"]):
        weight = jnp.where(chosen == model["expert_offset"] + e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(
            out + weight[:, None] * _p_expert(h, layers["moe"], at, e, lower))
    shared = model["num_shared_experts"]
    for e in range(shared):
        part = _p_expert(h, layers["shared"], at, e, lower)
        out = jax.block_until_ready(out + (part if wrong == "shared_summed" else part / shared))
    return out


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    lower = wrong == LOWER
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], lower)
    for at in range(config["num_hidden_layers"]):
        x = _program_block(x, program["blocks"]["layers"], at, config, wrong)
    return _p_head(
        x[-last:], program["ln_f"]["scale"], program["wte"]["embedding"],
        config["layer_norm_eps"], float(config["logit_scale"]), lower)


def program_loss(program, tokens, config) -> float:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq] from the
    program's own weights, one sequence at a time. The benchmark trains no
    such model; the harness's contract lists the entry point."""
    rows = [
        float(next_token_loss(program_logits(program, row, config, len(row)), row))
        for row in tokens
    ]
    return sum(rows) / len(rows)
