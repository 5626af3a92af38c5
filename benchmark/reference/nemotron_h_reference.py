"""NVIDIA-Nemotron-3-Nano-30B-A3B (``nemotron_h``): the forward pass and the loss in
plain ``jax.numpy`` and float32 at the highest matmul precision: no kernel, no
chunked scan, no remat, no sort and no grouped matmul; a loop over layers, the
recurrence **token by token** (one ``lax.scan`` step a token), attention over blocks
of queries, and a loop over the held experts. The yardstick the train path is
compared with: at a small size on the CPU, loss and every parameter's gradient
(``tests/test_nemotron_h.py``), and at the published widths on the chip in every
run's set-up, the first step's loss (:func:`program_loss`).

It follows the published ``config.json`` (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16)
and the family's ``modeling_nemotron_h.py``. With ``N(x) = x * rsqrt(mean(x^2) +
norm_eps) * g``, layer ``l`` is ``h += Mixer_l(N_l(h))``, one branch, ``Mixer_l`` by
letter ``l`` of ``hybrid_override_pattern``:

* ``M``: ``[z, xBC, dt] = W_in n`` (``mamba_proj_bias`` false); ``xBC_t <- silu(b +
  sum_j w_j xBC_(t - (K - 1) + j))``, ``K = conv_kernel`` taps, zeros before the
  sequence (``use_conv_bias`` true), written as ``K`` shifted products; ``[x, B, C] =
  xBC``, ``x`` ``[mamba_num_heads, mamba_head_dim]``, ``B`` and ``C`` ``[n_groups,
  ssm_state_size]``, head ``h`` reading group ``h // (mamba_num_heads / n_groups)``
  (the published code repeats ``B`` and ``C`` so many times a group); ``D_t =
  softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; **a token at a time** from a zero
  state: ``S_t = exp(D_t A_h) S_(t-1) + D_t x_t (x) B_t``, ``y_t = S_t C_t + D_h
  x_t``; ``W_out [N_group(y * silu(z)) * w]`` with the norm over each group's
  ``mamba_num_heads x mamba_head_dim / n_groups`` channels (``MambaRMSNormGated``
  with ``group_size``, ``norm_before_gate`` false, ``layer_norm_epsilon``);
* ``*``: ``num_attention_heads`` query heads over ``num_key_value_heads`` K/V heads
  of ``head_dim``, ``attention_bias`` false, nothing rotated (the family's attention
  has no rotary embedding), a full causal softmax of ``q.k / sqrt(head_dim)``, a
  block of queries at a time; ``W_o``;
* ``E``, in the published router's order: ``s = sigmoid(n W_r)`` in float32; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (``n_group`` 1,
  ``topk_group`` 1: every expert is in the one group, no limit); weights ``s`` of the
  chosen over their sum (``norm_topk_prob``; the published ``+ 1e-20`` in the
  denominator moves nothing in float32) times ``routed_scaling_factor``; expert ``e``
  is ``W_down^e relu(W_up^e n)^2`` (``mlp_hidden_act`` ``relu2``, ``mlp_bias`` false,
  no gate); the layer is **a loop over the held experts**, each applied to every
  token and weighed by a dense 0-or-weight mask a token; plus the shared expert, of
  the same form at ``moe_shared_expert_intermediate_size``, unweighted;
* ``norm_f`` behind the last layer and an untied head (``tie_word_embeddings``
  false); the loss is the mean next-token cross-entropy.

It is given the share the chip holds: the experts ``expert_offset .. expert_offset +
n_routed_experts - 1`` of the ``router_experts`` the router scores (what the absent
ones would add is left out, as in the program) and the first ``vocab_size`` rows of
the vocabulary.

Departures of the program under test, which the comparison accounts for: none in
the mathematics. The program stores a matrix as ``[in, out]``, the taps as
``[conv_kernel, channels]``; they are read as they lie.

``wrong`` names one omission at a time, to show what the comparison's limit catches:
``"one_group"`` (every head reads group 0's ``B`` and ``C``), ``"norm_all"`` (the
gated norm over all the channels at once), ``"relu"`` (``relu`` for ``relu^2``, routed
and shared), ``"unscaled"`` (the weights without ``routed_scaling_factor``),
``"no_bias"`` (the correction bias read as zero), ``"bf16_state"`` (the state rounded
to bfloat16 after every token), and ``"fp8_weights"``: every weight matrix rounded to
float8 (e4m3) as it is read, the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 512         # queries attended, and rows of logits made, at a time
LOWER = "fp8_weights"
WRONG = ("one_group", "norm_all", "relu", "unscaled", "no_bias", "bf16_state")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def recurrence(x, dt, a, b, c, round_state: bool = False):
    """``y`` [batch, seq, heads, p] of ``S_t = exp(dt_t a) S_(t-1) + dt_t x_t (x) b_t``,
    ``y_t = S_t c_t``: ``x`` [batch, seq, heads, p], ``dt`` [batch, seq, heads], ``a``
    [heads], ``b``, ``c`` [batch, seq, heads, n] (a head's own), one token a step."""
    def token(state, at):
        xt, dtt, bt, ct = at
        state = jnp.exp(dtt * a)[..., None, None] * state + (
            (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        if round_state:
            # bfloat16's 8 bits of mantissa; a cast there and back XLA may take out again
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, (state * ct[..., None, :]).sum(-1)

    batch, _, heads, p = x.shape
    by_token = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    _, y = jax.lax.scan(token, jnp.zeros((batch, heads, p, b.shape[-1]), F32), by_token)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(n, p, model: Dict[str, Any], wrong: Optional[str] = None):
    batch, seq, _ = n.shape
    heads, dim = model["mamba_num_heads"], model["mamba_head_dim"]
    groups, state = model["n_groups"], model["ssm_state_size"]
    inner = heads * dim
    z, xbc, dt = jnp.split(n @ p["in"], (inner, 2 * inner + 2 * groups * state), -1)
    taps = p["conv"]
    mixed = jnp.zeros_like(xbc) + p["conv_bias"]
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j          # tap j reads the input ``back`` steps ago
        mixed = mixed + taps[j] * jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :seq]
    mixed = jax.nn.silu(mixed)
    x = mixed[..., :inner].reshape(batch, seq, heads, dim)
    b, c = (
        part.reshape(batch, seq, groups, state)
        for part in jnp.split(mixed[..., inner:], 2, -1))
    if wrong == "one_group":
        b, c = (jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c))
    # a head's own B and C: its group's, repeated
    b, c = (jnp.repeat(v, heads // groups, axis=2) for v in (b, c))
    step = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, step, -jnp.exp(p["A_log"]), b, c, round_state=wrong == "bf16_state")
    y = (y + p["D"][:, None] * x).reshape(batch, seq, inner) * jax.nn.silu(z)
    normed_over = 1 if wrong == "norm_all" else groups
    y = rms_norm(
        y.reshape(batch, seq, normed_over, -1), 1.0, model["layer_norm_epsilon"]
    ).reshape(batch, seq, inner) * p["norm"]
    return y @ p["out"]


def attention_mixer(n, p, model: Dict[str, Any]):
    batch, seq, _ = n.shape
    heads, kv, dim = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    q = (n @ p["q"]).reshape(batch, seq, kv, heads // kv, dim)
    k = (n @ p["k"]).reshape(batch, seq, kv, dim)
    v = (n @ p["v"]).reshape(batch, seq, kv, dim)
    at = jnp.arange(seq)
    blocks = []
    for start in range(0, seq, ROWS):
        rows = slice(start, start + ROWS)
        scores = jnp.einsum("bqgnd,bkgd->bgnqk", q[:, rows], k) / math.sqrt(dim)
        scores = jnp.where(at[rows, None] >= at[None, :], scores, -jnp.inf)
        blocks.append(jnp.einsum("bgnqk,bkgd->bqgnd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(blocks, 1).reshape(batch, seq, heads * dim) @ p["o"]


def relu2_mlp(n, wi, wo, wrong: Optional[str] = None):
    """``W_down relu(W_up n)^2``; ``"relu"`` leaves the square out."""
    up = jax.nn.relu(n @ wi)
    return (up if wrong == "relu" else up * up) @ wo


def route(n, router, bias, model: Dict[str, Any], wrong: Optional[str] = None):
    """``(weights, chosen)`` [..., k] over all the experts the router scores."""
    scores = jax.nn.sigmoid(n @ router)
    for_choice = scores if wrong == "no_bias" else scores + bias
    _, chosen = jax.lax.top_k(for_choice, model["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, -1)
    if model.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return top * (1.0 if wrong == "unscaled" else model["routed_scaling_factor"]), chosen


def held_experts(n, weights, chosen, wi, wo, first: int, wrong: Optional[str] = None):
    """What the experts ``first .. first + len(wi) - 1`` (``wi`` [e, hidden, width],
    ``wo`` [e, width, hidden]) add for the tokens that chose them: each applied to
    every token under a dense mask, a token's weight for it or 0."""
    out = jnp.zeros_like(n)
    for i in range(wi.shape[0]):
        mask = (weights * (chosen == first + i)).sum(-1)
        out = out + mask[..., None] * relu2_mlp(n, wi[i], wo[i], wrong)
    return out


def expert_layer(n, p, bias, model: Dict[str, Any], wrong: Optional[str] = None):
    weights, chosen = route(n, p["router"], bias, model, wrong)
    routed = held_experts(n, weights, chosen, p["wi"], p["wo"], model["expert_offset"], wrong)
    return routed + relu2_mlp(n, p["shared_wi"], p["shared_wo"], wrong)


def layer_of(kind: str, x, p, bias, model: Dict[str, Any], wrong: Optional[str] = None):
    """A layer of ``kind`` (a letter of the pattern) on ``x`` [batch, seq, hidden]: one
    branch; ``bias`` is an expert layer's, None otherwise."""
    n = rms_norm(x, p["ln"], model["norm_eps"])
    if kind == "M":
        return x + mamba_mixer(n, p, model, wrong)
    if kind == "*":
        return x + attention_mixer(n, p, model)
    return x + expert_layer(n, p, bias, model, wrong)


def layer(x, p, bias, at: int, model: Dict[str, Any], wrong: Optional[str] = None):
    """Layer ``at``: what letter ``at`` of ``hybrid_override_pattern`` says."""
    return layer_of(model["hybrid_override_pattern"][at], x, p, bias, model, wrong)


def next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].mean()


def hidden(params, tokens, model, wrong=None):
    """The final hidden rows [batch, seq, hidden], behind ``norm_f``."""
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens]
        for at, (p, bias) in enumerate(params["layers"]):
            x = layer(x, p, bias, at, model, wrong)
        return rms_norm(x, params["ln_f"], model["norm_eps"])


def forward(params, tokens, model, wrong=None):
    """Logits [batch, seq, vocab]; ``params`` as :func:`from_program_params` gives them."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, model, wrong) @ params["head"]


def loss(params, tokens, model, wrong=None):
    return next_token_loss(forward(params, tokens, model, wrong), tokens)


# -- the program's weights, read as they lie -----------------------------------------


def _f32(a, lower: bool = False):
    a = jnp.asarray(a)
    if lower and a.ndim >= 2:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(F32)


def program_layers(program) -> Iterator[Tuple[Any, Any]]:
    """``(layer, bias)`` of every layer in order, from the program's tree: ``layers``
    one tree a layer, ``expert_bias`` one array an expert layer in their order; bias
    None for a layer without experts."""
    biases = iter(program["expert_bias"])
    for p in program["layers"]:
        yield p, next(biases) if "router" in p else None


def from_program_params(program, lower: bool = False) -> Dict[str, Any]:
    """The program's parameter tree as the reference's: float32, one entry a layer."""
    cast = functools.partial(_f32, lower=lower)
    return {
        "wte": cast(program["wte"]), "head": cast(program["head"]), "ln_f": cast(program["ln_f"]),
        "layers": [
            (jax.tree.map(cast, p), None if bias is None else _f32(bias))
            for p, bias in program_layers(program)],
    }


# -- at published widths, beside the step's state on the chip -----------------------
#
# The same functions, a layer at a time: each jitted call turns one layer's weights
# into float32 (an expert layer's 16 held experts: 0.64 GB) and applies them, so no
# float32 copy of the model exists on the chip; one sequence at a time.


def _model(config: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in config.items() if not isinstance(v, (dict, list))}


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(0,))
def _layer_of(x, p, bias, kind, model_items, wrong, lower):
    """One program a kind of layer, whichever layers of the pattern are of it."""
    p = jax.tree.map(functools.partial(_f32, lower=lower), p)
    with jax.default_matmul_precision("highest"):
        return layer_of(
            kind, x, p, None if bias is None else _f32(bias), dict(model_items), wrong)


@functools.partial(jax.jit, static_argnums=(3,))
def _rows_nll(x, head, targets, lower):
    """Summed cross-entropy of the rows ``x`` [n, hidden] against ``targets`` [n]."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ _f32(head, lower), -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1).sum()


def program_hidden(program, tokens, config, wrong=None, lower=False):
    """The final hidden rows [batch, seq, hidden] from the program's own weights."""
    model = _model(config)
    items = tuple(sorted(model.items()))
    x = _f32(program["wte"][jnp.asarray(tokens)], lower)
    for kind, (p, bias) in zip(model["hybrid_override_pattern"], program_layers(program)):
        x = _layer_of(x, p, bias, kind, items, wrong, lower)
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(program["ln_f"]), model["norm_eps"])


def program_logits(program, tokens, config, last: int, wrong=None, lower=False):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights."""
    x = program_hidden(program, jnp.asarray(tokens)[None], config, wrong, lower)[0, -last:]
    with jax.default_matmul_precision("highest"):
        return x @ _f32(program["head"], lower)


def program_loss(program, tokens, config, wrong=None, lower=False) -> float:
    """:func:`loss` of ``tokens`` [batch, seq] from the program's own weights, one
    sequence and ``ROWS`` rows of logits at a time (the 16,384 rows' logits over the
    vocabulary would not fit beside the state); ``config`` is the configuration's file."""
    total, count = 0.0, 0
    for row in tokens:
        x = program_hidden(program, row[None], config, wrong, lower)[0, :-1]
        for start in range(0, x.shape[0], ROWS):
            rows = slice(start, start + ROWS)
            total += float(_rows_nll(x[rows], program["head"], row[1:][rows], lower))
        count += x.shape[0]
    return total / count
