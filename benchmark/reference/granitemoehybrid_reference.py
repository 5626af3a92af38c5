"""granite-4.0-h (``granitemoehybrid``): the forward pass in plain ``jax.numpy``
and float32 at the highest matmul precision: no kernels, no cache, no batching,
no chunked form of the recurrence; a loop over layers, the Mamba-2 recurrence
**token by token** (one ``lax.scan`` step a token, the state updated and read as
the equations below have it), attention as a full causal softmax a block of
queries at a time. The yardstick the serving path is compared with, at a small
size on the CPU (``tests/benchmark/test_bench_granitemoehybrid.py``) and, at the
published widths on the chip, in every run's set-up (``program_logits``).

It follows the published ``config.json`` (ibm-granite/granite-4.0-h-micro). With
``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``:

* ``h = embedding_multiplier * E[id]``; every layer ``h += residual_multiplier *
  Mixer(RMSNorm(h))``, ``h += residual_multiplier * MLP(RMSNorm(h))``, ``MLP(r) =
  W_out (silu(g) * u)``, ``[g, u] = W_in r`` of width ``shared_intermediate_size``
  (``num_local_experts`` 0: no routed part); the head is tied, ``logits =
  RMSNorm(h) E^T / logits_scaling``;
* ``layer_types[l] == "attention"``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads of ``hidden_size / num_attention_heads``, no
  bias, no position encoding (``position_embedding_type`` ``nope``), a causal
  softmax of ``attention_multiplier * q . k``, ``W_o``;
* ``"mamba"``, per token ``t``: ``[z, xBC, dt] = W_in r`` (``mamba_expand *
  hidden_size``, that ``+ 2 * mamba_n_groups * mamba_d_state``, ``mamba_n_heads``;
  no bias); ``xBC <- silu(conv_t(xBC))``, a causal depthwise convolution of
  kernel ``mamba_d_conv`` with bias; ``[x, B, C] = xBC``, ``x`` as
  ``mamba_n_heads`` heads of ``mamba_d_head``, ``B`` and ``C`` of ``mamba_d_state``
  shared by all heads (``mamba_n_groups`` 1); ``D_t = softplus(dt + dt_bias)`` and
  ``A = -exp(A_log)`` a head; ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``, ``y_t
  = S_t C_t + D x_t``; ``y <- RMSNorm(y * silu(z))`` over all channels (one
  group), ``W_out``. ``time_step_limit`` is (0, inf): no clamp.

Departures of the program under test, which the comparison accounts for:

* the program stores ``W_in`` of a Mamba layer as its three parts (``in_z``,
  ``in_xbc``, ``in_dt``), the gate and the up projection of an MLP side by side,
  the convolution's kernel as ``[mamba_d_conv, channels]`` with the current
  token's tap last, and all K/V heads of a token side by side in one row; they
  are read as they lie;
* the layers are stacked by period (``layer_period`` layers with the attention
  layer at ``attention_layer_offset``, which is what ``layer_types`` says): one
  tree for each layer of a period, its leaves ``[periods, ...]``.

``wrong`` names one omission at a time, to show what the limit of the
comparison catches: ``"state_bf16"`` (the state rounded to bfloat16 after every
token), ``"no_D"``, ``"no_dt_bias"``, ``"no_conv_bias"``,
``"no_residual_multiplier"``, ``"plain_attention_scale"`` (``head_dim^-0.5`` in
place of ``attention_multiplier``) and ``"fp8_weights"``: every weight matrix
rounded to float8 (e4m3) as it is read, the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024        # queries attended at a time: [ROWS, seq] scores a head
COLUMNS = 2048     # of the MLP's width at a time
VOCAB_ROWS = 16384  # of the tied head at a time
LOWER = "fp8_weights"
WRONG = (
    "state_bf16", "no_D", "no_dt_bias", "no_conv_bias", "no_residual_multiplier",
    "plain_attention_scale")


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


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _gated(n, wi, wo, lower):
    f = wo.shape[0]
    wi = _w(wi, lower)
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ _w(wo, lower)


def _mlp(n, mlp, lower):
    """``W_out (silu(g) * u)``, ``COLUMNS`` of its width at a time."""
    wi, wo = mlp["wi"], mlp["wo"]
    f = wo.shape[0]
    out = 0.0
    for a in range(0, f, COLUMNS):
        b = min(a + COLUMNS, f)
        piece = jnp.concatenate([wi[:, a:b], wi[:, f + a:f + b]], 1)
        out = jax.block_until_ready(out + _gated(n, piece, wo[a:b], lower))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
@_highest
def _attention(n, attn, heads, kv_heads, scale, lower):
    seq = n.shape[0]
    q = (n @ _w(attn["q"]["kernel"], lower)).reshape(seq, heads, -1)
    k = (n @ _w(attn["k"]["kernel"], lower)).reshape(seq, kv_heads, -1)
    v = (n @ _w(attn["v"]["kernel"], lower)).reshape(seq, kv_heads, -1)
    out = []
    for a in range(0, seq, ROWS):
        rows = jnp.arange(a, min(a + ROWS, seq))
        mask = jnp.arange(seq)[None, :] <= rows[:, None]
        per_head = []
        for h in range(heads):
            at = h // (heads // kv_heads)
            scores = jnp.where(mask, (q[a:a + ROWS, h] @ k[:, at].T) * scale, -jnp.inf)
            per_head.append(jax.nn.softmax(scores, -1) @ v[:, at])
        out.append(jnp.concatenate(per_head, -1))
    return jnp.concatenate(out, 0) @ _w(attn["o"]["kernel"], lower)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
@_highest
def _mamba(n, p, heads, state, eps, wrong):
    """One Mamba-2 mixer over ``n`` [seq, hidden], the recurrence token by token."""
    lower = wrong == LOWER
    seq = n.shape[0]
    z = n @ _w(p["in_z"]["kernel"], lower)
    xbc = n @ _w(p["in_xbc"]["kernel"], lower)
    dt = n @ _w(p["in_dt"]["kernel"], lower)
    kernel = _w(p["conv"]["kernel"], lower)                 # [width, channels], current tap last
    width = kernel.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), F32), xbc], 0)
    mixed = sum(kernel[k] * padded[k:k + seq] for k in range(width))
    if wrong != "no_conv_bias":
        mixed = mixed + jnp.asarray(p["conv"]["bias"], F32)
    mixed = jax.nn.silu(mixed)
    inner = z.shape[1]
    x = mixed[:, :inner].reshape(seq, heads, -1)
    b, c = mixed[:, inner:inner + state], mixed[:, inner + state:]
    if wrong != "no_dt_bias":
        dt = dt + p["dt_bias"]
    dt = jax.nn.softplus(dt)                                # [seq, heads]
    a = -jnp.exp(p["A_log"])

    def token(s, at):
        x_t, b_t, c_t, dt_t = at
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t
        if wrong == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(F32)
        return s, (s * c_t).sum(-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, x.shape[-1], state), F32), (x, b, c, dt))
    if wrong != "no_D":
        y = y + p["D"][:, None] * x
    y = y.reshape(seq, inner) * jax.nn.silu(z)
    return rms_norm(y, jnp.asarray(p["norm"]["scale"], F32), eps) @ _w(p["out"]["kernel"], lower)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _head(x, ln_f, rows, eps, scaling, lower):
    return rms_norm(x, jnp.asarray(ln_f, F32), eps) @ _w(rows, lower).T / scaling


def _layer_of(tree, *at):
    return jax.tree.map(lambda a: a[at], tree)


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    lower, eps = wrong == LOWER, config["rms_norm_eps"]
    res = 1.0 if wrong == "no_residual_multiplier" else config["residual_multiplier"]
    heads = config["num_attention_heads"]
    scale = (
        (config["hidden_size"] // heads) ** -0.5 if wrong == "plain_attention_scale"
        else config["attention_multiplier"])
    period, attention_at = config["layer_period"], config["attention_layer_offset"]
    x = config["embedding_multiplier"] * _w(
        program["wte"]["embedding"][jnp.asarray(tokens)], lower)
    periods = program["periods"]
    for layer in range(config["num_hidden_layers"]):
        at, i = divmod(layer, period)
        if i == attention_at:
            p = _layer_of(periods["attn"], at)
            mixed = _attention(
                _norm(x, p["ln"]["scale"], eps), p, heads, config["num_key_value_heads"],
                float(scale), lower)
        else:
            p = _layer_of(periods["mamba"][i - (i > attention_at)], at)
            mixed = _mamba(
                _norm(x, p["ln"]["scale"], eps), p, config["mamba_n_heads"],
                config["mamba_d_state"], eps, wrong)
        x = x + res * mixed
        mlp = _layer_of(periods["mlp"][i], at)
        x = jax.block_until_ready(x + res * _mlp(_norm(x, mlp["ln"]["scale"], eps), mlp, lower))
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    x = _hidden(program, tokens, config, wrong)[-last:]
    table = program["wte"]["embedding"]
    return jnp.concatenate([
        _head(
            x, program["ln_f"]["scale"], table[a:a + VOCAB_ROWS], config["rms_norm_eps"],
            float(config["logits_scaling"]), wrong == LOWER)
        for a in range(0, table.shape[0], VOCAB_ROWS)], -1)


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
