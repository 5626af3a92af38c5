"""GPT-J's forward pass and loss in plain ``jax.numpy`` and float32: no
kernels, no cache, no batching tricks, no scan. The yardstick the system is
compared with (``tests/benchmark/test_bench_reference.py``: the train path's
logits, loss and gradients, and the server's prefill-then-decode through its
paged cache, on seeded random weights at a small size) and, at the published
widths on the chip, in every run's set-up: the first train step's loss and the
server's logits (``program_loss``, ``program_logits``, below).

It follows the published model (EleutherAI GPT-J-6B: ``config.json`` and the
``GPTJ`` block of mesh-transformer-jax / Hugging Face): one LayerNorm per
block feeding attention and the MLP in parallel, both added to the residual;
no biases on q, k, v, o; rotary embedding on the first ``rotary_dim`` features
of each head, rotating *interleaved* pairs (2i, 2i+1); ``gelu_new`` (the tanh
approximation); an untied output head with a bias.

Departures of the program under test, which the comparison accounts for:

* the program rotates *half-split* pairs (i, i + rotary_dim/2). That is the
  published rotation under a fixed permutation of each head's rotary features,
  applied alike to q and k, which leaves every q.k product unchanged;
  :func:`from_program_params` applies that permutation to the q and k kernels,
  so equal logits show the two are the same function of the weights;
* the program's LayerNorm epsilon is 1e-6 where the source says 1e-5; the
  comparison passes the program's value and says so.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rotary_interleaved(x, rotary_dim):
    """x: [batch, seq, heads, head_dim]; positions are 0..seq-1."""
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(half, dtype=F32) / half))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]   # [seq, half]
    sin, cos = jnp.sin(angles)[None, :, None, :], jnp.cos(angles)[None, :, None, :]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return jnp.concatenate([out.reshape(rot.shape), keep], axis=-1)


def embed(wte, tokens):
    return wte[tokens]


def block(x, layer, heads: int, rotary_dim: int, eps: float):
    """One GPT-J block on ``x`` [batch, seq, embed], positions 0..seq-1."""
    seq, head_dim = x.shape[1], x.shape[2] // heads
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    h = _layer_norm(x, layer["ln"], eps)
    q, k, v = (jnp.einsum("btd,dhk->bthk", h, layer[n]) for n in ("q", "k", "v"))
    q = _rotary_interleaved(q, rotary_dim)
    k = _rotary_interleaved(k, rotary_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attended = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    attention = jnp.einsum("bqhd,hde->bqe", attended, layer["o"])
    mlp = jax.nn.gelu(h @ layer["wi"] + layer["wi_bias"], approximate=True)
    mlp = mlp @ layer["wo"] + layer["wo_bias"]
    return x + attention + mlp


def head(x, ln_f, kernel, bias, eps: float):
    return _layer_norm(x, ln_f, eps) @ kernel + bias


def next_token_loss(logits, tokens):
    """Mean next-token cross-entropy over positions 0..seq-2."""
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].mean()


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any], eps=None):
    """Logits [batch, seq, vocab] for ``tokens`` [batch, seq]; ``params`` as
    :func:`from_program_params` returns them, ``model`` the published keys."""
    eps = model["layer_norm_epsilon"] if eps is None else eps
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"], tokens)
        for layer in params["layers"]:
            x = block(x, layer, model["n_head"], model["rotary_dim"], eps)
        return head(x, params["ln_f"], params["head"], params["head_bias"], eps)


def loss(params, tokens, model, eps=None):
    return next_token_loss(forward(params, tokens, model, eps), tokens)


def _rotary_order(head_dim: int, r: int):
    """published feature 2i <- program feature i; 2i+1 <- program feature i + r/2"""
    order = np.arange(head_dim)
    order[0:r:2], order[1:r:2] = np.arange(r // 2), np.arange(r // 2) + r // 2
    return order


def _f32(a):
    return jnp.asarray(a, F32)


def _layer_from_program(p, order):
    a, m = p["attn"], p["mlp"]
    return {
        "ln": jax.tree.map(_f32, p["ln"]),
        "q": _f32(a["q"]["kernel"])[..., order], "k": _f32(a["k"]["kernel"])[..., order],
        "v": _f32(a["v"]["kernel"]), "o": _f32(a["o"]["kernel"]),
        "wi": _f32(m["wi"]["kernel"]), "wi_bias": _f32(m["wi"]["bias"]),
        "wo": _f32(m["wo"]["kernel"]), "wo_bias": _f32(m["wo"]["bias"]),
    }


def from_program_params(program: Dict[str, Any], model: Dict[str, Any]) -> Dict[str, Any]:
    """The program's (unboxed) parameter tree as the reference's: float32, one
    entry per layer, and q/k kernels with each head's rotary features permuted
    from the program's half-split order to the published interleaved order."""
    order = _rotary_order(model["n_embd"] // model["n_head"], model["rotary_dim"])
    layers = program["blocks"]["layers"]      # the program keeps them stacked
    return {
        "wte": _f32(program["wte"]["embedding"]),
        "layers": [
            _layer_from_program(jax.tree.map(lambda a: a[i], layers), order)
            for i in range(model["n_layer"])
        ],
        "ln_f": jax.tree.map(_f32, program["ln_f"]),
        "head": _f32(program["lm_head"]["kernel"]),
        "head_bias": _f32(program["lm_head"]["bias"]),
    }


# -- at published widths, beside the system on the chip ----------------------
#
# The same functions, one layer at a time: each jitted call turns one layer of
# the program's weights (as served or trained, bfloat16) into float32 and
# applies it, so the float32 copy of the whole model never exists on the chip.


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _program_block(x, layers, at, heads, rotary_dim, eps):
    """Layer ``at`` of the program's stacked ``layers`` applied to ``x``."""
    p = jax.tree.map(lambda a: a[at], layers)
    order = _rotary_order(p["attn"]["q"]["kernel"].shape[-1], rotary_dim)
    with jax.default_matmul_precision("highest"):
        return block(x, _layer_from_program(p, order), heads, rotary_dim, eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _program_head(x, ln_f, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return head(
            x, jax.tree.map(_f32, ln_f), _f32(lm_head["kernel"]), _f32(lm_head["bias"]), eps
        )


@functools.partial(jax.jit, static_argnums=(4,))
def _program_row_loss(x, ln_f, lm_head, row, eps):
    return next_token_loss(_program_head(x, ln_f, lm_head, eps), row)


def _program_hidden(program, tokens, model, eps):
    x = _f32(embed(program["wte"]["embedding"], tokens))
    for i in range(model["n_layer"]):
        x = _program_block(
            x, program["blocks"]["layers"], i, model["n_head"], model["rotary_dim"], eps
        )
    return x


def _program_eps(config) -> float:
    """The program's LayerNorm epsilon (see the departures above), which the
    configuration's file states under ``reference``."""
    return config["reference"]["program_layer_norm_epsilon"]


def program_logits(program, tokens, config, last: int):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    eps = _program_eps(config)
    x = _program_hidden(program, jnp.asarray(tokens)[None], config, eps)[:, -last:]
    return _program_head(x, program["ln_f"], program["lm_head"], eps)[0]


def program_loss(program, tokens, config) -> float:
    """:func:`loss` of ``tokens`` [batch, seq] from the program's own weights,
    one sequence at a time (rows are equally long, so the mean of the rows'
    means is the batch's mean); ``config`` is the configuration's file."""
    eps = _program_eps(config)
    rows = []
    for row in tokens:
        x = _program_hidden(program, row[None], config, eps)
        rows.append(float(
            _program_row_loss(x, program["ln_f"], program["lm_head"], row[None], eps)
        ))
    return sum(rows) / len(rows)
