"""granite-4.0-h with routed experts (``granitemoehybrid``, granite-4.0-h-small):
the forward pass in plain ``jax.numpy`` and float32 at the highest matmul
precision: no kernels, no cache, no chunked recurrence, no sort and no grouped
matmul; a loop over layers and over the held experts, the Mamba-2 recurrence
token by token. The yardstick the serving path is compared with, at a small
size on the CPU (``tests/benchmark/test_bench_granite_4h_small.py``) and, at the
published widths on the chip, in every run's set-up (``program_logits``).

The mixers, the norms and the tied head are the block's that
``granitemoehybrid_reference`` writes out (Mamba-2 with one group, attention
without positions, the four multipliers) and are taken from there. What is new
is the second half of every layer (published ``GraniteMoeHybrid``: the shared
MLP beside ``GraniteMoeMoE``). With ``n = RMSNorm(h)``:

* the router scores all ``router_experts`` experts, ``l = W_r n`` (no bias); the
  ``num_experts_per_tok`` largest **logits** are taken first and the weights
  are a softmax **over those alone** (``GraniteMoeTopKGating``: top-k, then
  softmax), which the program computes as a softmax over all of them, its
  largest divided by their sum: equal up to rounding;
* ``Expert_e(n) = W_out,e (silu(G_e n) * U_e n)`` of width ``intermediate_size``,
  ``Shared(n)`` the same form of width ``shared_intermediate_size``;
* ``h += residual_multiplier * (Shared(n) + sum_k w_k Expert_{e_k}(n))``.

It is given the share the chip holds: the routed experts ``expert_offset ..
expert_offset + num_local_experts - 1`` of the ``router_experts`` the router
scores (what the absent ones would add is left out, as in the program) and the
first ``vocab_size`` rows of the tied vocabulary.

Departures of the program under test, which the comparison accounts for: those
``granitemoehybrid_reference`` lists; an expert's gate and up projection lie side
by side; the held experts of the layers at one place of a period are one stack
``[periods, num_local_experts, ...]`` under ``experts``, beside ``periods``.

``wrong`` names one omission at a time, to show what the limit of the
comparison catches: ``"no_shared"`` (no shared expert),
``"no_residual_multiplier"``, ``"uniform_weights"`` (``1 / k`` in place of the
softmax over the chosen), ``"eight_choices"`` (the eight largest in place of
``num_experts_per_tok``), ``"wrong_offset"`` (the held experts taken for the
other half's: ``expert_offset + num_local_experts``) and ``"fp8_weights"``: every
weight matrix rounded to float8 (e4m3) as it is read, the nearest precision
below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmark.reference import granitemoehybrid_reference as block
from benchmark.reference.granitemoehybrid_reference import (  # noqa: F401 — the harness's entry points
    F32, LOWER, next_token_loss)

WRONG = ("no_shared", "no_residual_multiplier", "uniform_weights", "eight_choices", "wrong_offset")


def route(n, router, k: int, wrong: Optional[str] = None):
    """``(weights [seq, k], chosen [seq, k])`` in the published order: the ``k``
    largest logits, then a softmax over them alone."""
    top, chosen = jax.lax.top_k(n @ router, 8 if wrong == "eight_choices" else k)
    weights = jax.nn.softmax(top, -1)
    return (jnp.full_like(weights, 1.0 / k) if wrong == "uniform_weights" else weights), chosen


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
@block._highest
def _route(n, router, k, wrong, lower):
    return route(n, block._w(router, lower), k, wrong)


def _second_half(n, mlp, held, config, wrong):
    """``Shared(n)`` and the held experts' part of the routed sum."""
    lower = wrong == LOWER
    weights, chosen = _route(n, mlp["router"], config["num_experts_per_tok"], wrong, lower)
    first = config.get("expert_offset", 0) + (
        held["wi"].shape[0] if wrong == "wrong_offset" else 0)
    out = 0.0
    for e in range(held["wi"].shape[0]):
        weight = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(
            out + weight[:, None] * block._gated(n, held["wi"][e], held["wo"][e], lower))
    if wrong != "no_shared":
        out = out + block._mlp(n, mlp, lower)
    return out


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    lower, eps = wrong == LOWER, config["rms_norm_eps"]
    res = 1.0 if wrong == "no_residual_multiplier" else config["residual_multiplier"]
    period, attention_at = config["layer_period"], config["attention_layer_offset"]
    x = config["embedding_multiplier"] * block._w(
        program["wte"]["embedding"][jnp.asarray(tokens)], lower)
    periods = program["periods"]
    for layer in range(config["num_hidden_layers"]):
        at, i = divmod(layer, period)
        if i == attention_at:
            p = block._layer_of(periods["attn"], at)
            mixed = block._attention(
                block._norm(x, p["ln"]["scale"], eps), p, config["num_attention_heads"],
                config["num_key_value_heads"], float(config["attention_multiplier"]), lower)
        else:
            p = block._layer_of(periods["mamba"][i - (i > attention_at)], at)
            mixed = block._mamba(
                block._norm(x, p["ln"]["scale"], eps), p, config["mamba_n_heads"],
                config["mamba_d_state"], eps, LOWER if lower else None)
        x = x + res * mixed
        mlp = block._layer_of(periods["mlp"][i], at)
        held = block._layer_of(program["experts"][i], at)
        x = jax.block_until_ready(x + res * _second_half(
            block._norm(x, mlp["ln"]["scale"], eps), mlp, held, config, wrong))
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one
    sequence ``tokens`` [seq], from the program's own weights; ``config`` is
    the configuration's file."""
    x = _hidden(program, tokens, config, wrong)[-last:]
    table = program["wte"]["embedding"]
    return jnp.concatenate([
        block._head(
            x, program["ln_f"]["scale"], table[a:a + block.VOCAB_ROWS], config["rms_norm_eps"],
            float(config["logits_scaling"]), wrong == LOWER)
        for a in range(0, table.shape[0], block.VOCAB_ROWS)], -1)


def program_loss(program, tokens, config) -> float:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq] from the
    program's own weights, one sequence at a time. The benchmark trains no
    such model; the harness's contract lists the entry point."""
    rows = [
        float(next_token_loss(program_logits(program, row, config, len(row)), row))
        for row in tokens
    ]
    return sum(rows) / len(rows)
