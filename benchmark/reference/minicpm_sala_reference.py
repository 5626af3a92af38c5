"""MiniCPM-SALA (``minicpm_sala``): the forward pass in plain ``jax.numpy`` and
float32 at the highest matmul precision: no kernels, no cache, no chunked form of
the recurrence, no gather of chosen rows; a loop over layers, the linear attention
as the **quadratic** masked product over the whole prompt, the sparse attention as
each query's own selection over every key, a block of queries and a head (or a K/V
head) at a time. The yardstick the serving path is compared with, at a small size on
the CPU (``tests/test_minicpm_sala.py``, ``tests/benchmark/test_bench_minicpm_sala.py``)
and, at the published widths on the chip, in every run's set-up (``program_logits``).
Written from the published description, not from ``ray_tpu/models/minicpm_sala.py``.

With ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``, ``d = hidden_size`` and
``r = scale_depth / sqrt(published_num_hidden_layers)``:

* ``h = scale_emb * E[id]``; every layer ``h += r * Mixer(RMSNorm(h))``, ``h += r *
  W_down (silu(W_gate n) * W_up n)``, ``n = RMSNorm(h)``; ``logits = W_head
  (RMSNorm(h) / (d / dim_model_base))``, the head not tied;
* ``lightning-attn``: ``lightning_nh`` heads of ``lightning_head_dim``, a key and a
  value head a query head; ``q``, ``k`` RMS-normed per head (``qk_norm``) and rotated
  (``lightning_use_rope``: ``rope_theta``, all features, feature ``i`` paired with
  ``i + d/2``); ``o_t = sum_{s <= t} l_h^(t-s) ((q_t / sqrt(d)) . k_s) v_s`` with ``l_h =
  exp(-slope_h)``: no softmax, no denominator; ``y = RMSNorm_head(o)``
  (``use_output_norm``), ``y *= sigmoid(W_g x)`` (``use_output_gate``), ``W_o y``;
* ``minicpm4``: ``num_attention_heads`` query heads over ``num_key_value_heads`` K/V
  heads, no position encoding (``attn_use_rope`` false), ``q``, ``k`` RMS-normed per
  head. A query at ``t < sparse_dense_len`` attends every key ``s <= t``. A query at ``t
  >= sparse_dense_len`` attends, K/V head ``g`` by K/V head, the tokens ``s <= t`` of
  chosen blocks of ``sparse_block_size`` tokens: compressed keys ``Kc_g[j] = mean(k_g[s j
  : s j + K])`` (``K`` ``sparse_kernel_size``, ``s`` ``sparse_kernel_stride``), visible to
  ``t`` when ``s j + K - 1 <= t``; ``p_h(t, .)`` the softmax of ``q_h(t) . Kc_g[j] /
  sqrt(d)`` over the visible ``j``; ``P_g(t, j)`` its sum over the query heads of ``g``; a
  block's score the maximum of ``P_g(t, j)`` over the ``j`` whose ``K`` tokens meet the
  block; always read are the first ``sparse_init_blocks`` blocks and every block that
  meets ``[t - sparse_window_size + 1, t]``, and of the other blocks that begin at or
  before ``t`` the ``sparse_topk`` with the largest score (a tie to the lower block).
  Then ``y = attention * sigmoid(W_g x)`` (``attn_use_output_gate``), ``W_o y``.

Departures of the program under test, which the comparison accounts for: the layers
are stacked by period of ``mixer_period`` (one tree for each layer of a period, its
leaves ``[periods, ...]``: ``sparse`` and ``linear`` the mixers in their order, ``mlp``
every layer's), the gate and the up projection of an MLP lie side by side, all K/V
heads of a token in one row; the decay slopes are the buffer ``lightning_slopes``
``[linear layers, heads]``. They are read as they lie.

``wrong`` names one omission at a time, to show what the limit of the comparison
catches: ``"no_decay"`` (``l = 1``), ``"rope_in_sparse"``, ``"no_rope_in_linear"``,
``"no_output_gate"`` (neither mixer's), ``"no_output_norm"``, ``"no_depth_scale"`` (``r =
1``), ``"dense_always"`` (no query selects), ``"window_only"`` (no chosen blocks),
``"one_selection_for_both_kv_heads"`` (the weights of all query heads summed),
``"mean_pooled_blocks"`` (a block scores the mean, not the maximum) and
``"fp8_weights"``: every weight matrix rounded to float8 (e4m3) as it is read, the
nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 256          # queries at a time
COLUMNS = 2048      # of the MLP's width at a time
VOCAB_COLUMNS = 16384  # of the head at a time
LOWER = "fp8_weights"
WRONG = (
    "no_decay", "rope_in_sparse", "no_rope_in_linear", "no_output_gate", "no_output_norm",
    "no_depth_scale", "dense_always", "window_only", "one_selection_for_both_kv_heads",
    "mean_pooled_blocks")
SPARSE, LINEAR = "minicpm4", "lightning-attn"


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


def rotate(x, theta: float):
    """``x`` [seq, heads, d] at positions 0, 1, ...: feature ``i`` of the first half
    turns with feature ``i + d/2`` by ``position x theta^(-2i/d)``."""
    seq, _, d = x.shape
    half = d // 2
    angle = jnp.arange(seq, dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnums=(2,))
@_highest
def _norm(x, scale, eps):
    return rms_norm(x, scale, eps)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _gated(n, wi, wo, lower):
    f = wo.shape[0]
    wi = _w(wi, lower)
    return (jax.nn.silu(n @ wi[:, :f]) * (n @ wi[:, f:])) @ _w(wo, lower)


def _mlp(n, mlp, lower):
    """``W_down (silu(W_gate n) * W_up n)``, ``COLUMNS`` of its width at a time."""
    wi, wo = mlp["wi"], mlp["wo"]
    f = wo.shape[0]
    out = 0.0
    for a in range(0, f, COLUMNS):
        b = min(a + COLUMNS, f)
        piece = jnp.concatenate([wi[:, a:b], wi[:, f + a:f + b]], 1)
        out = jax.block_until_ready(out + _gated(n, piece, wo[a:b], lower))
    return out


def _padded(x, rows: int):
    """``x`` [seq, ...] with zeros behind it up to a whole number of ``rows``."""
    return jnp.pad(x, ((0, -x.shape[0] % rows),) + ((0, 0),) * (x.ndim - 1))


def _sizes(config):
    return {k: config["sparse_" + k] for k in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks", "window_size", "topk",
        "dense_len")}


# -- lightning attention ---------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
@_highest
def _lightning(n, p, slopes, heads, eps, theta, wrong):
    """One linear-attention mixer over ``n`` [seq, hidden], quadratically."""
    lower = wrong == LOWER
    seq = n.shape[0]
    q, k, v = ((n @ _w(p[name]["kernel"], lower)).reshape(seq, heads, -1) for name in "qkv")
    d = q.shape[-1]
    q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    if wrong != "no_rope_in_linear":
        q, k = rotate(q, theta), rotate(k, theta)
    slopes = jnp.zeros_like(slopes) if wrong == "no_decay" else slopes
    q = _padded(q / np.sqrt(d), ROWS).reshape(-1, ROWS, heads, d)
    at = jnp.arange(seq)

    def block(xs):
        qb, first = xs                      # [ROWS, heads, d], the block's first position
        t = first + jnp.arange(ROWS)
        apart = (t[:, None] - at[None, :]).astype(F32)

        def head(ys):
            qh, kh, vh, slope = ys          # [ROWS, d], [seq, d], [seq, d], a scalar
            decay = jnp.where(apart >= 0, jnp.exp(-slope * jnp.maximum(apart, 0.0)), 0.0)
            return ((qh @ kh.T) * decay) @ vh

        return jax.lax.map(
            head, (qb.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2), slopes))

    o = jax.lax.map(block, (q, jnp.arange(q.shape[0]) * ROWS))     # [blocks, heads, ROWS, d]
    o = o.transpose(0, 2, 1, 3).reshape(-1, heads, d)[:seq]
    if wrong != "no_output_norm":
        o = rms_norm(o, p["o_norm"]["scale"], eps)
    y = o.reshape(seq, heads * d)
    if wrong != "no_output_gate":
        y = y * jax.nn.sigmoid(n @ _w(p["g"]["kernel"], lower))
    return y @ _w(p["o"]["kernel"], lower)


# -- block-sparse attention --------------------------------------------------------


def compressed_keys(k, kernel: int, stride: int):
    """``k`` [seq, kv, d] -> ``[J, kv, d]``: the mean of ``kernel`` keys every
    ``stride``; none where the sequence is shorter than one kernel."""
    count = max((k.shape[0] - kernel) // stride + 1, 0)
    if count == 0:
        return jnp.zeros((0,) + k.shape[1:], F32)
    return jax.vmap(lambda a: jax.lax.dynamic_slice_in_dim(k, a, kernel, 0).mean(0))(
        stride * jnp.arange(count))


def block_scores(weights, seq: int, sizes, mean: bool = False):
    """``weights`` [rows, J] (a query's summed softmax over the compressed keys, 0
    where it does not see one) -> [rows, blocks]: over the compressed keys whose
    ``kernel_size`` tokens meet the block, the maximum (``mean``: the mean)."""
    kernel, stride, size = sizes["kernel_size"], sizes["kernel_stride"], sizes["block_size"]
    blocks, count = -(-seq // size), weights.shape[1]
    out = []
    for b in range(blocks):
        # key j covers [stride j, stride j + kernel): it meets [size b, size (b + 1))
        # when stride j + kernel > size b and stride j < size (b + 1)
        lo = max((size * b - kernel) // stride + 1, 0)
        hi = min(-(-size * (b + 1) // stride) - 1, count - 1)
        if hi < lo:
            out.append(jnp.zeros(weights.shape[0], F32))
            continue
        part = weights[:, lo:hi + 1]
        out.append(part.mean(-1) if mean else part.max(-1))
    return jnp.stack(out, -1)


def chosen_blocks(scores, t, sizes, wrong=None):
    """``scores`` [rows, blocks] for queries at ``t`` [rows] -> bool [rows, blocks]:
    the blocks a query past ``dense_len`` reads."""
    size, blocks = sizes["block_size"], scores.shape[1]
    b = jnp.arange(blocks)[None, :]
    t = t[:, None]
    begun = size * b <= t
    always = (b < sizes["init_blocks"]) | (size * (b + 1) - 1 >= t - sizes["window_size"] + 1)
    always = always & begun
    if wrong == "window_only":
        return always
    others = begun & ~always
    # a stable sort of the negated scores: among equals the lower block first
    order = jnp.argsort(jnp.where(others, -scores, jnp.inf), axis=-1)[:, :sizes["topk"]]
    best = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], order].set(True)
    return always | (best & others)


@functools.partial(jax.jit, static_argnums=(5, 6))
@_highest
def _sparse_rows(q, first, k, v, kc, sizes, wrong):
    """``ROWS`` queries ``q`` [ROWS, kv, g, d] from position ``first`` over all of ``k``,
    ``v`` [seq, kv, d] and the compressed keys ``kc`` [J, kv, d]: ``(out [ROWS, kv, g,
    d], blocks read [ROWS, kv, blocks])``."""
    sizes = dict(sizes)
    seq, kv, d = k.shape
    size = sizes["block_size"]
    t = first + jnp.arange(ROWS)
    scale = 1.0 / np.sqrt(d)
    causal = jnp.arange(seq)[None, :] <= t[:, None]
    selects = (t >= sizes["dense_len"]) & (wrong != "dense_always")
    blocks = -(-seq // size)
    weights = []
    for g in range(kv):
        if kc.shape[0] == 0:
            weights.append(jnp.zeros((ROWS, 0), F32))
            continue
        seen = (sizes["kernel_stride"] * jnp.arange(kc.shape[0]) + sizes["kernel_size"] - 1
                )[None, :] <= t[:, None]                                    # [ROWS, J]
        logit = jnp.einsum("qhd,jd->qhj", q[:, g], kc[:, g]) * scale
        p = jax.nn.softmax(jnp.where(seen[:, None], logit, -jnp.inf), -1)
        weights.append(jnp.where(seen, jnp.nan_to_num(p).sum(1), 0.0))
    if wrong == "one_selection_for_both_kv_heads":
        weights = [sum(weights)] * kv
    outs, read = [], []
    for g in range(kv):
        scores = block_scores(weights[g], seq, sizes, mean=wrong == "mean_pooled_blocks")
        chosen = chosen_blocks(scores, t, sizes, wrong)                      # [ROWS, blocks]
        of_key = jnp.repeat(chosen, size, axis=1)[:, :seq]
        mask = jnp.where(selects[:, None], of_key, True) & causal
        logit = jnp.einsum("qhd,sd->qhs", q[:, g], k[:, g]) * scale
        weight = jax.nn.softmax(jnp.where(mask[:, None], logit, -jnp.inf), -1)
        outs.append(jnp.einsum("qhs,sd->qhd", weight, v[:, g]))
        begun = size * jnp.arange(blocks)[None, :] <= t[:, None]
        read.append(jnp.where(selects[:, None], chosen, begun))
    return jnp.stack(outs, 1), jnp.stack(read, 1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
@_highest
def _sparse_projections(n, p, heads, kv, eps, theta, wrong):
    lower = wrong == LOWER
    seq = n.shape[0]
    q = (n @ _w(p["q"]["kernel"], lower)).reshape(seq, heads, -1)
    k = (n @ _w(p["k"]["kernel"], lower)).reshape(seq, kv, -1)
    v = (n @ _w(p["v"]["kernel"], lower)).reshape(seq, kv, -1)
    q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    if wrong == "rope_in_sparse":
        q, k = rotate(q, theta), rotate(k, theta)
    gate = jax.nn.sigmoid(n @ _w(p["g"]["kernel"], lower))
    return q, k, v, gate


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _sparse_out(y, gate, wo, wrong):
    if wrong != "no_output_gate":
        y = y * gate
    return y @ _w(wo, wrong == LOWER)


def _sparse(n, p, config, wrong, blocks_read=None):
    """One block-sparse mixer over ``n`` [seq, hidden]; the blocks each query read
    are appended to ``blocks_read`` where one is given, ``[seq, kv, blocks]``."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    sizes = _sizes(config)
    seq = n.shape[0]
    q, k, v, gate = _sparse_projections(
        n, p, heads, kv, config["rms_norm_eps"], float(config["rope_theta"]), wrong)
    kc = compressed_keys(k, sizes["kernel_size"], sizes["kernel_stride"])
    q = _padded(q, ROWS).reshape(-1, ROWS, kv, heads // kv, q.shape[-1])
    outs, read = [], []
    for i in range(q.shape[0]):
        out, chosen = jax.block_until_ready(_sparse_rows(
            q[i], jnp.int32(i * ROWS), k, v, kc, tuple(sorted(sizes.items())), wrong))
        outs.append(out), read.append(chosen)
    if blocks_read is not None:
        blocks_read.append(np.asarray(jnp.concatenate(read, 0)[:seq]))
    y = jnp.concatenate(outs, 0)[:seq].reshape(seq, -1)
    return _sparse_out(y, gate, p["o"]["kernel"], wrong)


# -- the model ---------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _head(x, ln_f, columns, eps, divisor, lower):
    return (rms_norm(x, ln_f, eps) / divisor) @ _w(columns, lower)


def _layer_of(tree, at):
    return jax.tree.map(lambda a: a[at], tree)


def mixers_of(config):
    """The mixer of every layer that runs: ``mixer_period`` (a period's mixers,
    comma-separated) repeated to ``num_hidden_layers``."""
    period = config["mixer_period"].split(",")
    return period * (config["num_hidden_layers"] // len(period))


def _hidden(program, tokens, config, wrong, blocks_read=None):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    lower, eps = wrong == LOWER, config["rms_norm_eps"]
    res = 1.0 if wrong == "no_depth_scale" else (
        config["scale_depth"] / np.sqrt(config["published_num_hidden_layers"]))
    period = config["mixer_period"].split(",")
    x = config["scale_emb"] * _w(program["wte"]["embedding"][jnp.asarray(tokens)], lower)
    periods = program["periods"]
    linear_at = 0
    for layer, mixer in enumerate(mixers_of(config)):
        at, i = divmod(layer, len(period))
        if mixer == SPARSE:
            p = _layer_of(periods["sparse"][period[:i].count(SPARSE)], at)
            mixed = _sparse(_norm(x, p["ln"]["scale"], eps), p, config, wrong, blocks_read)
        else:
            p = _layer_of(periods["linear"][period[:i].count(LINEAR)], at)
            mixed = _lightning(
                _norm(x, p["ln"]["scale"], eps), p, program["lightning_slopes"][linear_at],
                config["lightning_nh"], eps, float(config["rope_theta"]), wrong)
            linear_at += 1
        x = x + res * mixed
        mlp = _layer_of(periods["mlp"][i], at)
        x = jax.block_until_ready(x + res * _mlp(_norm(x, mlp["ln"]["scale"], eps), mlp, lower))
    return x


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one sequence
    ``tokens`` [seq], from the program's own weights; ``config`` is the
    configuration's file."""
    x = _hidden(program, tokens, config, wrong)[-last:]
    kernel = program["head"]["kernel"]
    return jnp.concatenate([
        _head(
            x, program["ln_f"]["scale"], kernel[:, a:a + VOCAB_COLUMNS], config["rms_norm_eps"],
            config["hidden_size"] / config["dim_model_base"], wrong == LOWER)
        for a in range(0, kernel.shape[1], VOCAB_COLUMNS)], -1)


def program_blocks(program, tokens, config):
    """The blocks every query of ``tokens`` [seq] read in every sparse layer, bool
    ``[sparse layers, seq, kv, blocks]``: every block begun for a query before
    ``sparse_dense_len``."""
    read = []
    _hidden(program, tokens, config, None, read)
    return np.stack(read)


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
