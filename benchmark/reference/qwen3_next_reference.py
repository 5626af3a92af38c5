"""Qwen3-Next (``qwen3_next``): the forward pass in plain ``jax.numpy`` and float32 at
the highest matmul precision: no kernels, no cache, no state store, no chunked form of
the recurrence; a loop over layers, the gated delta rule **in its one-token form** (the
definition: a scan over the tokens, a state a value head), the attention a head and a
block of queries at a time, the experts one at a time. The yardstick the serving path is
compared with, at a small size on the CPU (``tests/test_qwen3_next.py``,
``tests/benchmark/test_bench_qwen3_next.py``) and, at the published widths on the chip, in
every run's set-up (``program_logits``). Written from the published description (the row
of the catalog and Hugging Face's ``modeling_qwen3_next.py`` form where the row has no
key), not from ``ray_tpu/models/qwen3_next.py``.

With ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)`` (zero-centred) and ``d =
hidden_size``: ``h = E[id]``; every layer ``h += Mixer(N(h))``, ``h += FFN(N(h))``;
``logits = W_head N(h)``, the head not tied. Layer ``i`` is full attention where ``(i +
1) % full_attention_interval == 0``, else a delta layer:

* delta layer: ``q, k`` [``linear_num_key_heads`` x ``linear_key_head_dim``], ``v, z``
  [``linear_num_value_heads`` x ``linear_value_head_dim``], ``b, a``
  [``linear_num_value_heads``] from ``n``; ``[q, k, v] <- silu(conv(q | k | v))``, causal
  and depthwise, ``linear_conv_kernel_dim`` taps, no bias; ``q <- q / |q| /
  sqrt(linear_key_head_dim)``, ``k <- k / |k|`` a head (``x * rsqrt(sum x^2 + 1e-6)``);
  value head ``j`` reads key head ``j // (value heads / key heads)``; ``beta =
  sigmoid(b)``, ``alpha = exp(-exp(A_log) * softplus(a + dt_bias))``; ``S <- alpha_t S``,
  ``S <- S + k_t (beta_t (v_t - S^T k_t))^T``, ``o_t = S^T q_t`` from ``S = 0``; ``W_o
  [RMSNorm(o_t) * w * silu(z_t)]`` (a plain weight, ``rms_norm_eps``);
* full layer: ``[q | gate] = W_q n`` a head, ``k, v``; ``q, k <- N(.)`` a head; the
  first ``partial_rotary_factor x head_dim`` features rotate (feature ``i`` with ``i +
  half``) at ``rope_theta``; causal softmax of ``q . k / sqrt(head_dim)``; ``W_o [a *
  sigmoid(gate)]``;
* FFN: ``p = softmax(W_r n)`` over every scored expert, the ``num_experts_per_tok``
  largest over their sum, the held experts' part of ``sum_k w_k E_k(n)``, and
  ``sigmoid(w_s . n) E_shared(n)``; every expert ``W_d (silu(W_g n) * W_u n)``.

Departures of the program under test, which the comparison accounts for: the layers are
stacked by period (``delta``: a tuple of a period's delta layers, ``full``, ``ffn``: a
tuple of every layer's, leaves ``[periods, ...]``), the experts of every layer in one
stack ``[layers, held, ...]``; ``q | k | v | z`` and ``b | a`` are one matrix each in
that order, a head's query and gate columns side by side, the gate and the up
projection of an expert side by side. They are read as they lie.

The gate has a second number beside the logits' (``max_state_error`` in the
configuration's ``reference`` group): the first delta layer's state **as the state store
keeps it** for the gate's sequence, read back from the engine that serves the weights in
this process (:func:`served_states`), against this file's own float32 state after as
many tokens (:func:`first_delta_states`, :func:`state_error`). Ten choices of 512 experts
lie so close that a bfloat16 hidden state moves the tenth in most rows, which holds the
logits' reading at some hundredths whatever the state's precision; the first layer's
state has seen no expert, and a state rounded to bfloat16 reads three times what the
served float32 one does. ``program_logits`` makes the comparison and says what it read.

``wrong`` names one omission at a time, to show what the limit of the comparison
catches: ``"no_decay"`` (``alpha = 1``), ``"beta_one"``, ``"no_delta"`` (``v_t`` written,
not ``v_t - S^T k_t``), ``"no_qk_norm"`` (the L2 norms of q and k left out),
``"no_conv_silu"``, ``"no_z_gate"`` (``silu(z)`` left out), ``"no_output_gate"`` (the
attention's), ``"rotate_all"``, ``"norm_not_centred"`` (``(1 + g)`` read as ``g``),
``"no_shared_gate"``, ``"bf16_state"`` (the state rounded to bfloat16 after every token)
and ``"fp8_weights"``: every weight matrix rounded to float8 (e4m3) as it is read, the
nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROWS = 1024         # queries attended at a time: [ROWS, seq] scores a head
LOWER = "fp8_weights"
WRONG = (
    "no_decay", "beta_one", "no_delta", "no_qk_norm", "no_conv_silu", "no_z_gate",
    "no_output_gate", "rotate_all", "norm_not_centred", "no_shared_gate", "bf16_state")


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


def centred_norm(x, g, eps, centred: bool = True):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)``; not ``centred``: ``* g``."""
    scale = jnp.asarray(g, F32) + (1.0 if centred else 0.0)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def l2_normed(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


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


def route(n, router, k: int):
    """``(weights [seq, k], chosen [seq, k])``: the ``k`` most probable experts under a
    softmax over all of them, their probabilities over their sum."""
    top, chosen = jax.lax.top_k(jax.nn.softmax(n @ router, -1), k)
    return top / top.sum(-1, keepdims=True), chosen


@functools.partial(jax.jit, static_argnums=(2, 3))
@_highest
def _norm(x, g, eps, centred):
    return centred_norm(x, g, eps, centred)


def delta_rule(q, k, v, alpha, beta, wrong: Optional[str] = None, cuts=()):
    """The definition, a token at a time from ``S = 0``: ``q``, ``k`` [seq, heads, dk],
    ``v`` [seq, heads, dv], ``alpha``, ``beta`` [seq, heads] -> ``o`` [seq, heads, dv] and
    the state [heads, dk, dv] after each of ``cuts`` tokens (ascending)."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def one(state, xs):
        qt, kt, vt, at, bt = xs
        state = at[:, None, None] * state
        held = jnp.einsum("hk,hkv->hv", kt, state)
        write = vt if wrong == "no_delta" else vt - held
        state = state + kt[:, :, None] * (bt[:, None] * write)[:, None, :]
        if wrong == "bf16_state":
            # the explicit rounding: a cast there and back the TPU compiler drops
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    state, at, out, states = jnp.zeros((heads, dk, dv), F32), 0, [], []
    for end in tuple(cuts) + (q.shape[0],):
        state, o = jax.lax.scan(one, state, tuple(x[at:end] for x in (q, k, v, alpha, beta)))
        out.append(o)
        states.append(state)
        at = end
    return jnp.concatenate(out, 0), states[:-1]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
@_highest
def _delta_mixer(n, p, key_heads, value_heads, key_dim, eps, wrong, cuts=()):
    """One Gated DeltaNet mixer over ``n`` [seq, hidden], and its state after each of
    ``cuts`` tokens."""
    lower = wrong == LOWER
    seq = n.shape[0]
    value_dim = p["norm"]["scale"].shape[-1]
    key_inner, value_inner = key_heads * key_dim, value_heads * value_dim
    qkvz = n @ _w(p["in_qkvz"]["kernel"], lower)
    qkv, z = qkvz[:, :2 * key_inner + value_inner], qkvz[:, 2 * key_inner + value_inner:]
    ba = n @ _w(p["in_ba"]["kernel"], lower)
    b, a = ba[:, :value_heads], ba[:, value_heads:]
    taps = _w(p["conv"]["kernel"], lower)                       # [taps, channels]
    width = taps.shape[0]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    mixed = sum(taps[i] * padded[i:i + seq] for i in range(width))
    if wrong != "no_conv_silu":
        mixed = jax.nn.silu(mixed)
    q = mixed[:, :key_inner].reshape(seq, key_heads, key_dim)
    k = mixed[:, key_inner:2 * key_inner].reshape(seq, key_heads, key_dim)
    v = mixed[:, 2 * key_inner:].reshape(seq, value_heads, value_dim)
    if wrong != "no_qk_norm":
        q, k = l2_normed(q), l2_normed(k)
    q = q / np.sqrt(key_dim)
    per_key = value_heads // key_heads
    q, k = jnp.repeat(q, per_key, axis=1), jnp.repeat(k, per_key, axis=1)
    beta = jnp.ones_like(b) if wrong == "beta_one" else jax.nn.sigmoid(b)
    alpha = jnp.exp(
        -jnp.exp(jnp.asarray(p["A_log"], F32)) * jax.nn.softplus(a + jnp.asarray(p["dt_bias"], F32)))
    if wrong == "no_decay":
        alpha = jnp.ones_like(alpha)
    o, states = delta_rule(q, k, v, alpha, beta, wrong, cuts)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * jnp.asarray(p["norm"]["scale"], F32)
    y = o.reshape(seq, value_inner)
    if wrong != "no_z_gate":
        y = y * jax.nn.silu(z)
    return y @ _w(p["out"]["kernel"], lower), states


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
@_highest
def _projected(n, p, heads, kv, eps, theta, features, wrong):
    """q [seq, heads, d] and k [seq, kv, d], normed and rotated, v [seq, kv, d] and
    the gate [seq, heads, d]."""
    lower, centred = wrong == LOWER, wrong != "norm_not_centred"
    seq = n.shape[0]
    both = (n @ _w(p["q"]["kernel"], lower)).reshape(seq, heads, -1)
    d = both.shape[-1] // 2
    q, gate = both[..., :d], both[..., d:]
    k = (n @ _w(p["k"]["kernel"], lower)).reshape(seq, kv, d)
    v = (n @ _w(p["v"]["kernel"], lower)).reshape(seq, kv, d)
    q = centred_norm(q, p["q_norm"]["scale"], eps, centred)
    k = centred_norm(k, p["k_norm"]["scale"], eps, centred)
    return rotate(q, theta, features), rotate(k, theta, features), v, gate


@jax.jit
@_highest
def _attend_head(q, k, v):
    """One query head: ``q`` [seq, d] over its K/V head's ``k``, ``v`` [seq, d],
    ``ROWS`` queries at a time, every key up to the query's own."""
    seq, d = q.shape
    at = jnp.arange(seq)
    out = []
    for a in range(0, seq, ROWS):
        rows = jnp.arange(a, min(a + ROWS, seq))
        scores = (q[a:a + ROWS] @ k.T) / np.sqrt(d)
        scores = jnp.where(at[None, :] <= rows[:, None], scores, -jnp.inf)
        out.append(jax.nn.softmax(scores, -1) @ v)
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnums=(3, 4))
@_highest
def _gated_out(attended, gate, o, gated, lower):
    if gated:
        attended = attended * jax.nn.sigmoid(gate)
    return attended.reshape(attended.shape[0], -1) @ _w(o, lower)


def _attention(n, p, config, wrong):
    heads, kv, d = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"])
    features = d if wrong == "rotate_all" else int(config["partial_rotary_factor"] * d)
    q, k, v, gate = _projected(
        n, p, heads, kv, config["rms_norm_eps"], float(config["rope_theta"]), features, wrong)
    groups = heads // kv
    # one head's scores at a time: dispatched ahead, each holds its buffers
    attended = jnp.stack([
        jax.block_until_ready(_attend_head(q[:, h], k[:, h // groups], v[:, h // groups]))
        for h in range(heads)], 1)
    return _gated_out(attended, gate, p["o"]["kernel"], wrong != "no_output_gate", wrong == LOWER)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def _expert(n, wi, wo, lower):
    return expert(n, _w(wi, lower), _w(wo, lower))


@functools.partial(jax.jit, static_argnums=(2, 3))
@_highest
def _route(n, router, k, lower):
    return route(n, _w(router, lower), k)


@functools.partial(jax.jit, static_argnums=(2, 3))
@_highest
def _shared_gate(n, gate, gated, lower):
    return jax.nn.sigmoid(n @ _w(gate, lower))[:, None] if gated else jnp.ones((n.shape[0], 1), F32)


def _ffn(n, p, wi, wo, config, wrong):
    """The held experts' part of the routed sum, and the shared expert under its gate."""
    lower = wrong == LOWER
    top, chosen = _route(n, p["router"], config["num_experts_per_tok"], lower)
    out = _shared_gate(n, p["gate"], wrong != "no_shared_gate", lower) * _expert(
        n, p["wi"], p["wo"], lower)
    for e in range(wi.shape[0]):
        weight = jnp.where(chosen == config.get("expert_offset", 0) + e, top, 0.0).sum(-1)
        # one expert's output at a time: dispatched ahead, each holds its buffer
        out = jax.block_until_ready(out + weight[:, None] * _expert(n, wi[e], wo[e], lower))
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_highest
def _head(x, ln_f, head, eps, centred, lower):
    return centred_norm(x, ln_f, eps, centred) @ _w(head, lower)


def layer_weights(program, config, at: int):
    """Layer ``at``'s piece of the program's tree: ``(its mixer's weights, whether it
    is full attention, its FFN's weights, its experts' (wi, wo))``."""
    period = config["full_attention_interval"]
    of, i = divmod(at, period)
    full = i == period - 1
    stack = program["periods"]["full"] if full else program["periods"]["delta"][i]
    take = functools.partial(jax.tree.map, lambda a: a[of])
    return (
        take(stack), full, take(program["periods"]["ffn"][i]),
        tuple(program["experts"][name][at] for name in ("wi", "wo")))


def _hidden(program, tokens, config, wrong):
    assert wrong is None or wrong in WRONG + (LOWER,), wrong
    eps, centred = config["rms_norm_eps"], wrong != "norm_not_centred"
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    for at in range(config["num_hidden_layers"]):
        p, full, ffn, experts = layer_weights(program, config, at)
        n = _norm(x, p["ln"]["scale"], eps, centred)
        if full:
            mixed = _attention(n, p, config, wrong)
        else:
            mixed, _ = _delta_mixer(
                n, p, config["linear_num_key_heads"], config["linear_num_value_heads"],
                config["linear_key_head_dim"], eps, wrong)
        h = x + mixed
        x = jax.block_until_ready(
            h + _ffn(_norm(h, ffn["ln"]["scale"], eps, centred), ffn, *experts, config, wrong))
    return x


def first_delta_states(program, tokens, config, cuts, wrong: Optional[str] = None):
    """The first layer's state [value heads, dk, dv], float32, after each of ``cuts``
    tokens (ascending) of one sequence ``tokens``: the layer whose inputs are the
    embedding's rows under one norm, which no expert's choice has entered."""
    p, full, _, _ = layer_weights(program, config, 0)
    assert not full
    x = _w(program["wte"]["embedding"][jnp.asarray(tokens)], wrong == LOWER)
    n = _norm(x, p["ln"]["scale"], config["rms_norm_eps"], wrong != "norm_not_centred")
    _, states = _delta_mixer(
        n, p, config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["rms_norm_eps"], wrong, tuple(cuts))
    return [np.asarray(s) for s in states]


def state_error(got, want) -> float:
    """How far a state ``got`` [heads, dk, dv] is from ``want``: the root mean square of
    the difference over that of ``want``, a head at a time, and the worst head's. A
    head that forgets slowly sums the most writes and shows a rounding of the state
    itself most; what the inputs' precision costs is alike in every head."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(
        np.sqrt(((got - want) ** 2).mean((1, 2)) / (want ** 2).mean((1, 2)))))


def served_states(program, tokens, last: int):
    """What the engine that serves ``program`` in this process holds of the sequence
    ``tokens`` (a prompt and all but the newest of ``last`` new tokens, asked for last):
    ``{tokens held: the first delta layer's state as the state store keeps it}``, for
    the snapshot the prefix cache took in the prompt's prefill (the chunk form's work,
    through the store a chunk at a time) and for the slot the sequence left behind when
    it finished (the one-token form's, a decode call at a time)."""
    from ray_tpu.serve import llm

    engines = [e for e in llm.live_engines() if e.params is program]
    if len(engines) != 1:
        raise RuntimeError(
            f"{len(engines)} engines in this process serve these weights: the "
            f"configuration's max_state_error needs the one whose state it reads")
    (engine,) = engines
    prompt = list(tokens[:len(tokens) - last + 1])
    held, finished = engine.held_snapshot(prompt), engine.last_finished
    if held is None or finished is None or finished[0] != len(tokens):
        raise RuntimeError(
            f"the engine holds snapshot {held} of the prompt's {len(prompt)} tokens and "
            f"finished last {finished}: not the sequence of {len(tokens)} tokens asked about")
    return {n: engine.pool.read_state(slot)[0][0] for n, slot in (held, finished)}


def program_logits(program, tokens, config, last: int, wrong: Optional[str] = None):
    """Float32 logits [last, vocab] of the last ``last`` positions of one sequence
    ``tokens`` [seq], from the program's own weights; ``config`` is the
    configuration's file.

    Where the file's ``reference`` group gives ``max_state_error``, the call is a
    gate's: an engine in this process serves ``program`` and has just finished this
    sequence (:func:`served_states`). Its first delta layer's state, after the cached
    part of the prompt and at the sequence's end, is compared with this reference's
    (:func:`state_error`), the readings are printed, and a state in another dtype than
    the file's ``state_dtype`` or further off than the limit makes every logit nan,
    which no limit on the logits passes: the harness compares logits and nothing else.
    With ``wrong`` the readings say how far that omission's state is from the served
    one, and the logits are left as they are."""
    x = _hidden(program, tokens, config, wrong)
    logits = _head(
        x[-last:], program["ln_f"]["scale"], program["head"]["kernel"], config["rms_norm_eps"],
        wrong != "norm_not_centred", wrong == LOWER)
    limit = config.get("reference", {}).get("max_state_error")
    if limit is None:
        return logits
    served = served_states(program, tokens, last)
    ours = first_delta_states(program, tokens, config, sorted(served), wrong)
    errors = {n: state_error(served[n], s) for n, s in zip(sorted(served), ours)}
    dtypes = {str(s.dtype) for s in served.values()}
    ok = dtypes == {config["state_dtype"]} and all(e <= limit for e in errors.values())
    print(
        f"[reference] the served state of the first delta layer ({sorted(dtypes)}) against "
        f"{wrong or 'the reference'}'s, worst head, after {errors} tokens: limit {limit}"
        + ("" if ok or wrong else ": NOT CORRECT, the logits are made nan"), flush=True)
    return logits if ok or wrong else jnp.full_like(logits, jnp.nan)


def expert_layer(n, ffn, wi, wo, config):
    """One expert layer's share for ``n`` [seq, hidden] in float32: what the experts
    ``wi``, ``wo`` [held, ...] from ``config["expert_offset"]`` on give, **and the
    shared expert**, which every share computes alike. For the test that adds the shares
    up to the uncut layer, the shared expert counted once."""
    return _ffn(jnp.asarray(n, F32), ffn, wi, wo, config, None)


def shared_expert(n, ffn):
    """The shared expert under its gate alone, for the same test."""
    n = jnp.asarray(n, F32)
    return _shared_gate(n, ffn["gate"], True, False) * _expert(n, ffn["wi"], ffn["wo"], False)


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
