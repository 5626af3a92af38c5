"""``models/qwen3_next.py`` on the CPU at a tiny size, float32, seeded weights: the
chunk form of the gated delta rule against its one-token form from a random state
(several lanes, a padded tail, a chunk that starts mid-sequence, a kept state); a
prompt in chunks and decode through ``LLMEngine``, the pool (the full layers' rows) and
the state store (the delta layers' state and convolution tail) against the plain
reference's full forward pass (logits), several lanes of unlike lengths, sixteen lanes
once; padding and fresh lanes; a prefix hit that restores rows, state and tail and
gives bitwise logits; the shares of the expert layer, the shared expert counted once,
adding up to the uncut reference's; each omission the reference names; and the
configuration's own arithmetic."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import yardstick
from benchmark.manifest import published_keys
from benchmark.models import qwen3_next as arch
from benchmark.reference import qwen3_next_reference as ref
from ray_tpu.models import moe, qwen3_next as qn
from ray_tpu.serve import batching, llm

CFG = qn.qwen3_next_nano()
CHUNK = CFG.delta_chunk
#: blocks of two sub-chunks, chunks of four
ENGINE = dict(
    num_blocks=96, block_size=16, prefill_chunk=32, prefill_lanes=1, lane_buckets=(1, 4, 16),
    prefill_token_buckets=(32,), cache_buckets=(64, 128), state_slots=40)
with open(os.path.join(os.path.dirname(__file__), "benchmark", "tiny", "qwen3_next.json")) as f:
    KEYS = json.load(f)["model"]
#: what the served logits may differ from the reference's by, as a share of their
#: standard deviation (``yardstick.logits_error``), in float32 on both sides: the
#: chunk form sums a sub-chunk's writes through a triangular inverse where the
#: reference writes a token at a time, some float32 roundings apart (read 3e-6)
LIMIT = 2e-5
#: the chunk form against the one-token form on the same float32 inputs, absolutely,
#: on outputs and states of order 1: both are sums of at most 32 products of order 1 a
#: state element, rounded to float32 (2^-24) in another order; read 2.4e-7
FORMS_AGREE = 5e-6


@pytest.fixture(scope="module")
def program():
    # the init's 0.02 would leave every logit near 0: make the projections matter
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0 if path[-1].key in (
            "kernel", "wi", "wo", "embedding", "router", "gate") else a,
        CFG.init_params(5))


@pytest.fixture(scope="module")
def engine(program):
    return llm.LLMEngine(CFG, program, **ENGINE)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def _ask(seed, n, new, **more):
    return batching._Sequence(
        {"prompt": _prompt(seed, n), "max_new_tokens": new, "return_logits": True, **more})


def _drive(eng, seqs):
    steps = 0
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 400
    assert eng._flight is None
    for s in seqs:
        assert s._error is None, s._error
    return [s._result for s in seqs]


def _wanted(program, prompt, result):
    fed = prompt + result["tokens"][:-1]
    return np.asarray(ref.program_logits(program, fed, KEYS, len(result["tokens"])))


# -- (a) the configuration ----------------------------------------------------------


def test_the_configuration_counts_what_the_published_model_has():
    program = CFG.init_params(0)
    assert sum(x.size for x in jax.tree.leaves(program)) == CFG.num_params()
    assert (CFG.period, CFG.periods, CFG.delta_layers, CFG.cache_layers) == (4, 2, 6, 2)
    assert CFG.cached_layers == (False, False, False, True) * 2
    assert program["experts"]["wi"].shape == (8, 4, 64, 64)
    assert program["periods"]["delta"][0]["A_log"].dtype == jnp.float32
    # the scales are drawn: (1 + g) is not a plain norm with weight one
    assert float(jnp.abs(program["ln_f"]["scale"]).max()) > 0.05
    assert float(jnp.abs(program["periods"]["delta"][1]["norm"]["scale"] - 1).max()) > 0.05
    assert CFG.cache_arrays == ((1, 32), (1, 32))
    assert CFG.state_arrays == (
        (6, (8, 16, 16), jnp.float32), (6, (3, 2 * 64 + 128), jnp.float32))
    assert CFG.state_chunk == CHUNK == 8
    assert CFG.counters == moe.COUNTERS + ("delta_tokens", "delta_state_passes")
    # the benchmark's tiny model is this preset with the served cut's draw of the q/k norms
    assert arch.program_config(published_keys(KEYS)) == qn.qwen3_next_nano(qk_norm_mean=0.5)
    # the served cut: the issue's count, piece by piece
    cut = qn.Qwen3NextConfig(num_layers=8, num_experts=128, vocab_size=37984)
    assert cut.num_params() == 3_667_251_328
    assert (cut.period, cut.periods, cut.delta_layers, cut.cache_layers) == (4, 2, 6, 2)
    assert cut.cache_arrays == ((1, 512), (1, 512))
    assert [shape for _, shape, _ in cut.state_arrays] == [(32, 128, 128), (3, 8192)]
    assert sum(
        layers * int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        for layers, shape, dtype in cut.state_arrays) == 12_877_824
    with pytest.raises(ValueError, match="whole periods"):
        qn.qwen3_next_nano(num_layers=6)
    with pytest.raises(ValueError, match="not among"):
        qn.qwen3_next_nano(expert_offset=13)
    with pytest.raises(ValueError, match="power of two"):
        qn.qwen3_next_nano(delta_chunk=12)


# -- (b) the two forms of the rule ---------------------------------------------------


def _rule_inputs(seed, lanes, t, heads=8, dk=16, dv=16):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    q = qn.l2_normed(jnp.asarray(rng.normal(size=(lanes, t, heads, dk)), f32)) / 4.0
    k = qn.l2_normed(jnp.asarray(rng.normal(size=(lanes, t, heads, dk)), f32))
    v = jnp.asarray(rng.normal(size=(lanes, t, heads, dv)), f32)
    log_alpha = -jnp.asarray(rng.uniform(0.0, 1.0, size=(lanes, t, heads)), f32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, size=(lanes, t, heads)), f32)
    state = jnp.asarray(rng.normal(size=(lanes, heads, dk, dv)), f32)
    return state, q, k, v, log_alpha, beta


def _token_by_token(state, q, k, v, log_alpha, beta):
    outs, states = [], []
    for i in range(q.shape[1]):
        o, state = qn.delta_step(
            state, q[:, i], k[:, i], v[:, i], jnp.exp(log_alpha[:, i]), beta[:, i])
        outs.append(o), states.append(state)
    return jnp.stack(outs, 1), states


def test_the_chunk_form_is_the_one_token_form_from_a_random_state():
    """Three lanes over four sub-chunks from a random state, one lane with a padded
    tail (20 real tokens of 32: ``alpha`` 1 and ``beta`` 0 behind them), the states kept
    after sub-chunks 0, 1 and 3; then the same tokens as two calls, the second starting
    mid-sequence from the state the first left."""
    state, q, k, v, log_alpha, beta = _rule_inputs(0, 3, 32)
    real = np.ones((3, 32), bool)
    real[1, 20:] = False
    log_alpha, beta = (jnp.where(real[..., None], x, 0.0) for x in (log_alpha, beta))
    o, last, kept = qn.delta_chunked(
        state, q, k, v, log_alpha, beta, CHUNK, jnp.float32, keep=jnp.array([0, 1, 3]))
    want, states = _token_by_token(state, q, k, v, log_alpha, beta)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(
        np.where(real[..., None, None], o, 0), np.where(real[..., None, None], want, 0),
        atol=FORMS_AGREE, rtol=0)
    np.testing.assert_allclose(last, states[-1], atol=FORMS_AGREE, rtol=0)
    # the padded tail changed nothing: the state after the lane's 20th token
    np.testing.assert_allclose(last[1], states[19][1], atol=FORMS_AGREE, rtol=0)
    for lane, at in enumerate((7, 15, 31)):
        np.testing.assert_allclose(kept[lane], states[at][lane], atol=FORMS_AGREE, rtol=0)
    # two calls: the second's sub-chunks lie elsewhere in their call and give the same bits
    first = qn.delta_chunked(
        state, q[:, :8], k[:, :8], v[:, :8], log_alpha[:, :8], beta[:, :8], CHUNK, jnp.float32)
    second = qn.delta_chunked(
        first[1], q[:, 8:], k[:, 8:], v[:, 8:], log_alpha[:, 8:], beta[:, 8:], CHUNK,
        jnp.float32)
    np.testing.assert_array_equal(np.asarray(second[0]), np.asarray(o[:, 8:]))
    np.testing.assert_array_equal(np.asarray(second[1]), np.asarray(last))


def test_the_rule_writes_what_the_state_does_not_hold_yet():
    """The delta: a key written twice with the same value and ``beta`` 1 changes nothing
    the second time, where an additive recurrence would double it."""
    k = qn.l2_normed(jnp.ones((1, 1, 4), jnp.float32))
    v = jnp.arange(3, dtype=jnp.float32).reshape(1, 1, 3) + 1.0
    one = jnp.ones((1, 1), jnp.float32)
    _, once = qn.delta_step(jnp.zeros((1, 1, 4, 3), jnp.float32), k, k, v, one, one)
    read, twice = qn.delta_step(once, k, k, v, one, one)
    np.testing.assert_allclose(twice, once, atol=1e-6)
    np.testing.assert_allclose(read, v, atol=1e-6)


# -- (c) extend ----------------------------------------------------------------------


def _states(slots):
    return tuple(
        jnp.zeros((layers, slots) + tuple(shape), dtype)
        for layers, shape, dtype in CFG.state_arrays)


def test_padding_changes_no_state_and_a_fresh_lane_ignores_what_its_slot_holds(program):
    extend = CFG.make_extend_fn()
    rng = np.random.default_rng(2)
    caches = tuple(jnp.zeros((CFG.cache_layers, 2, 64, 1, 32), jnp.float32) for _ in range(2))
    dirty = tuple(
        jnp.asarray(rng.normal(size=a.shape), a.dtype) for a in _states(4))
    tokens = jnp.asarray([_prompt(1, 16), _prompt(2, 11) + [-1] * 5], jnp.int32)
    lengths, slots, none = jnp.zeros((2,), jnp.int32), jnp.asarray([1, 2], jnp.int32), jnp.zeros(
        (2,), jnp.int32)
    out = extend(program, tokens, lengths, *caches, *dirty, slots, none, none)
    clean = extend(program, tokens, lengths, *caches, *_states(4), slots, none, none)
    # a fresh lane (length 0) starts from zeros whatever its slot held
    np.testing.assert_array_equal(np.asarray(out[0][1, :11]), np.asarray(clean[0][1, :11]))
    for got, want, was in zip(out[4:6], clean[4:6], dirty):
        np.testing.assert_array_equal(np.asarray(got[:, 1:3]), np.asarray(want[:, 1:3]))
        # no other slot is touched (slot 0, nobody's, takes the state no one asked to keep)
        np.testing.assert_array_equal(np.asarray(got[:, 3]), np.asarray(was[:, 3]))
    # the padded tail changed nothing: the same 11 tokens alone leave the same state
    # (in float32 roundings: the sub-chunk of 3 real tokens is another sum)
    alone = extend(
        program, tokens[1:, :8], lengths[1:], *(c[:, 1:] for c in caches), *_states(4),
        slots[1:], none[1:], none[1:])
    more = extend(
        program, jnp.asarray([_prompt(2, 11)[8:] + [-1] * 5], jnp.int32),
        jnp.asarray([8], jnp.int32),
        *(c[:, 1:].at[:, :, :8].set(new) for c, new in zip(caches, alone[2:4])), *alone[4:6],
        slots[1:], none[1:], none[1:])
    for got, want in zip(more[4:6], clean[4:6]):
        np.testing.assert_allclose(
            np.asarray(got[:, 2]), np.asarray(want[:, 2]), atol=2e-5, rtol=1e-5)
    counted = dict(zip(CFG.counters, np.asarray(out[-1])))
    assert counted["delta_tokens"] == 6 * 27 and counted["delta_state_passes"] == 6 * 2
    assert counted["moe_tokens"] == 8 * 27


def test_chunked_prefill_then_decode_is_the_references_full_forward(program, engine):
    """90 tokens in chunks of 32 + 32 + 26 (four sub-chunks a chunk, the last with a
    padded tail), then 7 decode calls, through the pool and the state store."""
    [out] = _drive(engine, [_ask(1, 90, 8)])
    want = _wanted(program, _prompt(1, 90), out)
    assert out["logits"].shape == (8, CFG.vocab_size) and float(np.std(want)) > 0.1
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]


def test_lanes_of_unlike_lengths_and_sixteen_lanes_once(program, engine):
    """Three lanes of unlike lengths (one shorter than a sub-chunk) decode together
    against the reference; then sixteen sequences at once fill the 16-lane bucket, and
    each gets what it gets alone."""
    before = engine.stats()
    sizes = [(3, 5, 6), (4, 40, 5), (5, 71, 4)]
    seqs = [_ask(seed, n, new) for seed, n, new in sizes]
    for (seed, n, _), out in zip(sizes, _drive(engine, seqs)):
        want = _wanted(program, _prompt(seed, n), out)
        assert yardstick.logits_error(out["logits"], want) < LIMIT, (seed, n)
    many = [_ask(20 + i, 9 + 3 * i, 20) for i in range(16)]
    outs = _drive(engine, many)
    after = engine.stats()
    decode = after["calls"]["decode"]
    assert any(
        name.startswith("extend_decode_16x") and group["n"] > 0
        for name, group in after["programs"].items())
    assert decode["lanes_used"] - before["calls"]["decode"]["lanes_used"] >= 16 * 3
    for i in (0, 7, 15):
        want = _wanted(program, _prompt(20 + i, 9 + 3 * i), outs[i])
        assert yardstick.logits_error(outs[i]["logits"], want) < LIMIT, i


@pytest.mark.parametrize("n,reused", [(77, 64), (65, 64), (80, 64)], ids=["mid", "end", "whole"])
def test_a_prefix_hit_restores_rows_state_and_tail_bitwise(program, n, reused):
    eng = llm.LLMEngine(CFG, program, **{**ENGINE, "lane_buckets": (1,), "cache_buckets": (128,)})
    first, again = (_drive(eng, [_ask(9, n, 5)])[0] for _ in range(2))
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, reused)
    assert again["tokens"] == first["tokens"]
    np.testing.assert_array_equal(again["logits"], first["logits"])
    stats = eng.stats()
    assert stats["state_restores"] == 1
    assert stats["state_bytes_moved"] == sum(
        layers * int(np.prod(shape)) * 4 for layers, shape, _ in CFG.state_arrays)


def test_a_probe_reads_back_the_states_a_finished_sequence_left(program):
    """What a benchmark's reference reads the served state through: the engine is
    found among the process's live ones by the weights it serves; the prefix cache's
    snapshot of a prompt is named without being taken (no block's count moves, no
    state is restored), the sequence that finished last by its tokens and its slot;
    and both slots hold the first delta layer's state of the reference's one-token
    form after that many tokens, in the dtype the configuration states."""
    eng = llm.LLMEngine(CFG, program, **{**ENGINE, "lane_buckets": (1,), "cache_buckets": (128,)})
    assert eng in llm.live_engines() and eng.params is program
    prompt = _prompt(31, 77)
    assert eng.held_snapshot(prompt) is None and eng.last_finished is None
    (out,) = _drive(eng, [_ask(31, 77, 5)])
    def untouched():
        stats = eng.stats()
        return [stats[k] for k in (
            "state_restores", "state_slots_in_use", "kv_blocks_in_use", "prefix_hits")]

    before = untouched()
    tokens, slot = eng.held_snapshot(prompt)
    fed, last = eng.last_finished
    assert (tokens, fed) == (64, 77 + 5 - 1) and slot != last
    assert eng.held_snapshot(prompt[:64]) is None           # its last token is never reused
    assert eng.held_snapshot(prompt[:65]) == (64, slot)
    assert eng.held_snapshot(_prompt(32, 77)) is None
    assert untouched() == before
    want = ref.first_delta_states(program, prompt + out["tokens"][:-1], KEYS, (tokens, fed))
    for at, exact in zip((slot, last), want):
        held, tail = eng.pool.read_state(at)
        assert held.shape == (CFG.delta_layers,) + exact.shape and held.dtype == np.float32
        assert tail.shape == (CFG.delta_layers, CFG.conv_width - 1, CFG.conv_dim)
        assert ref.state_error(held[0], exact) < FORMS_AGREE


# -- (d) the expert layer --------------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(program):
    """Four chips hold four of the sixteen scored experts each: what the program's
    layer gives at each offset, summed, **the shared expert counted once**, is the plain
    reference's layer with all sixteen."""
    whole = qn.qwen3_next_nano(num_experts=16, expert_offset=0).init_params(11)
    ffn = jax.tree.map(lambda a: a[0] * 6.0, {
        k: v for k, v in whole["periods"]["ffn"][1].items() if k != "ln"})
    wi, wo = (whole["experts"][name][1] * 6.0 for name in ("wi", "wo"))
    n = jnp.asarray(np.random.default_rng(0).normal(size=(24, CFG.embed_dim)), jnp.float32)
    want = np.asarray(ref.expert_layer(n, ffn, wi, wo, {**KEYS, "expert_offset": 0}))
    shared = np.asarray(ref.shared_expert(n, ffn))
    weights, chosen = moe.softmax_top_k(n, ffn["router"], CFG.experts_per_token)
    shares, held = [], 0
    for offset in range(0, 16, 4):
        share, counters = moe.held_experts_ffn(
            n, weights, chosen, jnp.ones((24,), bool), wi[offset:offset + 4],
            wo[offset:offset + 4], offset)
        shares.append(np.asarray(share))
        held += int(counters[1])
        one = np.asarray(ref.expert_layer(
            n, ffn, wi[offset:offset + 4], wo[offset:offset + 4],
            {**KEYS, "expert_offset": offset}))
        np.testing.assert_allclose(shares[-1] + shared, one, rtol=2e-4, atol=2e-5)
    assert held == 24 * CFG.experts_per_token
    assert np.abs(want - shared).max() > 0.1 and np.abs(shared).max() > 0.1
    np.testing.assert_allclose(sum(shares) + shared, want, rtol=2e-4, atol=2e-5)


# -- (e) what the limit catches ---------------------------------------------------------


@pytest.fixture(scope="module")
def gate(program, engine):
    prompt = _prompt(31, 60)
    [out] = _drive(engine, [_ask(31, 60, 6)])
    return prompt + out["tokens"][:-1], out["logits"]


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_each_omission_differs_by_more_than_the_limit(program, gate, wrong):
    fed, served = gate
    other = np.asarray(ref.program_logits(program, fed, KEYS, 6, wrong))
    # not (error <= limit): unnormalised keys make the rule diverge, which reads nan
    assert not yardstick.logits_error(served, other) <= 1000 * LIMIT, wrong
