"""``models/mimo_v2_flash.py`` on the CPU at a tiny size, float32, seeded weights: a
prompt in chunks and decode through ``LLMEngine``, the pool (the full layers' rows)
and the window store (the sliding layers' newest 8 rows, a ring a sequence) against
the plain reference's full forward pass (logits), several lanes of unlike lengths,
one shorter than the window; the window store after any mix of chunks and steps
against what one chunk over the same tokens leaves; padding, fresh lanes and
snapshots; a prefix hit that restores rows and the windows' snapshot and gives bitwise
logits; a slot freed exactly once with its lease; the shares of the expert layer
adding up to the uncut reference's; ``masked_attention`` with ``sinks`` in interpret
mode against the dense form; each omission the reference names; and the
configuration's own arithmetic."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import yardstick
from benchmark.manifest import published_keys
from benchmark.models import mimo_v2_flash as arch
from benchmark.reference import mimo_v2_flash_reference as ref
from ray_tpu.models import cohere2_moe, mimo_v2_flash as mimo, moe
from ray_tpu.ops import attention
from ray_tpu.serve import batching, llm

CFG = mimo.mimo_v2_flash_nano()
WINDOW = CFG.sliding_window
#: blocks of one window, chunks of two
ENGINE = dict(
    num_blocks=64, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(32, 64, 128), state_slots=14)
with open(os.path.join(os.path.dirname(__file__), "benchmark", "tiny", "mimo_v2_flash.json")) as f:
    KEYS = json.load(f)["model"]
#: what the served logits may differ from the reference's by, as a share of their
#: standard deviation (``yardstick.logits_error``), in float32 on both sides
LIMIT = 2e-5


@pytest.fixture(scope="module")
def program():
    # the init's 0.02 would leave every logit near 0: make the projections matter
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0 if path[-1].key in ("kernel", "wi", "wo", "embedding") else a,
        CFG.init_params(5))


@pytest.fixture(scope="module")
def engine(program):
    return llm.LLMEngine(CFG, program, **ENGINE)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def _ask(seed, n, new, **more):
    return batching._Sequence(
        {"prompt": _prompt(seed, n), "max_new_tokens": new, "return_logits": True, **more})


def _drive(eng, seqs, each_step=lambda step: None):
    steps = 0
    while not all(s.done for s in seqs):
        each_step(steps)
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 400
    assert eng._flight is None
    return steps


def _served(eng, seed, n, new, **more):
    s = _ask(seed, n, new, **more)
    _drive(eng, [s])
    assert s._error is None, s._error
    return s._result


def _empty_engine(eng):
    """Evict every snapshot, so that a test starts from an empty cache."""
    with eng.pool._lock:
        while eng.prefix._evict_snapshot():
            pass
    assert eng.pool.in_use() == 0 and eng.pool.slots_in_use() == 0


def _reference(program, prompt, out, wrong=None):
    new = len(out["tokens"])
    return np.asarray(ref.program_logits(program, prompt + out["tokens"][:-1], KEYS, new, wrong))


def _ints(*values):
    return jnp.asarray(values, jnp.int32)


def _caches(lanes, cap):
    return tuple(
        jnp.zeros((CFG.cache_layers, lanes, cap) + each[:2], jnp.float32)
        for each in CFG.cache_arrays)


def _arenas(slots, seed=None):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(
            rng.normal(size=(layers, slots) + shape) if seed is not None
            else np.zeros((layers, slots) + shape), dtype)
        for layers, shape, dtype in CFG.state_arrays)


def _feed(extend, program, tokens, pieces, arenas, slot=1, snap=None):
    """``tokens`` of one lane through ``extend`` in ``pieces`` (a number: a chunk of so
    many real tokens in a call of 16; ``1``: a decode call), rows kept in a padded
    cache of 64; ``snap`` ``(piece index, tokens in, slot)``. Returns the last call's
    logits, the arenas and the cache."""
    caches, at, logits = _caches(1, 64), 0, None
    for index, n in enumerate(pieces):
        tc = 1 if n == 1 else 16
        fed = np.full((1, tc), -1, np.int32)
        fed[0, :n] = tokens[at:at + n]
        where = (_ints(slot), _ints(0), _ints(0))
        if snap is not None and snap[0] == index:
            where = (_ints(slot), _ints(snap[1]), _ints(snap[2]))
        logits, _, k, v, *arenas, _ = extend(
            program, jnp.asarray(fed), _ints(at), *caches, *arenas, *where)
        caches = tuple(c.at[:, :, at:at + n].set(new[:, :, :n]) for c, new in zip(caches, (k, v)))
        at += n
    return np.asarray(logits), tuple(arenas), caches


# -- (a) the configuration ----------------------------------------------------------


def test_the_configuration_counts_what_the_published_model_has():
    program = CFG.init_params(0)
    assert sum(x.size for x in jax.tree.leaves(program)) == CFG.num_params()
    assert (CFG.period, CFG.periods, CFG.window_layers, CFG.cache_layers) == (3, 2, 4, 3)
    assert CFG.cached_layers == (True, False, False, True, False, False, True)
    assert program["periods"]["sliding"]["attn"]["sinks"].shape == (2, 2, 8)
    assert program["periods"]["sliding"]["attn"]["sinks"].dtype == jnp.float32
    assert program["experts"]["wi"].shape == (6, 4, 64, 64)
    assert CFG.cache_arrays == ((1, 2 * 24), (1, 2 * 16))
    assert CFG.state_arrays == ((4, (8, 4 * 24), jnp.float32), (4, (8, 4 * 16), jnp.float32))
    assert CFG.state_chunk == WINDOW
    assert arch.program_config(published_keys(KEYS)) == CFG
    # the served cut: the issue's count, piece by piece
    cut = mimo.MiMoV2FlashConfig(vocab_size=19072, num_experts=16)
    assert cut.num_params() == 3_429_955_392
    assert (cut.period, cut.periods, cut.window_layers, cut.cache_layers) == (6, 1, 5, 2)
    assert cut.cache_arrays == ((1, 768), (1, 512))
    assert [layers * int(np.prod(shape)) * 2 for layers, shape, _ in cut.state_arrays] == [
        5 * 128 * 8 * 192 * 2, 5 * 128 * 8 * 128 * 2]
    assert sum(layers * int(np.prod(shape)) * 2 for layers, shape, _ in cut.state_arrays) == 3_276_800
    # the published list's first period is a sliding layer short: not what the program runs
    published = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7
    with pytest.raises(ValueError, match="whole periods"):
        mimo.MiMoV2FlashConfig(num_layers=48, sliding_layers=published)
    with pytest.raises(ValueError, match="whole periods"):
        mimo.MiMoV2FlashConfig(num_layers=7, sliding_layers=(1, 1, 1, 1, 1, 1, 0))
    with pytest.raises(ValueError, match="not among"):
        mimo.mimo_v2_flash_nano(expert_offset=13)


def test_a_ring_holds_the_newest_position_of_each_residue():
    held = np.asarray(mimo.ring_positions(_ints(0, 3, 8, 13, 16), 8))
    assert (held[0] < 0).all()                                  # nothing written
    assert held[1].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]    # shorter than the window
    assert held[2].tolist() == list(range(8))
    assert held[3].tolist() == [8, 9, 10, 11, 12, 5, 6, 7]
    assert held[4].tolist() == list(range(8, 16))


# -- (b) the window store -----------------------------------------------------------


@pytest.mark.parametrize(
    "pieces", [(16, 16, 11), (16, 11, 1, 1, 1, 1, 1), (5, 1, 1, 1), (16, 8, 1, 1, 16, 3)],
    ids=["chunks", "chunk-then-steps", "shorter-than-the-window", "mixed"])
def test_the_window_store_holds_the_last_rows_whatever_fed_them(program, pieces):
    """After any mix of chunks and steps a lane's slot holds, row by row, what one call
    over all its tokens leaves: the newest eight rows of every sliding layer, each at
    its position modulo 8. And the next token's logits agree."""
    extend = CFG.make_extend_fn()
    total = sum(pieces)
    tokens = _prompt(3, total + 1)
    _, mixed, _ = _feed(extend, program, tokens, pieces, _arenas(3, seed=1))
    # one call over all of them, from a slot full of rubbish too
    fed = np.full((1, 64), -1, np.int32)
    fed[0, :total] = tokens[:total]
    *_, k_whole, v_whole, _ = extend(
        program, jnp.asarray(fed), _ints(0), *_caches(1, 64), *_arenas(3, seed=2), _ints(1),
        _ints(0), _ints(0))
    held = np.asarray(mimo.ring_positions(_ints(total), WINDOW))[0]
    for got, want in zip(mixed, (k_whole, v_whole)):
        got, want = np.asarray(got)[:, 1], np.asarray(want)[:, 1]
        np.testing.assert_allclose(got[:, held >= 0], want[:, held >= 0], rtol=2e-5, atol=2e-5)
        assert np.abs(want[:, held >= 0]).max() > 0.1
    # what the next step reads from either is the same
    def step(arenas, caches):
        return np.asarray(extend(
            program, _ints(tokens[total])[None], _ints(total), *caches, *arenas, _ints(1),
            _ints(0), _ints(0))[0])

    _, _, caches = _feed(extend, program, tokens, (16,) * (total // 16) + (
        (total % 16,) if total % 16 else ()), _arenas(3))
    np.testing.assert_allclose(
        step(mixed, caches), step((k_whole, v_whole), caches), rtol=2e-4, atol=2e-4)


def test_padding_changes_no_ring_and_a_fresh_lane_ignores_what_its_slot_holds(program):
    extend = CFG.make_extend_fn()
    tokens = _prompt(4, 16)
    clean, after_clean, _ = _feed(extend, program, tokens, (16,), _arenas(3))
    dirty, after_dirty, _ = _feed(extend, program, tokens, (16,), _arenas(3, seed=7))
    np.testing.assert_array_equal(clean, dirty)             # length 0: every row masked
    for a, b in zip(after_clean, after_dirty):
        np.testing.assert_array_equal(np.asarray(a)[:, 1], np.asarray(b)[:, 1])
    # a call of padding alone (slot 2) beside a real lane leaves slot 1 and 2 as they were
    before = _arenas(3, seed=9)
    fed = np.full((2, 16), -1, np.int32)
    fed[0, :5] = tokens[:5]
    *_, k_after, v_after, _ = extend(
        program, jnp.asarray(fed), _ints(0, 12), *_caches(2, 64), *before, _ints(1, 2),
        _ints(0, 0), _ints(0, 0))
    for was, now in zip(before, (k_after, v_after)):
        np.testing.assert_array_equal(np.asarray(was)[:, 2], np.asarray(now)[:, 2])
        assert not np.array_equal(np.asarray(was)[:, 1], np.asarray(now)[:, 1])
        # the five rows written, the three others as they were
        np.testing.assert_array_equal(np.asarray(was)[:, 1, 5:], np.asarray(now)[:, 1, 5:])
    # a padded decode lane writes nothing real: slot 0 is nobody's
    *_, k_step, v_step, _ = extend(
        program, _ints(tokens[5], -1)[:, None], _ints(5, 0), *_caches(2, 64), k_after, v_after,
        _ints(1, 0), _ints(0, 0), _ints(0, 0))
    for was, now in zip((k_after, v_after), (k_step, v_step)):
        changed = (np.asarray(was) != np.asarray(now)).any(-1)      # [layers, slots, rows]
        assert changed[:, 1, 5].all() and changed.sum() == CFG.window_layers
    assert np.asarray(k_step).shape == (CFG.window_layers, 3, WINDOW, 4 * 24)


def test_a_kept_window_lands_in_its_slot_and_a_copy_of_it_continues_bitwise(program):
    """``snap_at`` 8 of a chunk that begins at 16: slot 2 gets the ring after 24
    tokens, which is what a call that ends there leaves; a sequence that starts from a
    copy of it gives the logits of the one that ran through, bit for bit."""
    extend = CFG.make_extend_fn()
    tokens = _prompt(6, 40)
    through, arenas, caches = _feed(
        extend, program, tokens, (16, 16, 8), _arenas(4), snap=(1, 8, 2))
    _, ended, _ = _feed(extend, program, tokens, (16, 8), _arenas(4))
    for kept, want in zip(arenas, ended):
        np.testing.assert_array_equal(np.asarray(kept)[:, 2], np.asarray(want)[:, 1])
        assert not np.asarray(kept)[:, 3].any()                 # no other slot touched
    # slot 3 <- the snapshot; the last 16 tokens again, from position 24, rows restored
    copied = tuple(a.at[:, 3].set(a[:, 2]) for a in arenas)
    fed = np.full((1, 16), -1, np.int32)
    fed[0, :16] = tokens[24:40]
    again = extend(
        program, jnp.asarray(fed), _ints(24), *(c.at[:, :, 24:].set(0) for c in caches), *copied,
        _ints(3), _ints(0), _ints(0))[0]
    np.testing.assert_array_equal(np.asarray(again)[0, 15], through[0, 7])      # position 39


# -- (c) through the engine ---------------------------------------------------------


def test_chunked_prefill_then_decode_is_the_references_full_forward(program, engine):
    """A prompt of 77 tokens over five chunks of 16, then 8 decode steps, each reading
    its slot's eight rows and the full layers' gathered ones."""
    _empty_engine(engine)
    before = engine.stats()
    out = _served(engine, 7, 77, 8)
    want = _reference(program, _prompt(7, 77), out)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, rtol=2e-4, atol=2e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    after = engine.stats()
    counted = {k: after[k] - before[k] for k in CFG.counters}
    seen = [t + 1 for t in range(77 + 7)]
    assert counted["full_keys"] == CFG.cache_layers * sum(seen)
    assert counted["window_keys"] == CFG.window_layers * sum(min(s, WINDOW) for s in seen)
    assert counted["moe_tokens"] == 6 * (77 + 7)
    assert 0 < counted["moe_assignments"] < 4 * counted["moe_tokens"]
    # nothing of a sliding layer is gathered: three layers' rows a slot, and no window slot
    assert engine.pool.layers == 3 and engine.pool.cache_bytes(1) == 3 * 2 * (24 + 16) * 4
    assert after["window_slots"] == 0 == after["window_slots_outside"]
    assert after["state_bytes_moved"] == before["state_bytes_moved"]


def test_lanes_of_unlike_lengths_one_shorter_than_the_window(program, engine):
    """Three requests side by side: 5, 40 and 70 prompt tokens (the first decodes with
    rows of its ring never written), each against the reference's own forward."""
    _empty_engine(engine)
    asks = [(50, 5, 9), (51, 40, 7), (52, 70, 5)]
    before = engine.stats()
    seqs = [_ask(*ask) for ask in asks]
    _drive(engine, seqs)
    after = engine.stats()
    assert after["calls"]["decode"]["lanes_used"] - before["calls"]["decode"]["lanes_used"] > (
        after["calls"]["decode"]["n"] - before["calls"]["decode"]["n"])     # lanes shared calls
    for s, (seed, n, _) in zip(seqs, asks):
        assert s._error is None, s._error
        want = _reference(program, _prompt(seed, n), s._result)
        assert yardstick.logits_error(s._result["logits"], want) < LIMIT, n
        assert s._result["tokens"] == [int(t) for t in want.argmax(-1)]


@pytest.mark.parametrize("n,reused", [(77, 72), (65, 64), (80, 72)], ids=["mid", "end", "whole"])
def test_a_prefix_hit_restores_rows_and_the_windows_bitwise(engine, n, reused):
    """The reusable end of a prompt lies in the middle of its last chunk, at the end of
    the chunk before, or a block before the prompt's own end. The repeat reads the
    pool's rows of the full layers and the snapshot of the five windows that ends
    there; its logits are the first's, bit for bit, and so is a longer prompt's
    beginning against an engine without a prefix cache."""
    _empty_engine(engine)
    before = engine.stats()
    first = _served(engine, 20 + n, n, 6)
    again = _served(engine, 20 + n, n, 6)
    after = engine.stats()
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, reused)
    assert again["tokens"] == first["tokens"]
    np.testing.assert_array_equal(again["logits"], first["logits"])
    assert after["state_restores"] - before["state_restores"] == 1
    assert after["state_bytes_moved"] - before["state_bytes_moved"] == engine.pool.state_bytes
    assert engine.pool.state_bytes == CFG.window_layers * WINDOW * 4 * (24 + 16) * 4
    assert after["state_snapshots"] == 1 == after["state_slots_in_use"]
    longer = batching._Sequence({
        "prompt": _prompt(20 + n, n)[:reused] + _prompt(99, 20), "max_new_tokens": 2,
        "return_logits": True})
    _drive(engine, [longer])
    assert longer._result["prefix_cached_tokens"] == reused
    alone = llm.LLMEngine(CFG, engine._params, **{**ENGINE, "prefix_caching": False})
    fresh = batching._Sequence({
        "prompt": _prompt(20 + n, n)[:reused] + _prompt(99, 20), "max_new_tokens": 2,
        "return_logits": True})
    _drive(alone, [fresh])
    np.testing.assert_array_equal(longer._result["logits"], fresh._result["logits"])


def test_two_lanes_and_one_cancelled_under_way_leave_the_other_alone(engine):
    _empty_engine(engine)
    asks = [(40, 70, 10), (41, 20, 14)]
    alone = [_served(engine, *ask) for ask in asks]
    _empty_engine(engine)
    cancel = threading.Event()
    together = [_ask(*ask) for ask in asks]
    dropped = _ask(42, 45, 30, **{llm._CANCEL_KEY: cancel})

    def each_step(step):
        if step == 8:
            cancel.set()

    _drive(engine, together + [dropped], each_step)
    assert dropped._error is not None and "Cancelled" in type(dropped._error).__name__
    for s, want in zip(together, alone):
        assert s._error is None and s._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(s._result["logits"], want["logits"], rtol=2e-5, atol=2e-5)
    stats = engine.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"]
    assert stats["kv_blocks_in_use"] == stats["prefix_cached_blocks"]
    # asked again, the dropped request is served from rows never written
    assert _served(engine, 42, 45, 4)["tokens"] == _served(engine, 42, 45, 4)["tokens"]


def test_the_engine_asks_for_blocks_that_end_at_a_whole_window(program):
    with pytest.raises(ValueError, match="keeps a state every 8 tokens"):
        llm.LLMEngine(CFG, program, **{**ENGINE, "block_size": 4, "cache_buckets": (64,)})


# -- (d) the expert layer's shares ----------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(program):
    """Four chips hold four of the sixteen scored experts each: what the program's
    layer gives at each offset, summed, is the plain reference's layer with all
    sixteen (there is no shared expert to count once)."""
    whole = mimo.mimo_v2_flash_nano(num_experts=16, expert_offset=0).init_params(11)
    block = jax.tree.map(lambda a: a[0] * 6.0, whole["periods"]["full"]["moe"])
    block["bias"] = block["bias"] / 6.0
    wi, wo = (whole["experts"][name][2] * 6.0 for name in ("wi", "wo"))
    n = jnp.asarray(np.random.default_rng(0).normal(size=(24, CFG.embed_dim)), jnp.float32)
    want = np.asarray(ref.expert_layer(n, block, wi, wo, {**KEYS, "expert_offset": 0}))
    weights, chosen = moe.sigmoid_bias_top_k(
        n, block["router"], block["bias"], CFG.experts_per_token, CFG.routed_scale)
    shares, held = [], 0
    for offset in range(0, 16, 4):
        share, counters = moe.held_experts_ffn(
            n, weights, chosen, jnp.ones((24,), bool), wi[offset:offset + 4],
            wo[offset:offset + 4], offset)
        shares.append(np.asarray(share))
        held += int(counters[1])
        one = np.asarray(ref.expert_layer(
            n, block, wi[offset:offset + 4], wo[offset:offset + 4],
            {**KEYS, "expert_offset": offset}))
        np.testing.assert_allclose(shares[-1], one, rtol=2e-4, atol=2e-5)
    assert held == 24 * CFG.experts_per_token
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(sum(shares), want, rtol=2e-4, atol=2e-5)


# -- (e) the kernel's sinks ----------------------------------------------------------


def _dense_attend(q, k, v, mask, scale, sinks=None):
    logit = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    logit = jnp.where(mask[:, None, None], logit, -1e30)
    if sinks is not None:
        sink = jnp.broadcast_to(sinks[None, :, :, None, None], logit.shape[:-1] + (1,))
        logit = jnp.concatenate([logit, sink], -1)
    weight = jax.nn.softmax(logit, -1)[..., :k.shape[1]]
    return jnp.einsum("bhgqk,bkhd->bqhgd", weight, v)


@pytest.mark.parametrize("sunk", [False, True], ids=["no-sinks", "sinks"])
@pytest.mark.parametrize("groups", [4, 16], ids=["one-tile", "two-tiles-of-heads"])
def test_masked_attention_with_sinks_is_the_dense_form(sunk, groups):
    """In interpret mode, keys wider than values, several key tiles, a lane that stops
    short; with ``groups`` 16 the accumulator's room splits a K/V head's query heads
    over two tiles of the grid, and each must start from its own heads' sinks."""
    rng = np.random.default_rng(groups)
    b, t, kv, d, dv, s = 2, 16, 2, 24, 16, 40
    q = jnp.asarray(rng.normal(size=(b, t, kv, groups, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, dv)), jnp.float32)
    live = _ints(s, 24)
    mask = jnp.asarray(rng.random((b, t, s)) < 0.5) & (jnp.arange(s)[None, None] < live[:, None, None])
    mask = mask.at[0, 3].set(False)                        # a query that sees nothing
    sinks = jnp.asarray(2.0 * rng.normal(size=(kv, groups)), jnp.float32) if sunk else None
    room = attention.MASKED_ACC_BYTES
    try:
        if groups == 16:
            attention.MASKED_ACC_BYTES = 8 * 8 * dv * 4     # eight heads a tile
            assert attention._heads_a_tile(groups, 8, dv) == 8
        got = attention.masked_attention(
            q, k, v, mask, live, sinks=sinks, interpret=True, block_q=8, block_k=16)
    finally:
        attention.MASKED_ACC_BYTES = room
    want = _dense_attend(q, k, v, mask, 1 / np.sqrt(d), sinks)
    seeing = np.asarray(mask.any(-1))
    np.testing.assert_allclose(
        np.asarray(got)[seeing], np.asarray(want)[seeing], rtol=2e-5, atol=2e-5)
    if sunk:
        assert not np.asarray(got)[0, 3].any()             # the sink alone: no value
        bare = _dense_attend(q, k, v, mask, 1 / np.sqrt(d))
        assert np.abs(np.asarray(bare) - np.asarray(want))[seeing].max() > 0.05


# -- (f) what the comparison catches ------------------------------------------------


@pytest.fixture(scope="module")
def gate(program, engine):
    _empty_engine(engine)
    out = _served(engine, 13, 100, 6)
    return out, _reference(program, _prompt(13, 100), out)


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_each_omission_differs_by_more_than_the_limit(program, gate, wrong):
    out, want = gate
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    other = _reference(program, _prompt(13, 100), out, wrong)
    assert yardstick.logits_error(out["logits"], other) > 50 * LIMIT, wrong


def test_window_slots_count_what_a_call_gathers(program):
    """Command A+'s windows are per-token rows under a mask, and every call gathers
    them; this model's sliding layers gather nothing, attribute and all."""
    assert sum(CFG.sliding_layers) == 4 and CFG.sliding_window == 8
    eng = llm.LLMEngine(CFG, program, **{**ENGINE, "prefix_caching": False})
    _served(eng, 1, 40, 3)
    assert eng.window_slots == 0 == eng.window_slots_outside and eng._window_layers == 0
    other = cohere2_moe.cohere2_moe_nano()
    sizes = {k: v for k, v in ENGINE.items() if k != "state_slots"}
    eng = llm.LLMEngine(other, **{**sizes, "prefix_caching": False})
    seq = batching._Sequence({"prompt": _prompt(1, 40), "max_new_tokens": 3})
    _drive(eng, [seq])
    assert eng._window_layers == sum(other.sliding_layers) == 4
    assert eng.window_slots > eng.window_slots_outside > 0
