"""``models/granitemoehybrid.py`` and the engine's state store on the CPU at a
tiny size: the chunked recurrence against the token-by-token one, the decode
kernel (interpreted) against ``ssm_step``, ``extend`` on the state arenas where
the pool keeps them (scrambled slots against slots in order, bit for bit; padded
lanes; a kept state in the slot it is told), a prompt in
chunks and decode through ``LLMEngine`` against the plain reference's full
forward pass (logits), state carried from chunk to chunk, a snapshot taken in
the middle of a chunk and restored, lanes that join and leave, a lane's state
behind its token with a call in flight, what is left after a drain and after an
eviction, a pool too small for one more slot, and the configuration's own
arithmetic. float32 throughout."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt, granitemoehybrid as hybrid
from ray_tpu.serve import batching, llm
from ray_tpu.serve.handle import BackPressureError

CFG = hybrid.granite_hybrid_nano()
#: blocks of 8 (one sub-chunk), chunks of two blocks: a prompt's reusable end
#: lies at a chunk's end or in its middle, by its length
ENGINE = dict(
    num_blocks=48, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(64, 128), state_slots=14)
KEYS = dict(
    num_hidden_layers=CFG.num_layers, layer_period=CFG.period,
    attention_layer_offset=CFG.attention_at, num_attention_heads=CFG.num_heads,
    num_key_value_heads=CFG.kv_heads, hidden_size=CFG.embed_dim, mamba_n_heads=CFG.ssm_heads,
    mamba_d_state=CFG.ssm_state, rms_norm_eps=CFG.norm_eps,
    embedding_multiplier=CFG.embedding_multiplier, residual_multiplier=CFG.residual_multiplier,
    attention_multiplier=CFG.attention_multiplier, logits_scaling=CFG.logits_scaling)


@pytest.fixture(scope="module")
def program():
    # the init's 0.02 would leave every logit near 0: make the projections matter
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0 if path[-1].key in ("kernel", "wi", "wo", "embedding")
        and "conv" not in [getattr(k, "key", None) for k in path] else a,
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


def _reference(program, prompt, out):
    from benchmark.reference import granitemoehybrid_reference as ref

    fed = prompt + out["tokens"][:-1]
    return np.asarray(ref.program_logits(program, fed, KEYS, len(out["tokens"])))


def _empty_engine(eng):
    """Evict every snapshot, so that a test starts from an empty cache."""
    with eng.pool._lock:
        while eng.prefix._evict_snapshot():
            pass
    assert eng.pool.in_use() == 0 and eng.pool.slots_in_use() == 0


def _arenas(slots, seed=None):
    """The pool's state arenas ``[layers, slots, ...]``: zeros, or drawn."""
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(
            rng.normal(size=(layers, slots) + shape) if seed is not None
            else np.zeros((layers, slots) + shape), dtype)
        for layers, shape, dtype in CFG.state_arrays)


def _ints(*values):
    return jnp.asarray(values, jnp.int32)


def _caches(lanes, cap):
    return tuple(
        jnp.zeros((CFG.cache_layers, lanes, cap) + each, jnp.float32) for each in CFG.cache_arrays)


def test_the_configuration_counts_what_the_published_model_has():
    full = hybrid.GraniteMoeHybridConfig()
    assert full.num_params() == 3_191_396_096
    assert full.layer_types.count("attention") == 4 and full.ssm_layers == 36
    assert [i for i, t in enumerate(full.layer_types) if t == "attention"] == [5, 15, 25, 35]
    assert full.cache_layers == 4 and full.cache_arrays == ((1, 512), (1, 512))
    (layers, state, dtype), (_, tail, _) = full.state_arrays
    assert (layers, state, tail) == (36, (64, 64, 128), (3, 4352)) and dtype == jnp.float32
    # what a sequence and a cached token weigh
    assert 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    assert 4 * 2 * 512 * 2 == 8192
    assert sum(a.size for a in jax.tree.leaves(CFG.init_params(0))) == CFG.num_params()
    with pytest.raises(ValueError, match="whole periods"):
        hybrid.granite_hybrid_nano(num_layers=9)


@pytest.mark.parametrize("lanes", [1, 3])
def test_the_chunked_recurrence_is_the_token_by_token_one(lanes):
    rng = np.random.default_rng(lanes)
    t, heads, p, n = 32, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(lanes, t, heads, p)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(lanes, t, n)), jnp.float32) for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(lanes, t, heads)), jnp.float32)
    dt = dt.at[0, 27:].set(0.0)         # padded tokens at a lane's end
    a = -jnp.asarray(rng.uniform(1, 16, size=(heads,)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(lanes, heads, p, n)), jnp.float32)
    y, last, between = hybrid.ssm_chunked(state, x, dt, a, b, c, 8, jnp.float32)
    s, rows, at_16 = state, [], None
    for i in range(t):
        row, s = hybrid.ssm_step(s, x[:, i], dt[:, i], a, b[:, i], c[:, i])
        rows.append(row)
        at_16 = s if i == 15 else at_16
    np.testing.assert_allclose(y, jnp.stack(rows, 1), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last, s, rtol=1e-4, atol=1e-5)
    assert between.shape == (4,) + state.shape
    np.testing.assert_allclose(between[1], at_16, rtol=1e-4, atol=1e-5)
    # a padded token neither decays nor feeds the state
    np.testing.assert_array_equal(np.asarray(between[3][0]), np.asarray(last[0]))


@pytest.mark.parametrize("heads", [8, 2])
def test_the_decode_kernel_is_ssm_step_on_the_slots_it_is_given(heads):
    """Interpreted: four lanes of a layer of an arena of seven slots, one of
    them padding, one fresh: ``y`` and the lanes' slots are ``ssm_step``'s, and
    every other slot of every layer is bit for bit what it was."""
    rng = np.random.default_rng(heads)
    layers, slots, all_heads, p, n = 3, 7, 8, 16, 128
    arena = jnp.asarray(rng.normal(size=(layers, slots, all_heads, p, n)), jnp.float32)
    at, where = 1, _ints(5, 2, 0, 6)
    real, fresh = np.array([1, 1, 0, 1], bool), np.array([0, 1, 1, 0], bool)
    x = jnp.asarray(rng.normal(size=(4, all_heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(4, all_heads)) * real[:, None], jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(all_heads,)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(4, n)), jnp.float32) for _ in range(2))
    y, after = hybrid.ssm_step_slots(
        arena, jnp.int32(at), where, jnp.asarray(real), jnp.asarray(fresh), x, dt, a, b, c,
        heads=heads, interpret=True)
    want_y, want = hybrid.ssm_step(
        jnp.where(fresh[:, None, None, None], 0.0, arena[at, where]), x, dt, a, b, c)
    lanes = np.flatnonzero(real)
    np.testing.assert_allclose(y[lanes], want_y[lanes], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(after[at, where[lanes]], want[lanes], rtol=1e-6, atol=1e-6)
    assert not np.asarray(y[2]).any()
    untouched = np.ones((layers, slots), bool)
    untouched[at, np.asarray(where)[lanes]] = False
    np.testing.assert_array_equal(np.asarray(after)[untouched], np.asarray(arena)[untouched])


def _in_place(program, extend, tokens, lengths, slots, snap_at, snap_slots, arenas, seed=3):
    """``extend`` over drawn caches on ``arenas`` with the lanes in ``slots``."""
    rng = np.random.default_rng(seed)
    caches = tuple(
        jnp.asarray(rng.normal(size=c.shape), c.dtype) for c in _caches(len(slots), 64))
    return extend(
        program, jnp.asarray(tokens, jnp.int32), _ints(*lengths), *caches, *arenas,
        _ints(*slots), _ints(*snap_at), _ints(*snap_slots))


def _moved(arenas, pairs, slots=9):
    """Arenas of zeros but for slot ``dst`` holding ``arenas``' ``src``."""
    src, dst = (list(x) for x in zip(*pairs))
    return tuple(jnp.zeros_like(a[:, :slots]).at[:, dst].set(a[:, src]) for a in arenas)


FORMS = {
    # three lanes and one of padding: a decode call, and a chunk that keeps the
    # states 8 and 16 tokens in of its first two lanes
    "decode": dict(
        tokens=[[11], [12], [13], [-1]], lengths=(5, 9, 3, 0), snap_at=(0,) * 4,
        snap_slots=(0,) * 4),
    "chunk": dict(
        tokens=[_prompt(1, 16), _prompt(2, 16), _prompt(3, 11) + [-1] * 5, [-1] * 16],
        lengths=(8, 0, 16, 0), snap_at=(8, 16, 0, 0), snap_slots=(4, 8, 0, 0)),
}


@pytest.mark.parametrize("form", FORMS)
def test_lanes_in_scrambled_slots_are_bit_for_bit_lanes_in_order(program, form):
    """What the parent made of gather -> ``extend`` -> scatter: the lanes'
    states in slots 7, 2, 5 of nine give the bits they give in slots 1, 2, 3,
    as do the kept states wherever they are told to go, and no slot that no lane
    names is written."""
    call = FORMS[form]
    extend = CFG.make_extend_fn()
    drawn = _arenas(9, seed=4)
    scrambled, kept = (7, 2, 5, 0), call["snap_slots"]
    out = _in_place(program, extend, **{**call, "slots": scrambled}, arenas=drawn)
    in_order = _moved(drawn, zip(scrambled[:3], (1, 2, 3)))
    moved_to = tuple({4: 5, 8: 6, 0: 0}[k] for k in kept)
    want = _in_place(
        program, extend, **{**call, "slots": (1, 2, 3, 0), "snap_slots": moved_to},
        arenas=in_order)
    for got, expected in zip(out[:4], want[:4]):          # logits, hidden, K and V rows
        np.testing.assert_array_equal(np.asarray(got)[:3], np.asarray(expected)[:3])
    written = {0, *scrambled, *kept}
    for got, expected, before in zip(out[4:6], want[4:6], drawn):
        got, expected, before = (np.asarray(x) for x in (got, expected, before))
        np.testing.assert_array_equal(got[:, scrambled[:3]], expected[:, [1, 2, 3]])
        for mine, theirs in zip(kept, moved_to):
            if mine:
                np.testing.assert_array_equal(got[:, mine], expected[:, theirs])
                assert np.abs(got[:, mine] - before[:, mine]).max() > 1e-3
        rest = [s for s in range(9) if s not in written]
        np.testing.assert_array_equal(got[:, rest], before[:, rest])
        assert np.abs(got[:, scrambled[:3]] - before[:, scrambled[:3]]).max() > 1e-3
    assert list(np.asarray(out[-1])) == list(np.asarray(want[-1]))


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_padded_lanes_leave_every_slot_but_slot_0_untouched(program, path, built_for_tpu,
                                                            monkeypatch):
    """One real lane in the four-lane bucket, through ``extend``'s decode form
    both ways (the chip's kernel, interpreted): slot 6 moves, slot 0 is
    nobody's, the seven others hold their bits; and both ways agree."""
    if path == "kernel":
        monkeypatch.setattr(
            hybrid, "ssm_step_slots", functools.partial(hybrid.ssm_step_slots, interpret=True))
    built_for_tpu(path == "kernel")
    drawn = _arenas(9, seed=6)
    call = dict(
        tokens=[[21], [-1], [-1], [-1]], lengths=(5, 0, 0, 0), slots=(6, 0, 0, 0),
        snap_at=(0,) * 4, snap_slots=(0,) * 4)
    out = _in_place(program, CFG.make_extend_fn(), **call, arenas=drawn)
    for got, before in zip(out[4:6], drawn):
        got, before = np.asarray(got), np.asarray(before)
        rest = [s for s in range(1, 9) if s != 6]
        np.testing.assert_array_equal(got[:, rest], before[:, rest])
        assert np.abs(got[:, 6] - before[:, 6]).max() > 1e-3
    built_for_tpu(False)
    plain = _in_place(program, CFG.make_extend_fn(), **call, arenas=drawn)
    np.testing.assert_allclose(out[0][0], plain[0][0], rtol=1e-5, atol=1e-5)
    for got, expected in zip(out[4:6], plain[4:6]):
        np.testing.assert_allclose(got[:, 6], expected[:, 6], rtol=1e-5, atol=1e-6)


def test_a_kept_state_lands_in_its_slot_and_a_copy_of_it_continues_bitwise(program):
    """A chunk of 16 tokens keeps the state 8 tokens in, in slot 3; copied to
    slot 2 (what a prefix hit does) and fed the other 8 tokens over the first 8's
    K and V, it ends in the bits the whole chunk left in slot 1."""
    extend = CFG.make_extend_fn()
    tokens = np.asarray([_prompt(9, 16)], np.int32)
    logits, _, k, v, *whole, _ = extend(
        program, jnp.asarray(tokens), _ints(0), *_caches(1, 64), *_arenas(5, seed=8),
        _ints(1), _ints(8), _ints(3))
    whole = [np.asarray(a) for a in whole]            # the copy donates its arenas
    copied = llm._state_programs().copy(
        tuple(map(jnp.asarray, whole)), np.int32(3), np.int32(2))
    caches = tuple(c.at[:, :, :8].set(rows[:, :, :8]) for c, rows in zip(_caches(1, 64), (k, v)))
    rest = np.concatenate([tokens[:, 8:], np.full((1, 8), -1, np.int32)], 1)
    again, _, _, _, *after, _ = extend(
        program, jnp.asarray(rest), _ints(8), *caches, *copied, _ints(2), _ints(0), _ints(0))
    for got, want in zip(after, whole):
        np.testing.assert_array_equal(np.asarray(got[:, 2]), np.asarray(want[:, 1]))
        np.testing.assert_array_equal(np.asarray(got[:, 3]), np.asarray(want[:, 3]))
    np.testing.assert_allclose(again[0, :8], logits[0, 8:], rtol=2e-5, atol=2e-5)


def test_a_padded_token_changes_no_state_and_a_fresh_lane_starts_from_zeros(program):
    extend = CFG.make_extend_fn()
    tokens = jnp.asarray([_prompt(1, 16)], jnp.int32)
    where = (_ints(1), _ints(8), _ints(2))      # the lane's slot; keep the state 8 tokens in, in slot 2
    full = extend(program, tokens, _ints(0), *_caches(1, 64), *_arenas(4), *where)
    # 11 real tokens in the bucket of 16, from slots full of rubbish
    rubbish = tuple(jnp.full_like(s, 3.0) for s in _arenas(4))
    cut = extend(
        program, tokens.at[:, 11:].set(-1), _ints(0), *_caches(1, 64), *rubbish, *where)
    np.testing.assert_allclose(cut[0][:, :11], full[0][:, :11], rtol=1e-5, atol=1e-6)
    # the state after 8 tokens is the same either way; after the last real one it is not
    for of_cut, of_full in zip(cut[4:6], full[4:6]):
        np.testing.assert_array_equal(np.asarray(of_cut[:, 2]), np.asarray(of_full[:, 2]))
        # and the slot nobody named holds what it held
        assert (np.asarray(of_cut[:, 3]) == 3.0).all() and not np.asarray(of_full[:, 3]).any()
    assert np.abs(np.asarray(cut[4][:, 1]) - np.asarray(full[4][:, 1])).max() > 1e-4
    assert dict(zip(CFG.counters, np.asarray(cut[-1]).tolist())) == {
        "ssm_tokens": 11 * CFG.ssm_layers, "ssm_state_passes": CFG.ssm_layers}


def test_chunked_prefill_then_decode_is_the_references_full_forward(program, engine):
    """A prompt of 45 tokens over three chunks of 16, then 8 decode steps."""
    _empty_engine(engine)
    prompt = _prompt(7, 45)
    out = _served(engine, 7, 45, 8)
    want = _reference(program, prompt, out)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    np.testing.assert_allclose(out["logits"], want, rtol=2e-4, atol=2e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    assert out["prefix_cached_tokens"] == 0


def test_a_prompt_over_three_chunks_carries_its_state(program, engine):
    """The same 45 tokens fed in one call of 48 give the logits the three
    chunks gave: each chunk went on from the state the chunk before left."""
    _empty_engine(engine)
    out = _served(engine, 8, 45, 1)
    extend = CFG.make_extend_fn()
    tokens = jnp.asarray([_prompt(8, 45) + [-1] * 3], jnp.int32)
    logits, *_ = extend(
        program, tokens, _ints(0), *_caches(1, 64), *_arenas(2), _ints(1), _ints(0), _ints(0))
    np.testing.assert_allclose(out["logits"][0], logits[0, 44], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,reused", [(45, 40), (33, 32), (48, 40)], ids=["mid", "end", "whole"])
def test_a_snapshot_restored_gives_bitwise_the_uncached_logits(engine, n, reused):
    """The reusable end of a prompt lies in the middle of its last chunk (45:
    40 of 32..48), at the end of the chunk before (33: 32) or a block before
    the prompt's own end (48: 40): the state kept there is one the chunked
    recurrence made between two sub-chunks, and the repeat's logits are the
    first's, bit for bit."""
    _empty_engine(engine)
    before = engine.stats()
    first = _served(engine, 20 + n, n, 6)
    again = _served(engine, 20 + n, n, 6)
    after = engine.stats()
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, reused)
    assert again["tokens"] == first["tokens"]
    np.testing.assert_array_equal(again["logits"], first["logits"])
    assert after["state_restores"] - before["state_restores"] == 1
    assert after["state_snapshots"] == 1 == after["state_slots_in_use"]
    # a longer prompt with the same beginning goes on from the same snapshot
    longer = batching._Sequence({
        "prompt": _prompt(20 + n, n)[:reused] + _prompt(99, 20), "max_new_tokens": 2,
        "return_logits": True})
    _drive(engine, [longer])
    assert longer._result["prefix_cached_tokens"] == reused


def test_lanes_that_join_and_leave_do_not_disturb_another_lanes_state(engine):
    _empty_engine(engine)
    asks = [(30 + i, 12 + 9 * i, 3 + 2 * i) for i in range(6)]
    alone = [_served(engine, *ask) for ask in asks]
    _empty_engine(engine)
    together = [_ask(*ask) for ask in asks]
    late = [_ask(50, 20, 9), _ask(51, 37, 2)]
    # two more join while the six are under way
    _drive(engine, together, lambda step: together.extend(late) if step == 5 else None)
    assert all(s.done and s._error is None for s in late)
    for s, want in zip(together, alone):
        assert s._error is None and s._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(s._result["logits"], want["logits"], rtol=1e-5, atol=1e-6)
    stats = engine.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"]


def test_with_a_call_in_flight_a_lanes_state_follows_its_token(engine):
    """Decode calls are launched before the call before has landed and read
    their lanes' tokens on the device; the states they read are those that call
    wrote: the tokens are those of the requests served one at a time."""
    _empty_engine(engine)
    asks = [(60 + i, 10 + 5 * i, 12) for i in range(3)]
    alone = [_served(engine, *ask, return_logits=False) for ask in asks]
    _empty_engine(engine)
    before = engine.stats()
    seqs = [_ask(*ask, return_logits=False) for ask in asks]
    _drive(engine, seqs)
    after = engine.stats()
    assert after["calls_ahead"] > before["calls_ahead"]
    assert after["tokens_fed_on_device"] > before["tokens_fed_on_device"]
    assert [s._result["tokens"] for s in seqs] == [r["tokens"] for r in alone]
    # an eos_token that lands while the lane's next call is in flight: the slot
    # goes back with that call's write still to come, and its next owner starts
    # from zeros all the same
    eos = alone[0]["tokens"][3]
    ended = _ask(60, 10, 12, return_logits=False, eos_token=eos)
    follower = _ask(61, 15, 12, return_logits=False)
    _drive(engine, [ended, follower])
    assert ended._result["tokens"] == alone[0]["tokens"][:alone[0]["tokens"].index(eos) + 1]
    assert follower._result["tokens"] == alone[1]["tokens"]


def test_after_a_drain_only_snapshots_hold_slots_and_an_eviction_frees_one(engine):
    _empty_engine(engine)
    for i in range(3):
        _served(engine, 70 + i, 20 + 8 * i, 2)
    stats = engine.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"] == 3
    assert stats["kv_blocks_in_use"] == stats["prefix_cached_blocks"] == 2 + 3 + 4
    assert stats["ssm_state_passes"] > 0
    with engine.pool._lock:
        assert engine.prefix._evict_snapshot()
    stats = engine.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"] == 2
    assert stats["kv_blocks_in_use"] == stats["prefix_cached_blocks"] == 3 + 4
    # the evicted chain is gone with its snapshot: its prompt is prefilled again
    assert _served(engine, 70, 20, 2)["prefix_cached_tokens"] == 0
    assert _served(engine, 71, 28, 2)["prefix_cached_tokens"] == 24
    # a prompt of less than a block leaves nothing behind
    _empty_engine(engine)
    _served(engine, 75, 7, 2)
    assert engine.stats()["state_slots_in_use"] == 0


def test_snapshots_make_room_for_sequences_and_a_pool_without_room_sheds(program):
    """Five slots: the cache's snapshots go, oldest first, when sequences need
    theirs; a sequence that finds none while nothing is in flight is shed as it
    is for blocks; one that finds none for its snapshot is served and not cached."""
    eng = llm.LLMEngine(CFG, program, **{**ENGINE, "state_slots": 6})
    for i in range(4):
        _served(eng, 80 + i, 20, 2)
    assert eng.stats()["state_snapshots"] == 4
    seqs = [_ask(90 + i, 20, 6) for i in range(6)]
    _drive(eng, seqs)
    shed = [s for s in seqs if s._error is not None]
    assert shed and all(isinstance(s._error, BackPressureError) for s in shed)
    assert "state slot" in str(shed[0]._error)
    assert len(seqs) - len(shed) >= 3
    stats = eng.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"] <= 5
    assert stats["kv_blocks_in_use"] == stats["prefix_cached_blocks"]
    # the first four prompts' snapshots went to the sequences
    assert _served(eng, 80, 20, 2)["prefix_cached_tokens"] == 0


def test_a_lane_that_ends_with_its_next_call_in_flight_leaves_its_neighbours_alone(engine):
    """Three decode together; the first meets its ``eos_token`` while the call
    behind is in flight, which advances its slot once more: by then the slot is
    free, and the two beside it read what they would have read alone. A fourth
    that takes the freed slot starts from zeros."""
    _empty_engine(engine)
    asks = [(100 + i, 9 + 7 * i, 14) for i in range(3)]
    alone = [_served(engine, *ask) for ask in asks]
    late = _served(engine, 104, 21, 5)
    _empty_engine(engine)
    eos = alone[0]["tokens"][4]
    seqs = [_ask(*asks[0], eos_token=eos)] + [_ask(*ask) for ask in asks[1:]]
    joins = _ask(104, 21, 5)
    before = engine.stats()["calls_ahead"]
    _drive(engine, seqs, lambda step: seqs.append(joins) if step == 9 else None)
    assert engine.stats()["calls_ahead"] > before
    assert seqs[0]._result["tokens"] == alone[0]["tokens"][:alone[0]["tokens"].index(eos) + 1]
    for s, want in zip(seqs[1:], alone[1:] + [late]):
        assert s._error is None and s._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(s._result["logits"], want["logits"], rtol=1e-5, atol=1e-6)


def test_the_store_copies_a_state_for_a_prefix_hit_and_for_nothing_else(engine):
    """``state_bytes_moved`` is the restores' alone: a request of three chunks
    and six decode calls copies nothing; its repeat, one state."""
    _empty_engine(engine)
    start = engine.stats()
    _served(engine, 110, 45, 6)
    first = engine.stats()
    assert first["state_bytes_moved"] == start["state_bytes_moved"]
    assert first["ssm_state_passes"] - start["ssm_state_passes"] == CFG.ssm_layers * (3 + 5)
    assert set(first["phase_s"]) == set(llm.PHASES) | {"state_restore"}
    _served(engine, 110, 45, 6)
    again = engine.stats()
    assert again["state_bytes_moved"] - first["state_bytes_moved"] == engine.pool.state_bytes
    assert again["traced"]["state_bytes_moved"] == 0     # no profiler session recorded a step


def test_the_engine_asks_for_blocks_and_chunks_that_end_at_a_kept_state(program):
    with pytest.raises(ValueError, match="keeps a state every 8 tokens"):
        llm.LLMEngine(CFG, program, **{**ENGINE, "block_size": 4, "cache_buckets": (64,)})
    with pytest.raises(ValueError, match="keeps a state every 8 tokens"):
        llm.LLMEngine(CFG, program, **{**ENGINE, "prefill_chunk": 12})


def test_a_configuration_without_state_arrays_runs_as_before():
    """No slots, no state programs, no third phase, the operand buffer as wide
    as it was."""
    eng = llm.LLMEngine(
        gpt.gpt_nano(), num_blocks=8, block_size=16, lane_buckets=(1,), prefill_chunk=16,
        prefill_token_buckets=(16,), cache_buckets=(64,))
    assert eng.pool.states == () and eng.pool.state_slots == 0 and eng.pool.state_bytes == 0
    assert eng._operand_width == llm._operand_width(16, 4) == 4 + 4 * 16
    assert llm._operand_width(16, 4, True) == eng._operand_width + 3
    stats = eng.stats()
    assert not [k for k in stats if k.startswith("state_")]
    assert set(stats["phase_s"]) == set(llm.PHASES)
    assert eng.pool.layers == eng.cfg.num_layers
