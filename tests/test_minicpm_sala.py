"""``models/minicpm_sala.py`` on the CPU at a tiny size, float32, seeded weights: a
prompt in chunks and decode through ``LLMEngine``, the pool and the state store
against the plain reference's full forward pass (logits); the chunked, the one-step
and the reference's quadratic lightning attention against each other, the states
between sub-chunks the one-step form's; every query's blocks against the
reference's, a K/V head at a time, in the prefill and in the decode form, and a layer
under ``dense_len`` dense attention bit for bit; the compressed keys in the pool at
their own grain (paged back with the page of their last key, gathered, cloned); a
prefix hit that restores rows, compressed keys and the state snapshot and gives
bitwise logits; two lanes, one of them cancelled under way; each omission the
reference names; and the configuration's own arithmetic."""

import dataclasses
import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import yardstick
from benchmark.reference import minicpm_sala_reference as ref
from ray_tpu.models import minicpm_sala as sala
from ray_tpu.serve import batching, llm

CFG = sala.minicpm_sala_nano()
#: blocks of 8 (one sub-chunk, one selection block, four compressed keys), chunks of
#: two blocks; the 32 bucket is under ``dense_len`` and selects nothing
ENGINE = dict(
    num_blocks=64, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(32, 64, 128), state_slots=14)
KEYS = dict(
    num_hidden_layers=CFG.num_layers, mixer_period=",".join(CFG.mixer_types[:CFG.period]),
    published_num_hidden_layers=CFG.depth_layers, num_attention_heads=CFG.num_heads,
    num_key_value_heads=CFG.kv_heads, hidden_size=CFG.embed_dim, lightning_nh=CFG.linear_heads,
    rms_norm_eps=CFG.norm_eps, rope_theta=CFG.rope_base, scale_emb=CFG.scale_emb,
    scale_depth=CFG.scale_depth, dim_model_base=CFG.dim_model_base,
    sparse_kernel_size=CFG.kernel_size, sparse_kernel_stride=CFG.kernel_stride,
    sparse_block_size=CFG.select_block, sparse_init_blocks=CFG.init_blocks,
    sparse_window_size=CFG.window_size, sparse_topk=CFG.topk, sparse_dense_len=CFG.dense_len)
#: what the served logits may differ from the reference's by, as a share of their
#: standard deviation (``yardstick.logits_error``), in float32 on both sides
LIMIT = 2e-5


def _gate_probe():
    spec = importlib.util.spec_from_file_location(
        "gate_probe", os.path.join(os.path.dirname(__file__), "..", "scripts", "gate_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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


def _ints(*values):
    return jnp.asarray(values, jnp.int32)


def _caches(lanes, cap, cfg=CFG):
    return tuple(
        jnp.zeros((cfg.cache_layers, lanes, cap // llm.cache_grain(each)) + each[:2], jnp.float32)
        for each in cfg.cache_arrays)


def _arena(slots, seed=None):
    (layers, shape, dtype), = CFG.state_arrays
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.normal(size=(layers, slots) + shape) if seed is not None
        else np.zeros((layers, slots) + shape), dtype)


def test_the_configuration_counts_what_the_published_model_has():
    full = sala.MiniCPMSALAConfig()
    assert full.num_params() == 9_477_111_552          # the "9B", its 24 x 32 decay slopes with it
    assert full.sparse_layers == 8 and full.linear_layers == 24 and full.period == 32
    assert [i for i, m in enumerate(full.mixer_types) if m == sala.SPARSE] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert full.cache_layers == 8
    assert full.cache_arrays == ((1, 256), (1, 256), (1, 256, 16))
    (layers, state, dtype), = full.state_arrays
    assert (layers, state) == (24, (32, 128, 128)) and dtype == jnp.float32
    assert abs(full.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    # the served cut: four periods of a sparse layer and three linear ones
    cut = sala.MiniCPMSALAConfig(
        num_layers=16, mixer_types=((sala.SPARSE,) + (sala.LINEAR,) * 3) * 4)
    assert cut.num_params() == 5_039_400_832 and (cut.period, cut.periods) == (4, 4)
    assert cut.residual_scale == full.residual_scale    # the published 32, whatever runs
    # what a sequence and a cached token weigh
    assert 12 * 32 * 128 * 128 * 4 == 25_165_824 and 4 * (2 * 256 * 2 + 256 * 2 // 16) == 4224
    slopes = np.asarray(sala.decay_slopes(cut))
    assert slopes.shape == (12, 32)
    np.testing.assert_allclose(np.exp(-slopes[0, [0, -1]]), [0.4313, 0.9961], atol=1e-4)
    params = CFG.init_params(0)
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    np.testing.assert_array_equal(params["lightning_slopes"], sala.decay_slopes(CFG))
    with pytest.raises(ValueError, match="mixer_types names 8 layers"):
        sala.minicpm_sala_nano(num_layers=9)
    with pytest.raises(ValueError, match="whole in the next"):
        sala.minicpm_sala_nano(select_block=7)


# -- (b) the three forms of the lightning recurrence -----------------------------


@pytest.mark.parametrize("lanes", [1, 3])
def test_the_chunked_the_one_step_and_the_quadratic_lightning_agree(lanes):
    rng = np.random.default_rng(lanes)
    t, heads, d = 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(lanes, t, heads, d)), jnp.float32) for _ in range(3))
    slopes = jnp.asarray(2.0 ** (-8.0 * np.arange(1, heads + 1) / heads), jnp.float32)
    valid = np.ones((lanes, t), bool)
    valid[0, 27:] = False               # padded tokens at a lane's end
    state = jnp.asarray(rng.normal(size=(lanes, heads, d, d)), jnp.float32)
    o, last, between = sala.linear_chunked(
        state, q, k, v, slopes, jnp.asarray(valid), 8, jnp.float32)
    s, rows, at_16 = state, [], None
    for i in range(t):
        row, s = sala.linear_step(s, q[:, i], k[:, i], v[:, i], slopes, jnp.asarray(valid[:, i]))
        rows.append(row)
        at_16 = s if i == 15 else at_16
    np.testing.assert_allclose(o, jnp.stack(rows, 1), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last, s, rtol=1e-4, atol=1e-5)
    assert between.shape == (4,) + state.shape
    # the states between sub-chunks are the one-step form's
    np.testing.assert_allclose(between[1], at_16, rtol=1e-4, atol=1e-5)
    # a padded token neither decays nor feeds the state
    np.testing.assert_array_equal(np.asarray(between[3][0]), np.asarray(last[0]))
    # from zeros, the quadratic form the reference is written in
    o, _, _ = sala.linear_chunked(
        jnp.zeros_like(state), q, k, v, slopes, jnp.ones((lanes, t), bool), 8, jnp.float32)
    apart = np.arange(t)[:, None] - np.arange(t)[None, :]
    decay = np.where(
        apart >= 0, np.exp(-np.asarray(slopes)[:, None, None] * np.maximum(apart, 0)), 0.0)
    want = np.einsum("bqhd,bshd,hqs,bshv->bqhv", q, k, decay, v)
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-4)


def test_a_kept_state_lands_in_its_slot_and_a_copy_of_it_continues_bitwise(program):
    """A chunk of 16 tokens keeps the state 8 tokens in, in slot 3; copied to slot 2
    (what a prefix hit does) and fed the other 8 tokens over the first 8's K, V and
    compressed keys, it ends in the bits the whole chunk left in slot 1; a slot nobody
    names holds what it held."""
    extend = CFG.make_extend_fn()
    tokens = np.asarray([_prompt(9, 16)], np.int32)
    logits, _, k, v, c, whole, counters = extend(
        program, jnp.asarray(tokens), _ints(0), *_caches(1, 64), _arena(5, seed=8),
        _ints(1), _ints(8), _ints(3))
    whole = np.asarray(whole)           # the copy donates its arenas
    (copied,) = llm._state_programs().copy((jnp.asarray(whole),), np.int32(3), np.int32(2))
    kc, vc, cc = _caches(1, 64)
    caches = (
        kc.at[:, :, :8].set(k[:, :, :8]), vc.at[:, :, :8].set(v[:, :, :8]),
        # the compressed keys whose last key lies in the first 8 tokens: rows 1 .. 3
        cc.at[:, :, 1:4].set(c[:, :, 1:4]))
    rest = np.concatenate([tokens[:, 8:], np.full((1, 8), -1, np.int32)], 1)
    again, _, _, _, c_rest, after, _ = extend(
        program, jnp.asarray(rest), _ints(8), *caches, copied, _ints(2), _ints(0), _ints(0))
    np.testing.assert_array_equal(np.asarray(after[:, 2]), whole[:, 1])
    np.testing.assert_array_equal(np.asarray(after[:, 3]), whole[:, 3])
    np.testing.assert_array_equal(np.asarray(after[:, 4]), np.asarray(_arena(5, seed=8))[:, 4])
    np.testing.assert_allclose(again[0, :8], logits[0, 8:], rtol=2e-5, atol=2e-5)
    # the second call's compressed keys are the first's: the key that ends at token 9
    # is the mean of tokens 6 .. 9, two of them read from the cache
    np.testing.assert_allclose(c_rest[:, :, :4], c[:, :, 4:], rtol=1e-6, atol=1e-6)
    assert not np.asarray(c[:, :, 0]).any()            # no key ends at token 1
    assert dict(zip(CFG.counters, np.asarray(counters).tolist()))["linear_tokens"] == (
        16 * CFG.linear_layers)


def test_a_padded_token_changes_no_state_and_a_fresh_lane_starts_from_zeros(program):
    extend = CFG.make_extend_fn()
    tokens = jnp.asarray([_prompt(1, 16)], jnp.int32)
    where = (_ints(1), _ints(8), _ints(2))
    full = extend(program, tokens, _ints(0), *_caches(1, 64), _arena(4), *where)
    rubbish = jnp.full_like(_arena(4), 3.0)
    cut = extend(program, tokens.at[:, 11:].set(-1), _ints(0), *_caches(1, 64), rubbish, *where)
    np.testing.assert_allclose(cut[0][:, :11], full[0][:, :11], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(cut[5][:, 2]), np.asarray(full[5][:, 2]))
    assert (np.asarray(cut[5][:, 3]) == 3.0).all() and not np.asarray(full[5][:, 3]).any()
    assert np.abs(np.asarray(cut[5][:, 1]) - np.asarray(full[5][:, 1])).max() > 1e-4
    # 11 real tokens bring the compressed keys that end at tokens 3, 5, 7, 9
    assert np.abs(np.asarray(cut[4])[:, 0, 1:5]).min(-1).all() and not np.asarray(cut[4])[:, 0, 5:].any()
    counted = dict(zip(CFG.counters, np.asarray(cut[-1]).tolist()))
    assert counted["linear_tokens"] == 11 * CFG.linear_layers
    assert counted["linear_state_passes"] == CFG.linear_layers
    assert counted["sparse_queries"] == 0 and counted["sparse_slots_read"] == 11 * CFG.sparse_layers


# -- (c) the selection -------------------------------------------------------------


def test_block_scores_pool_the_compressed_keys_whose_tokens_meet_a_block():
    """Compressed key ``j`` covers tokens ``2 j .. 2 j + 3`` and lies in row ``j + 1``:
    block ``b`` (tokens ``8 b .. 8 b + 7``) meets keys ``4 b - 1 .. 4 b + 3``, rows ``4 b ..
    4 b + 4``."""
    weights = jnp.asarray(np.random.default_rng(0).uniform(size=(3, 16)), jnp.float32)
    got = np.asarray(sala.block_scores(weights, CFG))
    assert got.shape == (3, 4)
    for b in range(4):
        np.testing.assert_array_equal(got[:, b], np.asarray(weights)[:, 4 * b:4 * b + 5].max(-1))
    full = sala.MiniCPMSALAConfig()
    wide = jnp.asarray(np.random.default_rng(1).uniform(size=(2, 64)), jnp.float32)
    got = np.asarray(sala.block_scores(wide, full))           # max_pool1d(5, 4, 1) over the keys
    for b in range(16):
        np.testing.assert_array_equal(got[:, b], np.asarray(wide)[:, 4 * b:4 * b + 5].max(-1))


def test_forced_and_chosen_blocks_are_the_issues_rule():
    t = jnp.asarray([40, 47, 63, 100])
    forced, reachable = (np.asarray(x) for x in sala.forced_blocks(t, 16, CFG))
    for row, at in enumerate([40, 47, 63, 100]):
        want = [b for b in range(16) if 8 * b <= at and (b == 0 or 8 * b + 7 >= at - 15)]
        assert np.flatnonzero(forced[row]).tolist() == want
        assert np.flatnonzero(reachable[row]).tolist() == list(range(at // 8 + 1))
    scores = jnp.asarray([[0.5, 0.9, 0.2, 0.9, 0.1, 0.9, 0.3, 0.0] + [0.0] * 8] * 2, jnp.float32)
    ids, chosen = (np.asarray(x) for x in sala.choose_blocks(scores, jnp.asarray([63, 20]), CFG))
    # at 63 the window holds blocks 6 and 7 and block 0 is forced: of 1 .. 5 the two
    # best, a tie to the lower block; at 20 blocks 0 .. 2 are all forced: none to choose
    assert ids[0].tolist() == [1, 3] and chosen[0].all() and not chosen[1].any()


def test_every_querys_blocks_are_the_references_in_both_forms(program):
    """100 tokens: 96 in chunks of 16 (masks), 4 a token at a time (gathered rows)."""
    fed = _prompt(11, 100)
    ours = _gate_probe().blocks_of(sala.make_probe_fn(CFG), CFG, program, fed, 16, 128)
    theirs = ref.program_blocks(program, fed, KEYS)
    assert theirs.shape == (CFG.sparse_layers, 100, CFG.kv_heads, 13)
    np.testing.assert_array_equal(ours[..., :13], theirs)
    assert not ours[..., 13:].any()
    past = theirs[:, CFG.dense_len:]
    # block 0, the window's two or three and the two best; the two K/V heads differ
    assert set(past.sum(-1).ravel().tolist()) <= {5, 6}
    assert (past[:, :, 0] != past[:, :, 1]).any()


def test_the_probe_changes_nothing_and_a_decode_lane_gives_a_chunks_row(program):
    extend, probe = CFG.make_extend_fn(), sala.make_probe_fn(CFG)
    tokens = jnp.asarray([_prompt(2, 64)], jnp.int32)
    where = (_ints(1), _ints(0), _ints(0))
    chunk = probe(program, tokens, _ints(0), *_caches(1, 128), _arena(2), *where)
    plain = extend(program, tokens, _ints(0), *_caches(1, 128), _arena(2), *where)
    assert len(plain) == 7 and np.array_equal(plain[0], chunk[0])
    k, v, c, arena = chunk[2:6]
    before = extend(
        program, tokens[:, :48], _ints(0), *_caches(1, 128), _arena(2), *where)
    kc, vc, cc = _caches(1, 128)
    held = (
        kc.at[:, :, :48].set(k[:, :, :48]), vc.at[:, :, :48].set(v[:, :, :48]),
        cc.at[:, :, :24].set(c[:, :, :24]))
    one = probe(program, tokens[:, 48:49], _ints(48), *held, before[5], *where)
    np.testing.assert_allclose(one[0][0, 0], chunk[0][0, 48], atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(one[-1])[:, 0, :, 0], np.asarray(chunk[-1])[:, 0, :, 48])
    counted = dict(zip(CFG.counters, np.asarray(one[-2]).tolist()))
    assert counted["sparse_queries"] == CFG.sparse_layers
    assert counted["sparse_keys_causal"] == CFG.sparse_layers * CFG.kv_heads * 49
    # 23 compressed keys end at or before token 48
    assert counted["sparse_keys_scored"] == CFG.sparse_layers * CFG.kv_heads * 23
    assert counted["sparse_keys_attended"] == int(np.asarray(one[-1]).sum())


def test_under_dense_len_a_layer_is_dense_attention_bit_for_bit(program):
    """30 tokens in the 64 bucket, where a query could select, against a configuration
    that never selects at that shape: the same bits, in a chunk and in a decode lane."""
    never = dataclasses.replace(CFG, dense_len=64)
    tokens = jnp.asarray([_prompt(3, 30) + [-1, -1]], jnp.int32)
    where = (_ints(1), _ints(0), _ints(0))
    outs = [
        cfg.make_extend_fn()(program, tokens, _ints(0), *_caches(1, 64), _arena(2), *where)
        for cfg in (CFG, never)]
    np.testing.assert_array_equal(np.asarray(outs[0][0])[:, :30], np.asarray(outs[1][0])[:, :30])
    held = tuple(
        cache.at[:, :, :new.shape[2]].set(new)
        for cache, new in zip(_caches(1, 64), outs[0][2:5]))
    steps = [
        cfg.make_extend_fn()(
            program, jnp.asarray([[7]], jnp.int32), _ints(30), *held, outs[0][5], *where)
        for cfg in (CFG, never)]
    np.testing.assert_array_equal(np.asarray(steps[0][0]), np.asarray(steps[1][0]))
    # and past it the two differ
    longer = jnp.asarray([_prompt(3, 64)], jnp.int32)
    a, b = (
        cfg.make_extend_fn()(program, longer, _ints(0), *_caches(1, 64), _arena(2), *where)[0]
        for cfg in (CFG, never))
    np.testing.assert_array_equal(np.asarray(a)[:, :32], np.asarray(b)[:, :32])
    assert float(jnp.abs(a[:, 48:] - b[:, 48:]).max()) > 1e-4


# -- the compressed keys in the pool -------------------------------------------------


def test_the_pool_keeps_the_compressed_keys_at_their_own_grain(program):
    pool = llm.KVBlockPool(CFG, num_blocks=6, block_size=8, state_slots=3)
    assert pool.grains == (1, 1, 2)
    assert [a.shape for a in pool.arenas] == [
        (2, 6, 8, 1, 32), (2, 6, 8, 1, 32), (2, 6, 4, 1, 32)]
    # K and V a token, a compressed key for every two
    assert pool.cache_bytes(16) == 2 * 16 * (32 + 32 + 16) * 4
    with pytest.raises(ValueError, match="do not hold whole rows"):
        llm.KVBlockPool(CFG, num_blocks=6, block_size=7, state_slots=3)
    # a call of one lane: 5 tokens from position 6 (the cache holds 6), in blocks 4, 2
    width = llm._operand_width(8, 4, True)
    operands = np.zeros((1, width), np.int32)
    tokens, rows, slots, table = llm._sections(operands)
    operands[0, llm._LENGTH], operands[0, llm._COUNT] = 6, 5
    table[0, :2] = [4, 2]
    rows[0, :5] = np.arange(5)
    slots[0, :5] = [4 * 8 + 6, 4 * 8 + 7, 2 * 8 + 0, 2 * 8 + 1, 2 * 8 + 2]
    rng = np.random.default_rng(0)
    news = tuple(
        jnp.asarray(rng.normal(size=(2, 1, n, 1, 32)), jnp.float32) for n in (8, 8, 4))
    pool.page_back(
        news, jnp.asarray(operands), jnp.zeros((1, CFG.vocab_size), jnp.float32), (), 1)
    k4, v4, c4 = pool.read_block(4)
    k2, _, c2 = pool.read_block(2)
    np.testing.assert_array_equal(k4[:, 6:], np.asarray(news[0])[:, 0, :2])
    np.testing.assert_array_equal(k2[:, :3], np.asarray(news[0])[:, 0, 2:5])
    assert not k4[:, :6].any() and not k2[:, 3:].any()
    # tokens 7 and 9 end a group of two: the call's first compressed row goes to the
    # last row of block 4 (the page of token 7), its second to the first of block 2
    np.testing.assert_array_equal(c4[:, 3], np.asarray(news[2])[:, 0, 0])
    np.testing.assert_array_equal(c2[:, 0], np.asarray(news[2])[:, 0, 1])
    assert not c4[:, :3].any() and not c2[:, 1:].any()
    # gathered, they lie where their tokens do; cloned, they go with their page
    gathered = pool.gather(jnp.asarray(operands), 2)
    assert [g.shape for g in gathered] == [(2, 1, 16, 1, 32), (2, 1, 16, 1, 32), (2, 1, 8, 1, 32)]
    np.testing.assert_array_equal(np.asarray(gathered[2])[:, 0, 3], np.asarray(news[2])[:, 0, 0])
    np.testing.assert_array_equal(np.asarray(gathered[2])[:, 0, 4], np.asarray(news[2])[:, 0, 1])
    pool.clone_block(4, 1)
    for cloned, source in zip(pool.read_block(1), (k4, v4, c4)):
        np.testing.assert_array_equal(cloned, source)


# -- (a), (d), (e) through the engine ---------------------------------------------------


def _reference(program, prompt, out, wrong=None):
    fed = prompt + out["tokens"][:-1]
    return np.asarray(ref.program_logits(program, fed, KEYS, len(out["tokens"]), wrong))


def test_chunked_prefill_then_decode_is_the_references_full_forward(program, engine):
    """A prompt of 77 tokens over five chunks of 16 (the third crosses ``dense_len``),
    then 8 decode steps that gather their blocks' rows."""
    _empty_engine(engine)
    before = engine.stats()
    out = _served(engine, 7, 77, 8)
    want = _reference(program, _prompt(7, 77), out)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, rtol=2e-4, atol=2e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    after = engine.stats()
    counted = {k: after[k] - before[k] for k in CFG.counters + ("sparse_slots_gathered",)}
    # queries 32 .. 83 select, in both sparse layers
    assert counted["sparse_queries"] == CFG.sparse_layers * (77 + 7 - 32)
    assert counted["linear_tokens"] == CFG.linear_layers * (77 + 7)
    assert counted["linear_state_passes"] == CFG.linear_layers * (5 + 7)
    assert 0 < counted["sparse_keys_attended"] < counted["sparse_keys_causal"]
    assert counted["sparse_slots_read"] < counted["sparse_slots_gathered"]
    assert after["state_bytes_moved"] == before["state_bytes_moved"]


@pytest.mark.parametrize("n,reused", [(77, 72), (65, 64), (80, 72)], ids=["mid", "end", "whole"])
def test_a_prefix_hit_restores_rows_compressed_keys_and_state_bitwise(engine, n, reused):
    """The reusable end of a prompt lies in the middle of its last chunk, at the end of
    the chunk before, or a block before the prompt's own end. Every compressed key's
    four tokens lie over two pages at a block's edge (the key that ends at token 8 b +
    1 begins in the block before), and the first one the second run makes itself is
    such a key: it reads two rows the prefix cache restored. The repeat's logits are the
    first's, bit for bit, and so is a longer prompt's beginning."""
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
    """Two requests decode side by side (the two-lane bucket, contexts past and under
    ``dense_len``: the general form of the decode call); a third joins and is cancelled
    after a few tokens: its blocks and its slot go back, and the others' tokens and
    logits are those they give alone."""
    _empty_engine(engine)
    asks = [(40, 70, 10), (41, 20, 14)]
    alone = [_served(engine, *ask) for ask in asks]
    _empty_engine(engine)
    cancel = threading.Event()
    together = [_ask(*ask) for ask in asks]
    dropped = _ask(42, 45, 30, **{llm._CANCEL_KEY: cancel})
    seqs = together + [dropped]

    def each_step(step):
        if step == 8:
            cancel.set()

    _drive(engine, seqs, each_step)
    assert dropped._error is not None and "Cancelled" in type(dropped._error).__name__
    for s, want in zip(together, alone):
        assert s._error is None and s._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(s._result["logits"], want["logits"], rtol=2e-5, atol=2e-5)
    stats = engine.stats()
    assert stats["state_slots_in_use"] == stats["state_snapshots"]
    assert stats["kv_blocks_in_use"] == stats["prefix_cached_blocks"]
    # asked again, the dropped request is served from zeros
    assert _served(engine, 42, 45, 4)["tokens"] == _served(engine, 42, 45, 4)["tokens"]


def test_lanes_past_dense_len_decode_together_as_they_do_alone(engine):
    _empty_engine(engine)
    asks = [(60 + i, 40 + 9 * i, 9) for i in range(3)]
    alone = [_served(engine, *ask, return_logits=False) for ask in asks]
    _empty_engine(engine)
    before = engine.stats()
    seqs = [_ask(*ask, return_logits=False) for ask in asks]
    _drive(engine, seqs)
    after = engine.stats()
    assert after["calls_ahead"] > before["calls_ahead"]
    assert [s._result["tokens"] for s in seqs] == [r["tokens"] for r in alone]


def test_the_engine_asks_for_blocks_and_chunks_that_end_at_a_kept_state(program):
    with pytest.raises(ValueError, match="keeps a state every 8 tokens"):
        llm.LLMEngine(CFG, program, **{**ENGINE, "block_size": 4, "cache_buckets": (64,)})


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
