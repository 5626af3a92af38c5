"""``extend`` told which row of each lane is read (``last``; PR 57): the head's
product for that row alone, none in a chunk where no lane reads one, and
everything else a call returns as the all-rows form returns it. Every
architecture's ``make_extend_fn`` at its CPU size, then the engine's own use of
it: a prompt of three chunks beside one of two, through two prefill lanes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (
    cohere2_moe, glm_moe_dsa, gpt, granitemoehybrid, keye_vl2, kimi_k2, longcat_flash,
    mimo_v2_flash, minicpm_sala, qwen3_next)
from ray_tpu.serve import batching, llm

LANES, TOKENS, CACHE, SLOTS = 2, 16, 32, 4

ARCHITECTURES = {
    "gpt": gpt.gpt_nano,
    "cohere2_moe": cohere2_moe.cohere2_moe_nano,
    "keye_vl2": keye_vl2.keye_vl2_nano,
    "kimi_k2": kimi_k2.kimi_k2_nano,
    "granite_hybrid": granitemoehybrid.granite_hybrid_nano,
    "granite_hybrid_moe": lambda: granitemoehybrid.granite_hybrid_nano(router_experts=8),
    "minicpm_sala": minicpm_sala.minicpm_sala_nano,
    "mimo_v2_flash": mimo_v2_flash.mimo_v2_flash_nano,
    "qwen3_next": qwen3_next.qwen3_next_nano,
    "glm_moe_dsa": glm_moe_dsa.glm_moe_dsa_nano,
    "longcat_flash": longcat_flash.longcat_flash_nano,
}


def _a_chunk(cfg):
    """What ``extend`` takes behind ``params`` for a chunk of ``TOKENS`` in two
    lanes: a fresh lane that is fed all of them, and one with 8 tokens cached that
    is fed 12 and padding; caches and states of small finite noise."""
    rng = np.random.default_rng(3)
    dtype = jnp.float32 if cfg.dtype is None else cfg.dtype

    def noise(shape, dtype):
        return jnp.asarray(0.1 * rng.standard_normal(shape), dtype)

    tokens = rng.integers(0, cfg.vocab_size, (LANES, TOKENS)).astype(np.int32)
    tokens[1, 12:] = -1
    layers = getattr(cfg, "cache_layers", cfg.num_layers)
    caches = [
        noise((layers, LANES, CACHE // llm.cache_grain(each)) + tuple(each[:2]), dtype)
        for each in cfg.cache_arrays]
    states = [
        noise((n, SLOTS) + tuple(shape), kind)
        for n, shape, kind in getattr(cfg, "state_arrays", ())]
    where = [jnp.asarray(x, jnp.int32) for x in ([1, 2], [0, 8], [0, 3])] if states else []
    return (jnp.asarray(tokens), jnp.asarray([0, 8], jnp.int32), *caches, *states, *where)


@pytest.mark.parametrize("name", list(ARCHITECTURES))
def test_the_row_last_names_is_the_all_rows_forms_and_nothing_else_moves(name):
    cfg = ARCHITECTURES[name]()
    extend, params, args = cfg.make_extend_fn(), cfg.init_params(0), _a_chunk(cfg)
    logits, hidden, *rest = extend(params, *args)
    assert logits.shape == (LANES, TOKENS, cfg.vocab_size)
    assert hidden.shape == (LANES, TOKENS, cfg.embed_dim)
    last = np.asarray([TOKENS - 1, 11], np.int32)
    row, hidden_row, *rest_read = extend(params, *args, last=jnp.asarray(last))
    assert row.shape == (LANES, cfg.vocab_size) and row.dtype == jnp.float32
    assert hidden_row.shape == (LANES, cfg.embed_dim) and hidden_row.dtype == jnp.float32
    lane = np.arange(LANES)
    np.testing.assert_allclose(row, np.asarray(logits)[lane, last], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(hidden_row, np.asarray(hidden)[lane, last], rtol=1e-5, atol=1e-6)
    # no lane reads a row: no head, and zeros of the same two shapes
    nobody = jnp.full((LANES,), -1, jnp.int32)
    unread, hidden_unread, *rest_unread = extend(params, *args, last=nobody)
    assert unread.shape == row.shape and hidden_unread.shape == hidden_row.shape
    assert np.isfinite(unread).all() and not np.asarray(unread).any()
    # new cache rows, state arenas and counters: the same bits in all three
    assert len(rest) == len(rest_read) == len(rest_unread) >= len(cfg.cache_arrays)
    for whole, read, none in zip(rest, rest_read, rest_unread):
        assert whole.dtype == read.dtype == none.dtype
        np.testing.assert_array_equal(np.asarray(read), np.asarray(whole))
        np.testing.assert_array_equal(np.asarray(none), np.asarray(whole))
    # one lane of two reads a row: the head runs, and the other's row is never read
    one, *_ = extend(params, *args, last=jnp.asarray([-1, 11], jnp.int32))
    np.testing.assert_array_equal(np.asarray(one)[1], np.asarray(row)[1])


def test_a_decode_call_reads_every_lanes_row_whatever_last_says():
    """One token a lane is the decode program: no ``cond`` in it, each lane's row."""
    cfg = gpt.gpt_nano()
    extend, params = cfg.make_extend_fn(), cfg.init_params(0)
    tokens, lengths, *caches = _a_chunk(cfg)
    args = (tokens[:, :1], lengths, *caches)
    logits, hidden, *_ = extend(params, *args)
    for last in ([0, 0], [-1, -1]):
        last = jnp.asarray(last, jnp.int32)
        row, hidden_row, *_ = extend(params, *args, last=last)
        np.testing.assert_array_equal(np.asarray(row), np.asarray(logits)[:, 0])
        np.testing.assert_array_equal(np.asarray(hidden_row), np.asarray(hidden)[:, 0])
    assert "cond" not in str(jax.make_jaxpr(lambda *a: extend(*a, last=last))(params, *args))
    assert "cond" in str(jax.make_jaxpr(
        lambda *a: extend(*a, last=last))(params, tokens, lengths, *caches))


def _by_hand(eng, prompt, new):
    """``new`` greedy tokens and their rows of logits after ``prompt``, from the
    all-rows ``extend`` alone: the prompt in the engine's chunks over a padded cache
    kept on the host, each lane's last valid row taken here."""
    cfg, cap = eng.cfg, eng.cache_buckets[-1]
    caches = [
        np.zeros((cfg.num_layers, 1, cap) + tuple(each), np.float32) for each in cfg.cache_arrays]
    ids, rows, length, fed = [], [], 0, list(prompt)
    while len(ids) < new:
        chunk, fed = fed[:eng.prefill_chunk], fed[eng.prefill_chunk:]
        tokens = np.full((1, eng.prefill_chunk if len(chunk) > 1 else 1), -1, np.int32)
        tokens[0, :len(chunk)] = chunk
        logits, _, *news = eng._extend(
            eng._params, tokens, np.asarray([length], np.int32), *caches)
        for cache, rows_new in zip(caches, news):
            cache[:, 0, length:length + len(chunk)] = np.asarray(rows_new)[:, 0, :len(chunk)]
        length += len(chunk)
        if not fed:
            rows.append(np.asarray(logits)[0, len(chunk) - 1])
            ids.append(int(np.argmax(rows[-1])))
            fed = [ids[-1]]
    return ids, np.stack(rows)


def test_a_prompt_of_three_chunks_beside_one_of_two_gives_what_all_rows_gave():
    """Two prefill lanes, chunks of 16: a prompt of 40 tokens (three chunks) and one
    of 24 (two). The second call holds a chunk that emits beside one that does not
    (``heads`` 1 of 2 lanes), the first none that does: its head does not run. Ids
    and rows of logits are the all-rows form's, driven by hand."""
    eng = llm.LLMEngine(
        gpt.gpt_nano(), num_blocks=32, block_size=16, prefill_chunk=16, prefill_lanes=2,
        lane_buckets=(1, 2), prefill_token_buckets=(16,), cache_buckets=(64,),
        prefix_caching=False)
    rng, new = np.random.default_rng(11), 4
    prompts = [rng.integers(0, eng.cfg.vocab_size, n).tolist() for n in (40, 24)]
    seqs = [
        batching._Sequence({"prompt": p, "max_new_tokens": new, "return_logits": True})
        for p in prompts]
    heads, launch = [], eng._launch

    def launched(lanes, chunks, tc, emits):
        heads.append((tc, len(lanes), sum(emits)))
        return launch(lanes, chunks, tc, emits)

    eng._launch = launched
    steps = 0
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 50
    assert [h for h in heads if h[0] > 1] == [(16, 2, 0), (16, 2, 1), (16, 1, 1)]
    calls = eng.stats()["calls"]
    assert calls["prefill"]["heads"] == 2 and calls["prefill"]["lanes_used"] == 5
    assert calls["decode"]["heads"] == calls["decode"]["lanes_used"] == 2 * (new - 1)
    for s, prompt in zip(seqs, prompts):
        assert s._error is None, s._error
        ids, rows = _by_hand(eng, prompt, new)
        assert s._result["tokens"] == ids
        assert s._result["logits"].shape == rows.shape
        np.testing.assert_allclose(s._result["logits"], rows, rtol=1e-4, atol=1e-5)
