"""``models/keye_vl2.py`` on the CPU at a tiny size: the two forms of the
selection give the same keys (ties, zeros of either sign, fewer visible keys
than ``topk``), a decode lane and a prefill chunk give the same row of logits,
padding selects nothing, the chip's attention kernel (interpreted) against the
dense form, the softmax router, and the configuration's own arithmetic. The
comparison with the plain reference is the benchmark's
(``tests/benchmark/test_bench_keye_vl2.py``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import keye_vl2, layers, moe
from ray_tpu.ops import attention

CFG = keye_vl2.keye_vl2_nano()


def _scores(case, rng, rows=6, cache=64):
    scores = rng.standard_normal((rows, cache)).astype(np.float32)
    if case == "ties":                          # a few values, so the k-th is shared
        scores = np.round(scores * 2) / 2
    elif case == "zeros":                       # most scores an exact zero of either sign
        scores = np.where(rng.random(scores.shape) < 0.7, 0.0, scores).astype(np.float32)
        scores = np.where(rng.random(scores.shape) < 0.5, -scores, scores)
    elif case == "all_equal":
        scores[:] = 1.5
    return scores


@pytest.mark.parametrize("case", ["distinct", "ties", "zeros", "all_equal"])
@pytest.mark.parametrize("k", [1, 16, 64, 100])
def test_the_mask_and_the_rows_are_the_same_keys(case, k):
    """``select_mask`` (a prefill chunk's) against ``select_rows`` (a decode
    lane's) and against a sort on the host: the ``k`` largest visible scores,
    ties to the lower position, all the visible ones where they are fewer."""
    rng = np.random.default_rng(hash((case, k)) % 2**32)
    scores = _scores(case, rng)
    seen = rng.integers(0, 65, size=scores.shape[0])
    seen[0], seen[1] = 0, 64                     # a row that sees nothing, one that sees all
    visible = np.arange(64)[None, :] < seen[:, None]
    mask = np.asarray(jax.jit(layers.select_mask, static_argnums=2)(scores, visible, k))
    rows, chosen = (np.asarray(x) for x in layers.select_rows(
        jnp.asarray(scores), jnp.asarray(visible), k))
    assert rows.shape == chosen.shape == (scores.shape[0], min(k, 64))
    for r in range(scores.shape[0]):
        order = sorted(range(seen[r]), key=lambda s: (-(scores[r, s] + 0.0), s))[:k]
        assert sorted(np.flatnonzero(mask[r])) == sorted(order), (case, k, r)
        assert sorted(rows[r][chosen[r]]) == sorted(order), (case, k, r)
        assert chosen[r].sum() == min(k, seen[r])


def _caches(lanes, cache):
    return [
        jnp.zeros((CFG.num_layers, lanes, cache) + tuple(each), jnp.float32)
        for each in CFG.cache_arrays]


@pytest.fixture(scope="module")
def program():
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key == "scale" else a * 8.0, CFG.init_params(5))


def test_a_decode_lane_and_a_prefill_chunk_give_the_same_row(program):
    """50 tokens (topk is 16) in one chunk, against 49 in a chunk and the 50th
    as a decode lane over the cache they left: the same logits, the same new
    K, V and indexer key, the same selection."""
    extend, probe = CFG.make_extend_fn(), keye_vl2.make_probe_fn(CFG)
    tokens = jnp.asarray(
        [np.random.default_rng(2).integers(0, CFG.vocab_size, size=50)], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    logits, _, k, v, i, _, selected = probe(program, tokens, zero, *_caches(1, 64))
    plain = extend(program, tokens, zero, *_caches(1, 64))
    assert len(plain) == 6 and np.array_equal(plain[0], logits)    # the probe changes nothing
    held = [jnp.pad(x[:, :, :49], ((0, 0), (0, 0), (0, 15), (0, 0), (0, 0))) for x in (k, v, i)]
    one = probe(program, tokens[:, 49:], jnp.full((1,), 49, jnp.int32), *held)
    np.testing.assert_allclose(one[0][0, 0], logits[0, 49], atol=2e-5, rtol=2e-5)
    for new, whole in zip(one[2:5], (k, v, i)):
        np.testing.assert_allclose(new[:, :, 0], whole[:, :, 49], atol=1e-5, rtol=1e-5)
    assert np.array_equal(np.asarray(one[-1])[:, 0, 0], np.asarray(selected)[:, 0, 49])
    assert np.asarray(selected)[:, 0, 49].sum(-1).tolist() == [16] * CFG.num_layers
    assert i.shape == (CFG.num_layers, 1, 50, 1, CFG.index_dim)


def test_a_short_context_is_causal_attention(program):
    """Up to ``topk`` keys every query reads everything before it: the
    selection decides nothing, and a configuration that reads more keys than
    there are gives the same logits."""
    import dataclasses

    tokens = jnp.asarray(
        [np.random.default_rng(3).integers(0, CFG.vocab_size, size=16)], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    sparse = CFG.make_extend_fn()(program, tokens, zero, *_caches(1, 64))
    dense = dataclasses.replace(CFG, topk=64).make_extend_fn()(
        program, tokens, zero, *_caches(1, 64))
    np.testing.assert_allclose(sparse[0], dense[0], atol=1e-6)
    longer = jnp.asarray(
        [np.random.default_rng(3).integers(0, CFG.vocab_size, size=48)], jnp.int32)
    sparse = CFG.make_extend_fn()(program, longer, zero, *_caches(1, 64))
    dense = dataclasses.replace(CFG, topk=64).make_extend_fn()(
        program, longer, zero, *_caches(1, 64))
    np.testing.assert_allclose(sparse[0][0, :16], dense[0][0, :16], atol=1e-5)
    assert float(jnp.abs(sparse[0][0, 40:] - dense[0][0, 40:]).max()) > 1e-3


def test_padding_changes_no_real_token_and_counts_nothing(program):
    extend = CFG.make_extend_fn()
    rng = np.random.default_rng(4)
    real = rng.integers(0, CFG.vocab_size, size=(2, 24))
    lengths = jnp.zeros((2,), jnp.int32)
    whole = extend(program, jnp.asarray(real, jnp.int32), lengths, *_caches(2, 64))
    padded = np.full((2, 32), -1)
    padded[:, :24] = real
    padded[1, 10:] = -1                                      # the second lane is shorter
    out = extend(program, jnp.asarray(padded, jnp.int32), lengths, *_caches(2, 64))
    np.testing.assert_allclose(out[0][0, :24], whole[0][0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out[0][1, :10], whole[0][1, :10], atol=2e-5, rtol=2e-5)
    named = dict(zip(CFG.counters, np.asarray(out[-1]).tolist()))
    tokens = CFG.num_layers * (24 + 10)
    assert named["moe_tokens"] == named["sparse_queries"] == tokens
    assert named["moe_assignments"] == CFG.experts_per_token * tokens
    assert named["sparse_keys_scored"] == CFG.num_layers * (24 * 25 // 2 + 10 * 11 // 2)
    assert named["sparse_keys_attended"] == CFG.num_layers * (
        sum(min(16, t) for t in range(1, 25)) + sum(range(1, 11)))
    # a slot is read if some query chose it: at least the 16 of the last query
    assert CFG.num_layers * (16 + 10) <= named["sparse_slots_read"] <= CFG.num_layers * (24 + 10)


def _dense_attend(q, k, v, mask, scale):
    """What a prefill chunk computes off the chip (``_attend``'s ``attend_block``)."""
    logit = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    weight = jax.nn.softmax(jnp.where(mask[:, None, None], logit, -1e30), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", weight.astype(v.dtype), v)


def _chunk(rng, starts, tokens, cache=64):
    """A call at the tiny model's widths (8 query heads over 2 K/V heads of 16):
    lane ``i`` holds ``tokens`` queries from position ``starts[i]``, each
    reading about half the keys before it."""
    lanes, kv, groups, dim = len(starts), CFG.kv_heads, CFG.num_heads // CFG.kv_heads, CFG.head_dim
    q = rng.standard_normal((lanes, tokens, kv, groups, dim)).astype(np.float32)
    k, v = (rng.standard_normal((lanes, cache, kv, dim)).astype(np.float32) for _ in range(2))
    positions = np.asarray(starts)[:, None] + np.arange(tokens)[None, :]
    mask = (np.arange(cache)[None, None, :] <= positions[:, :, None]) & (
        rng.random((lanes, tokens, cache)) < 0.5)
    mask[np.arange(lanes)[:, None], np.arange(tokens)[None, :], positions] = True   # itself
    return q, k, v, mask, positions


KERNEL_CASES = {
    # starts of the lanes, queries, query tile, key tile
    "grouped_heads_whole_blocks": ((5,), 32, 16, 16),
    "chunk_is_no_whole_number_of_query_blocks": ((5,), 24, 16, 16),
    "cache_is_no_whole_number_of_key_blocks": ((5,), 32, 16, 48),
    "one_lane_short_one_long": ((0, 30), 32, 16, 16),
    "one_tile_for_everything": ((3, 11), 16, 512, 2048),
}


@pytest.mark.parametrize("empty_row", [False, True], ids=["", "an_all_false_row"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_attention_kernel_computes_the_dense_form(case, empty_row):
    """``ops/attention.masked_attention``, interpreted, against the dense form
    under the same mask. Keys past each lane's live bound hold NaN: the bound
    engages and nothing past it is read. A query with an empty mask gets
    finite values and changes no other."""
    starts, tokens, block_q, block_k = KERNEL_CASES[case]
    q, k, v, mask, positions = _chunk(np.random.default_rng(len(case)), starts, tokens)
    if empty_row:
        mask[0, 3] = False
    kv_len = positions.max(1) + 1
    dirty_k, dirty_v = k.copy(), v.copy()
    step = min(block_k, k.shape[1])
    for lane, n in enumerate(kv_len):
        for dirty in (dirty_k, dirty_v):
            dirty[lane, -(-n // step) * step:] = np.nan
    if step < k.shape[1]:
        assert np.isnan(dirty_k).any()           # the short lane does leave blocks out
    out = np.asarray(attention.masked_attention(
        jnp.asarray(q), jnp.asarray(dirty_k), jnp.asarray(dirty_v), jnp.asarray(mask),
        jnp.asarray(kv_len, jnp.int32), scale=0.25, block_q=block_q, block_k=block_k,
        interpret=True))
    want = np.asarray(_dense_attend(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(mask), 0.25))
    assert out.shape == want.shape == q.shape and np.isfinite(out).all()
    real = np.ones(out.shape[:2], bool)
    real[0, 3] = not empty_row
    np.testing.assert_allclose(out[real], want[real], atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("block_k", [16, 64])
def test_a_query_gets_the_same_bits_at_any_row_of_a_chunk(block_k):
    """The same token is row 31 of one chunk and row 15 of another (a prompt
    served from the prefix cache starts its chunks elsewhere), beside other
    queries and under another live bound: its output is bitwise the same."""
    q, k, v, mask, positions = _chunk(np.random.default_rng(7), (8,), 32)

    def run(q, mask, positions):
        return np.asarray(attention.masked_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(mask),
            jnp.asarray(positions.max(1) + 1, jnp.int32), scale=0.25, block_q=16,
            block_k=block_k, interpret=True))

    first = run(q, mask, positions)
    other_q, _, _, other_mask, _ = _chunk(np.random.default_rng(8), (8 + 32,), 16)
    moved = run(
        np.concatenate([q[:, 16:], other_q], 1), np.concatenate([mask[:, 16:], other_mask], 1),
        positions + 16)
    assert np.array_equal(first[:, 16:], moved[:, :16])


SERVED_WIDTHS = {
    # K/V heads, key width, value width
    "command_a_plus_8x128": (8, 128, 128),
    "qwen3_next_2x256": (2, 256, 256),
    "mimo_4x192_over_128": (4, 192, 128),
    "one_latent_576_over_512": (1, 576, 512),
}


@pytest.mark.parametrize("groups, sunk, dtype", [
    (2, False, jnp.float32), (4, False, jnp.float32), (2, True, jnp.float32),
    (4, True, jnp.float32), (4, True, jnp.bfloat16),
], ids=["one-tile", "two-tiles", "one-tile-sinks", "two-tiles-sinks", "bf16-two-tiles-sinks"])
@pytest.mark.parametrize("case", list(SERVED_WIDTHS))
def test_the_kernel_computes_the_dense_form_at_the_served_widths(
        case, groups, sunk, dtype, monkeypatch):
    """``masked_attention``, interpreted, against ``layers.plain_attend`` at the K
    and V widths the serve configurations hold: a lane that stops short of the cache
    (NaN past its live blocks), a cache that is no whole number of key tiles, a K/V
    head's query heads in one tile of the grid and in two, with and without sinks."""
    kv, d, dv = SERVED_WIDTHS[case]
    lanes, tokens, cache, block_q, block_k = 2, 16, 40, 8, 16
    if groups == 4:     # an accumulator with room for two heads' tile of queries
        monkeypatch.setattr(attention, "MASKED_ACC_BYTES", 2 * block_q * dv * 4)
    assert attention._heads_a_tile(groups, block_q, dv) == 2
    rng = np.random.default_rng(kv * d + groups)
    q = jnp.asarray(rng.standard_normal((lanes, tokens, kv, groups, d)), dtype)
    k = jnp.asarray(rng.standard_normal((lanes, cache, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((lanes, cache, kv, dv)), dtype)
    kv_len = np.asarray([cache, 21])
    mask = (rng.random((lanes, tokens, cache)) < 0.5) & (
        np.arange(cache)[None, None] < kv_len[:, None, None])
    mask[:, :, 0] = True
    sinks = jnp.asarray(2.0 * rng.standard_normal((kv, groups)), jnp.float32) if sunk else None
    dirty_k, dirty_v = (x.at[1, -(-21 // block_k) * block_k:].set(np.nan) for x in (k, v))
    out = np.asarray(attention.masked_attention(
        q, dirty_k, dirty_v, jnp.asarray(mask), jnp.asarray(kv_len, jnp.int32), scale=0.125,
        sinks=sinks, block_q=block_q, block_k=block_k, interpret=True).astype(jnp.float32))
    if sunk:
        want = _sunk_dense_attend(q, k, v, mask, 0.125, sinks)
    else:
        want = layers.plain_attend(q, k, v, jnp.asarray(mask), 0.125)
    assert out.shape == (lanes, tokens, kv, groups, dv)
    close = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, np.asarray(want, np.float32), atol=close, rtol=close)


def _sunk_dense_attend(q, k, v, mask, scale, sinks):
    """``layers.plain_attend`` with a learned logit a query head in the denominator."""
    logit = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    logit = jnp.where(jnp.asarray(mask)[:, None, None], logit, -1e30)
    sink = jnp.broadcast_to(sinks[None, :, :, None, None], logit.shape[:-1] + (1,))
    weight = jax.nn.softmax(jnp.concatenate([logit, sink], -1), -1)[..., :k.shape[1]]
    return jnp.einsum("bhgqk,bkhd->bqhgd", weight, v)


def test_laid_out_by_head_asks_for_heads_outermost_and_changes_no_value():
    """``ops/attention.laid_out_by_head`` (Command A+'s chunk, PR 59) is a constraint
    on where a projection's result lies, [b, heads, t, d] in memory, and nothing else."""
    x = jnp.arange(2 * 3 * 4 * 8, dtype=jnp.float32).reshape(2, 3, 4, 8)
    told = jax.jit(lambda x: attention.laid_out_by_head(2 * x) + 1)
    assert np.array_equal(np.asarray(told(x)), np.asarray(2 * x + 1))
    constraint, = re.findall(r"@LayoutConstraint.*", told.lower(x).as_text())
    assert "result_layouts = [dense<[3, 1, 2, 0]>" in constraint      # minor to major


def test_the_softmax_router_takes_the_largest_probabilities_and_renormalises():
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    h, router = jax.random.normal(keys[0], (10, 32)), jax.random.normal(keys[1], (32, 16))
    weights, experts = moe.softmax_top_k(h, router, 4)
    p = np.asarray(jax.nn.softmax(np.asarray(h) @ np.asarray(router), -1))
    want = np.argsort(-p, axis=-1)[:, :4]
    assert np.array_equal(np.asarray(experts), want) and experts.dtype == jnp.int32
    top = np.take_along_axis(p, want, -1)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True), rtol=1e-5)
    # the sigmoid router picks the same experts (both rise with the logit), other weights
    other, same = moe.sigmoid_top_k(h, router, 4)
    assert np.array_equal(np.asarray(same), want)
    assert float(jnp.abs(other - weights).max()) > 1e-3


def test_the_configuration_counts_its_parameters_and_states_what_a_token_holds():
    params = CFG.init_params(0)
    assert CFG.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert CFG.cache_arrays == ((2, 16), (2, 16), (1, 8))
    assert CFG.counters == moe.COUNTERS + layers.SPARSE_COUNTERS
    assert CFG.count_gathered(4, 128) == {"sparse_slots_gathered": 3 * 4 * 128}
    full = keye_vl2.KeyeVL2Config(num_layers=6)
    assert full.num_params() == pytest.approx(4.375e9, rel=1e-3)
    assert full.cache_arrays == ((4, 128), (4, 128), (1, 64))
    with pytest.raises(ValueError, match="do not divide"):
        keye_vl2.keye_vl2_nano(kv_heads=3)
    # seeded: the same seed gives the same weights, another seed others
    again, other = CFG.init_params(0), CFG.init_params(1)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not np.array_equal(params["head"]["kernel"], other["head"]["kernel"])
