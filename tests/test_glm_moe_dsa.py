"""``models/glm_moe_dsa.py`` on the CPU at a tiny size: what a token leaves behind (a
latent row and an indexer key, nothing a head), the indexer reads the query latent
and rotates its first features only, the decode form and the chunk form select the
same positions and those of a sort on the host (padding selects nothing), the
absorbed form over gathered rows, the absorbed form under a mask and the chip's
expanded-form kernel (interpreted) under the same mask give the same row from the
same cached bits, a query under ``topk`` reads all it sees, the counters of a
hand-worked call, and the configuration's own arithmetic. The comparison with the
plain reference is the benchmark's (``tests/benchmark/test_bench_glm_moe_dsa.py``)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import glm_moe_dsa, layers, moe
from ray_tpu.ops import attention, backend

CFG = glm_moe_dsa.glm_moe_dsa_nano()
RANK, ROPE, ROW = CFG.kv_rank, CFG.rope_dim, CFG.row_dim


@pytest.fixture(scope="module")
def program():
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, CFG.init_params(5))


@pytest.fixture(scope="module")
def probe():
    return glm_moe_dsa.make_probe_fn(CFG)


def _caches(lanes, cache):
    return [
        jnp.zeros((CFG.num_layers, lanes, cache) + tuple(each), jnp.float32)
        for each in CFG.cache_arrays]


def _named(counters):
    return dict(zip(CFG.counters, np.asarray(counters).tolist()))


def _tokens(n, seed=0, lanes=1):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, CFG.vocab_size, size=(lanes, n)), jnp.int32)


ZERO = jnp.zeros((1,), jnp.int32)


def test_a_token_leaves_a_latent_row_and_an_indexer_key_and_nothing_a_head(program):
    extend = CFG.make_extend_fn()
    tokens = _tokens(24)
    logits, hidden, rows, keys, counters = extend(program, tokens, ZERO, *_caches(1, 64))
    assert (RANK, ROPE, ROW) == (32, 8, 128)
    assert CFG.cache_arrays == ((1, ROW), (1, CFG.index_dim)) == ((1, 128), (1, 16))
    assert rows.shape == (CFG.num_layers, 1, 24, 1, ROW)
    assert keys.shape == (CFG.num_layers, 1, 24, 1, CFG.index_dim)
    assert not np.asarray(rows)[..., RANK + ROPE:].any()        # zeros up to whole lane tiles
    assert logits.shape == (1, 24, CFG.vocab_size) and hidden.shape == (1, 24, CFG.embed_dim)
    latent = np.asarray(rows)[..., :RANK]
    np.testing.assert_allclose((latent ** 2).mean(-1), 1.0, rtol=1e-4)      # normed, scale 1
    # the same tokens fed at another position: the same latent, another rotary key of the
    # same length; of the indexer's key the first ``index_rope_dim`` features turn (in
    # pairs i, i + 4: the same lengths) and the others stay
    _, _, moved, moved_keys, _ = extend(
        program, tokens, jnp.full((1,), 7, jnp.int32), *_caches(1, 64))
    first = lambda x: np.asarray(x)[0, 0]                       # layer 0's, whose input is the same
    np.testing.assert_allclose(first(moved)[..., :RANK], first(rows)[..., :RANK], atol=1e-5)
    assert np.abs(first(moved)[..., RANK:RANK + ROPE] - first(rows)[..., RANK:RANK + ROPE]).max() > 1e-2
    turn = CFG.index_rope_dim
    np.testing.assert_allclose(first(moved_keys)[..., turn:], first(keys)[..., turn:], atol=1e-5)
    assert np.abs(first(moved_keys)[..., :turn] - first(keys)[..., :turn]).max() > 1e-2
    half = turn // 2
    length = lambda k: k[..., :half] ** 2 + k[..., half:turn] ** 2
    np.testing.assert_allclose(length(first(moved_keys)), length(first(keys)), rtol=1e-4, atol=1e-5)


def test_the_indexers_key_is_layer_normed_with_its_drawn_bias(program):
    """Layer 0's key of a token at position 0 (no rotation there) is ``LayerNorm(W_Ik n) +
    bias`` of the embedding's normed row, with the bias the seed drew: not zeros."""
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), program["first"])
    tokens = _tokens(1, seed=9)
    _, _, _, keys, _ = CFG.make_extend_fn()(program, tokens, ZERO, *_caches(1, 64))
    x = np.asarray(program["wte"]["embedding"], np.float64)[np.asarray(tokens[0])]
    n = x / np.sqrt((x * x).mean(-1, keepdims=True) + CFG.norm_eps) * p["ln_1"]["scale"]
    raw = n @ p["index"]["k"]["kernel"]
    normed = (raw - raw.mean(-1, keepdims=True)) / np.sqrt(raw.var(-1, keepdims=True) + CFG.index_norm_eps)
    want = normed * p["index"]["k_norm"]["scale"] + p["index"]["k_norm"]["bias"]
    np.testing.assert_allclose(np.asarray(keys)[0, 0, :, 0], want, atol=1e-5, rtol=1e-4)
    assert np.abs(p["index"]["k_norm"]["bias"]).mean() > 0.1
    assert p["index"]["q"]["kernel"].shape == (CFG.q_rank, CFG.index_heads, CFG.index_dim)


def _index_scores_by_hand(program, tokens):
    """Layer 0's ``I(t, s)`` from the weights in float64, in the program's own (half-split)
    pairing: its queries from the normed query latent, its key layer-normed."""
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), program["first"])
    x = np.asarray(program["wte"]["embedding"], np.float64)[np.asarray(tokens[0])]
    rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + CFG.norm_eps) * g
    n = rms(x, p["ln_1"]["scale"])
    c_q = rms(n @ p["attn"]["q_a"]["kernel"], p["attn"]["q_norm"]["scale"])

    def rotate(v):                              # [t, ..., d]: its first index_rope_dim, half-split
        turn, half = CFG.index_rope_dim, CFG.index_rope_dim // 2
        freqs = 1.0 / CFG.rope_base ** (np.arange(half) / half)
        angles = (np.arange(v.shape[0])[:, None] * freqs).reshape(
            (v.shape[0],) + (1,) * (v.ndim - 2) + (half,))
        a, b = v[..., :half], v[..., half:turn]
        return np.concatenate(
            [a * np.cos(angles) - b * np.sin(angles), b * np.cos(angles) + a * np.sin(angles),
             v[..., turn:]], -1)

    qi = rotate(np.einsum("tr,rhk->thk", c_q, p["index"]["q"]["kernel"]))
    raw = n @ p["index"]["k"]["kernel"]
    ki = (raw - raw.mean(-1, keepdims=True)) / np.sqrt(
        raw.var(-1, keepdims=True) + CFG.index_norm_eps)
    ki = rotate(ki * p["index"]["k_norm"]["scale"] + p["index"]["k_norm"]["bias"])
    w = n @ p["index"]["w"]["kernel"] * CFG.index_scale
    return (np.maximum(np.einsum("thk,sk->ths", qi, ki), 0.0) * w[:, :, None]).sum(1)


def test_both_forms_select_what_a_sort_of_the_hand_made_scores_selects(program, probe):
    """50 tokens (``topk`` is 16) in one chunk: layer 0's selection is, for every query, the
    16 largest of the scores made by hand from the weights (its first 16 queries select
    all they see), in the chunk form; the 50th token as a decode lane over the cache the
    first 49 left selects the same 16 in every layer; and padding selects nothing."""
    tokens = _tokens(50, seed=2)
    whole = probe(program, tokens, ZERO, *_caches(1, 64))
    selected = np.asarray(whole[-1])                            # [layers, 1, 50, 64]
    assert CFG.index_scale == pytest.approx(2 ** -0.5 * 16 ** -0.5)
    scores = _index_scores_by_hand(program, tokens)
    for t in range(50):
        order = sorted(range(t + 1), key=lambda s: (-scores[t, s], s))[:CFG.topk]
        assert sorted(np.flatnonzero(selected[0, 0, t])) == sorted(order), t
    assert selected.sum(-1)[:, 0].tolist() == [[min(t + 1, 16) for t in range(50)]] * CFG.num_layers
    held = [
        jnp.pad(x[:, :, :49], ((0, 0), (0, 0), (0, 15), (0, 0), (0, 0))) for x in whole[2:4]]
    one = probe(program, tokens[:, 49:], jnp.full((1,), 49, jnp.int32), *held)
    assert np.array_equal(np.asarray(one[-1])[:, 0, 0], selected[:, 0, 49])
    np.testing.assert_allclose(one[0][0, 0], whole[0][0, 49], atol=2e-5, rtol=2e-5)
    for new, rows in zip(one[2:4], whole[2:4]):
        np.testing.assert_allclose(new[:, :, 0], rows[:, :, 49], atol=1e-5, rtol=1e-5)
    # the probe changes nothing
    plain = CFG.make_extend_fn()(program, tokens, ZERO, *_caches(1, 64))
    assert len(plain) == 5 and np.array_equal(plain[0], whole[0])
    # a padded lane: its padding selects nothing and moves no real token
    padded = np.full((1, 64), -1)
    padded[0, :50] = np.asarray(tokens[0])
    out = probe(program, jnp.asarray(padded, jnp.int32), ZERO, *_caches(1, 64))
    assert not np.asarray(out[-1])[:, 0, 50:].any()
    assert np.array_equal(np.asarray(out[-1])[:, 0, :50], selected[:, 0])
    assert _named(out[4]) == _named(whole[4])


def test_a_query_under_topk_reads_all_it_sees_and_one_past_it_exactly_topk(program):
    """Up to ``topk`` rows of context the selection decides nothing (a configuration
    that reads more rows than there are gives the same logits); past it a query attends
    ``topk`` rows and no more, and that changes its logits."""
    tokens = _tokens(48, seed=3)
    sparse = CFG.make_extend_fn()(program, tokens, ZERO, *_caches(1, 64))
    dense = dataclasses.replace(CFG, topk=64).make_extend_fn()(
        program, tokens, ZERO, *_caches(1, 64))
    np.testing.assert_allclose(sparse[0][0, :16], dense[0][0, :16], atol=1e-5)
    assert float(jnp.abs(sparse[0][0, 40:] - dense[0][0, 40:]).max()) > 1e-3
    got, all_of_it = _named(sparse[4]), _named(dense[4])
    seen = sum(range(1, 49))
    assert got["sparse_keys_scored"] == all_of_it["sparse_keys_scored"] == CFG.num_layers * seen
    assert all_of_it["sparse_keys_attended"] == all_of_it["mla_pairs_absorbed"] == CFG.num_layers * seen
    attended = sum(min(t + 1, 16) for t in range(48))
    assert got["sparse_keys_attended"] == got["mla_pairs_absorbed"] == CFG.num_layers * attended


@pytest.fixture
def on_the_chip(monkeypatch):
    """``backend.on_tpu`` answers yes, and the chip's kernels run interpreted."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(
        attention, "latent_attention",
        functools.partial(attention.latent_attention, interpret=True))
    monkeypatch.setattr(
        moe, "grouped_matmul", functools.partial(moe.grouped_matmul, interpret=True))


@pytest.fixture(scope="module")
def off_the_chip(program, probe):
    """64 tokens off the chip: 40 cached by one chunk, then a chunk of 24 over them in the
    absorbed form under the selection mask. ``(tokens, the caches the 40 left, the chunk's
    outputs)``."""
    tokens = _tokens(64, seed=6)
    before = probe(program, tokens[:, :40], ZERO, *_caches(1, 64))
    held = [jnp.pad(x, ((0, 0), (0, 0), (0, 24), (0, 0), (0, 0))) for x in before[2:4]]
    return tokens, held, probe(program, tokens[:, 40:], jnp.full((1,), 40, jnp.int32), *held)


def test_the_absorbed_and_the_expanded_form_agree_on_the_same_cached_bits(
        program, off_the_chip, on_the_chip):
    """40 tokens cached, then a chunk of 24 over them: off the chip the chunk attends in
    the absorbed form under the selection mask, on it in the expanded form (``W_kvb`` over
    each tile of rows inside ``latent_attention``, interpreted here, nope 12 a head: the
    heads' slabs, no lane-aligned columns) under the same mask, and the last token alone
    attends, absorbed, over its 16 gathered rows. The same cached bits go in; the logits
    agree to a float32 rounding of sums in another order (2e-5 of logits of order 1: the
    absorbed form sums 40 products of the latent's features where the expanded sums 20 of a
    head's, and the softmax's weights are the same to 1e-6), and the selections are equal."""
    tokens, held, absorbed = off_the_chip
    on_chip = glm_moe_dsa.make_probe_fn(CFG)    # traced under the fixture: the chip's forms
    expanded = on_chip(program, tokens[:, 40:], jnp.full((1,), 40, jnp.int32), *held)
    assert float(jnp.abs(absorbed[0]).max()) > 0.3
    np.testing.assert_allclose(expanded[0], absorbed[0], atol=2e-5, rtol=2e-5)
    for a, b in zip(expanded[2:4], absorbed[2:4]):
        # what a token leaves does not depend on the form: layer 0's rows to the bit, a later
        # layer's as near as its input, which the layers before it attended
        assert np.array_equal(a[0], b[0])
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert np.array_equal(expanded[-1], absorbed[-1])
    a, e = _named(absorbed[4]), _named(expanded[4])
    pairs = CFG.num_layers * 24 * 16
    assert (a["mla_pairs_absorbed"], a["mla_pairs_expanded"], a["mla_rows_expanded"]) == (pairs, 0, 0)
    assert (e["mla_pairs_absorbed"], e["mla_pairs_expanded"]) == (0, pairs)
    assert e["mla_rows_expanded"] == CFG.num_layers * 64       # every live slot, selected or not
    assert {k: v for k, v in a.items() if not k.startswith("mla_")} == {
        k: v for k, v in e.items() if not k.startswith("mla_")}
    # the decode form over the same bits: the last token alone
    cached = [c.at[:, :, 40:63].set(new[:, :, :23]) for c, new in zip(held, absorbed[2:4])]
    one = on_chip(program, tokens[:, 63:], jnp.full((1,), 63, jnp.int32), *cached)
    np.testing.assert_allclose(one[0][0, 0], expanded[0][0, 23], atol=2e-5, rtol=2e-5)
    assert np.array_equal(np.asarray(one[-1])[:, 0, 0], np.asarray(expanded[-1])[:, 0, 23])


def test_the_counters_of_a_hand_worked_call(program):
    """Two lanes of a decode call, one 9 tokens in (under ``topk`` 16) and one 40 in: the
    indexer scores 10 + 41 pairs a layer, the attend reads 10 + 16, the slots read are
    those; a third lane is padding and counts nothing."""
    extend = CFG.make_extend_fn()
    tokens = jnp.asarray([[5], [7], [-1]], jnp.int32)
    lengths = jnp.asarray([9, 40, 0], jnp.int32)
    rng = np.random.default_rng(8)
    caches = [jnp.asarray(rng.standard_normal(c.shape), jnp.float32) for c in _caches(3, 64)]
    got = _named(extend(program, tokens, lengths, *caches)[4])
    L = CFG.num_layers
    assert got["mla_queries"] == got["sparse_queries"] == 2 * L
    assert got["sparse_keys_scored"] == (10 + 41) * L
    assert got["sparse_keys_attended"] == got["mla_pairs_absorbed"] == (10 + 16) * L
    assert got["sparse_slots_read"] == (10 + 16) * L
    assert got["mla_pairs_expanded"] == got["mla_rows_expanded"] == 0
    assert got["moe_tokens"] == 2 * CFG.expert_layers
    assert CFG.count_gathered(3, 64) == {"sparse_slots_gathered": L * 3 * 64}
    assert CFG.counters == moe.COUNTERS + layers.MLA_COUNTERS + layers.SPARSE_COUNTERS


def test_the_published_sizes_and_the_cut():
    whole = glm_moe_dsa.GlmMoeDsaConfig()
    assert (whole.embed_dim, whole.num_heads, whole.q_rank, whole.kv_rank) == (6144, 64, 2048, 512)
    assert (whole.nope_dim, whole.rope_dim, whole.v_dim) == (192, 64, 256)
    assert (whole.index_heads, whole.index_dim, whole.index_rope_dim, whole.topk) == (32, 128, 64, 2048)
    assert whole.softmax_scale == 1 / 16 and whole.row_dim == 640
    assert whole.cache_arrays == ((1, 640), (1, 128))
    assert 7.4e11 < whole.num_params() < 7.5e11                 # "744B"
    cut = glm_moe_dsa.GlmMoeDsaConfig(
        vocab_size=19360, num_layers=6, dense_layers=1, num_experts=16)
    assert cut.num_params() == 400_898_816 + 5 * 817_708_032 + 237_895_680 + 6144 == 4_727_340_800
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(CFG.init_params(0)))
    with pytest.raises(ValueError, match="are not among the 16"):
        glm_moe_dsa.glm_moe_dsa_nano(expert_offset=13)
    with pytest.raises(ValueError, match="dense layers"):
        glm_moe_dsa.glm_moe_dsa_nano(dense_layers=0)
    with pytest.raises(ValueError, match="rotated features"):
        glm_moe_dsa.glm_moe_dsa_nano(index_rope_dim=32)


def test_latent_attention_takes_heads_whose_columns_start_inside_a_lane_tile():
    """``ops/attention.latent_attention`` at a ``nope`` that is no whole number of the
    chip's 128 lanes (GLM-5's 192: here 24 beside a lane-aligned value of 128) reads a
    head's half of ``W_kvb`` as a slab, heads outermost, and gives the dense expanded form
    under a mask that is not lower-triangular: a key tile under the live bound may be
    wholly masked for a block of queries."""
    rng = np.random.default_rng(0)
    b, t, s, heads, nope, rope, rank, dv = 1, 16, 64, 4, 24, 8, 32, 128
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q_nope, q_rope, rows = f(b, t, heads, nope), f(b, t, heads, rope), f(b, s, 128)
    k_up, v_up = 0.3 * f(rank, heads, nope), 0.3 * f(rank, heads, dv)
    mask = np.zeros((b, t, s), bool)
    for q in range(t):
        mask[0, q, rng.choice(48, size=6, replace=False)] = True
    mask[0, :8, :16] = False                                    # a tile no query of a block reads
    mask[0, :8, 40] = True
    got = attention.latent_attention(
        q_nope, q_rope, rows, k_up, v_up, jnp.asarray(mask), jnp.asarray([48], jnp.int32),
        scale=0.2, block_q=8, block_k=16, interpret=True)
    latent, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
    k = jnp.einsum("bsc,chn->bshn", latent, k_up)
    v = jnp.einsum("bsc,chv->bshv", latent, v_up)
    logit = (jnp.einsum("bthn,bshn->bhts", q_nope, k)
             + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope)) * 0.2
    weight = jax.nn.softmax(jnp.where(mask[:, None], logit, -1e30), -1)
    want = jnp.einsum("bhts,bshv->bthv", weight, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
