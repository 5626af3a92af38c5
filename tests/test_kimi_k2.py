"""``models/kimi_k2.py`` on the CPU at a tiny size: the absorbed attend against
the expanded form written out here, a decode lane and a prefill chunk and the
chip's chunk (the expanded form's kernel, interpreted) give the same row and
count their pairs each under its form, a decode lane through the block table (the
pool's arena, read where it lies: off the chip densely and to the bit what padded
caches give, on it ``paged_attention`` with no value arena, interpreted),
padding changes and counts nothing,
YaRN's frequencies and scale against the closed form at the published keys, the
router's bias chooses and does not weigh, the attention kernel with ``d_k !=
d_v`` and one K/V head under several blocks of query heads, the kernel that
expands each tile of latents against the dense expanded form and against the
absorbed one, and the configuration's own arithmetic. The comparison with the plain reference is the
benchmark's (``tests/benchmark/test_bench_kimi_k2.py``)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_k2, moe
from ray_tpu.ops import attention

CFG = kimi_k2.kimi_k2_nano()


@pytest.fixture(scope="module")
def program():
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, CFG.init_params(5))


RANK, ROPE, ROW = CFG.kv_rank, CFG.rope_dim, CFG.row_dim


def _cache(lanes, cache):
    return jnp.zeros((CFG.num_layers, lanes, cache, 1, ROW), jnp.float32)


def _named(counters):
    return dict(zip(CFG.counters, np.asarray(counters).tolist()))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG.vocab_size, size=(1, n)), jnp.int32)


def test_a_token_leaves_one_row_the_normed_latent_and_the_rotated_key(program):
    extend = CFG.make_extend_fn()
    tokens = _tokens(24)
    logits, hidden, rows, counters = extend(
        program, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    # one row a token: the latent, the key behind it, zeros up to whole 128-lane tiles
    assert (RANK, ROPE, ROW) == (32, 8, 128) and CFG.cache_arrays == ((1, ROW),)
    assert rows.shape == (CFG.num_layers, 1, 24, 1, ROW)
    assert not np.asarray(rows)[..., RANK + ROPE:].any()
    assert logits.shape == (1, 24, CFG.vocab_size) and hidden.shape == (1, 24, CFG.embed_dim)
    # normed: the latent's mean square is 1 (its scale is 1), the key's is not
    latent, key = np.asarray(rows)[..., :RANK], np.asarray(rows)[..., RANK:RANK + ROPE]
    np.testing.assert_allclose((latent ** 2).mean(-1), 1.0, rtol=1e-4)
    assert abs((key ** 2).mean() - 1.0) > 0.1
    # rotated: the same token fed at another position leaves the same latent
    # and another key of the same length
    _, _, moved, _ = extend(program, tokens, jnp.full((1,), 7, jnp.int32), _cache(1, 64))
    moved = np.asarray(moved)
    np.testing.assert_allclose(moved[0, ..., :RANK], latent[0], rtol=1e-5, atol=1e-6)
    assert np.abs(moved[0, ..., RANK:RANK + ROPE] - key[0]).max() > 1e-2
    np.testing.assert_allclose(
        (moved[0, ..., RANK:RANK + ROPE] ** 2).sum(-1), (key[0] ** 2).sum(-1), rtol=1e-4)


def _expanded_layer_zero(program, tokens):
    """Layer 0's attention output in the expanded form, from the weights: every
    head's own keys and values made from the latent, plain causal softmax."""
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), program["first"])
    x = np.asarray(program["wte"]["embedding"], np.float64)[np.asarray(tokens[0])]

    def rms(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + CFG.norm_eps) * scale

    def rotate(v):                              # [t, ..., rope_dim], half-split pairs
        half = CFG.rope_dim // 2
        angles = np.arange(v.shape[0])[:, None] * CFG.rope_frequencies[None, :]
        angles = angles.reshape((v.shape[0],) + (1,) * (v.ndim - 2) + (half,))
        a, b = v[..., :half], v[..., half:]
        return np.concatenate(
            [a * np.cos(angles) - b * np.sin(angles), b * np.cos(angles) + a * np.sin(angles)], -1)

    n = rms(x, p["ln_1"]["scale"])
    attn = p["attn"]
    q = np.einsum("tr,rhk->thk", rms(n @ attn["q_a"]["kernel"], attn["q_norm"]["scale"]),
                  attn["q_b"]["kernel"])
    both = n @ attn["kv_a"]["kernel"]
    c_kv = rms(both[:, :CFG.kv_rank], attn["kv_norm"]["scale"])
    k_rope = rotate(both[:, CFG.kv_rank:])
    k = np.concatenate([
        np.einsum("tc,chn->thn", c_kv, attn["k_up"]["kernel"]),
        np.broadcast_to(k_rope[:, None], (len(x), CFG.num_heads, CFG.rope_dim))], -1)
    v = np.einsum("tc,chv->thv", c_kv, attn["v_up"]["kernel"])
    q = np.concatenate([q[..., :CFG.nope_dim], rotate(q[..., CFG.nope_dim:])], -1)
    scores = np.einsum("qhd,khd->hqk", q, k) * CFG.softmax_scale
    scores = np.where(np.tril(np.ones((len(x), len(x)), bool))[None], scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    out = np.einsum("hqk,khv->qhv", weights, v)
    return x + np.einsum("qhv,hvd->qd", out, attn["o"]["kernel"]), c_kv, k_rope


def test_the_absorbed_attend_is_the_expanded_form(program):
    """Layer 0 alone (its MLP and every later layer's output matrices are
    zeroed, so ``hidden`` is the norm of what its attention leaves): the program
    never makes a head's key or value, and gives what the expanded form gives."""
    cfg = CFG
    layers = program["blocks"]["layers"]
    params = {
        **program,
        "first": {**program["first"], "mlp": jax.tree.map(jnp.zeros_like, program["first"]["mlp"])},
        "blocks": {"layers": {
            **layers,
            "attn": {**layers["attn"], "o": jax.tree.map(jnp.zeros_like, layers["attn"]["o"])},
            "moe": {**layers["moe"], "wo": jnp.zeros_like(layers["moe"]["wo"])},
            "shared": {**layers["shared"], "wo": jnp.zeros_like(layers["shared"]["wo"])},
        }},
    }
    tokens = _tokens(40, seed=3)
    after, c_kv, k_rope = _expanded_layer_zero(program, tokens)
    _, hidden, rows, _ = cfg.make_extend_fn()(
        params, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    normed = after / np.sqrt((after * after).mean(-1, keepdims=True) + cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(hidden)[0], normed, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(rows)[0, 0, :, 0, :RANK], c_kv, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(rows)[0, 0, :, 0, RANK:RANK + ROPE], k_rope, atol=1e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def chunked(program):
    """24 tokens as one prefill chunk, then the 25th as a decode lane over the
    cache the chunk left."""
    extend = CFG.make_extend_fn()
    tokens = _tokens(25, seed=1)
    logits, _, rows, counters = extend(
        program, tokens[:, :24], jnp.zeros((1,), jnp.int32), _cache(1, 64))
    held = jnp.pad(rows, ((0, 0), (0, 0), (0, 40), (0, 0), (0, 0)))
    return extend, tokens, logits, held, counters


BLOCK = 16


def _paged(held, rubbish):
    """``held`` [layers, 1, 64, 1, row] as a pool would keep it: an arena of nine blocks
    of 16 whose blocks 5, 2, 7, 0 are the lane's pages, the others ``rubbish``, and
    the lane's table with one more entry than it has pages, which names rubbish."""
    arena = np.full((CFG.num_layers, 9, BLOCK, 1, ROW), rubbish, np.float32)
    table = np.array([[5, 2, 7, 0, 8]], np.int32)
    arena[:, table[0, :4]] = np.asarray(held)[:, 0].reshape(CFG.num_layers, 4, BLOCK, 1, ROW)
    return jnp.asarray(arena), jnp.asarray(table)


def test_a_decode_lane_and_a_prefill_chunk_give_the_same_row(program, chunked):
    """The decode lane as the engine calls it: handed the pool's arena and the lane's
    block table. Off the chip that is to the bit what the padded cache gives (the
    table's pages side by side are the padded cache), whatever the table names past
    the lane's live pages (something finite, as a pool's arena holds: the dense form
    weighs it 0, the kernel never fetches it)."""
    extend, tokens, _, held, first = chunked
    lengths = jnp.full((1,), 24, jnp.int32)
    arena, table = _paged(held, 1e3)
    one, hidden, row, counters = extend(program, tokens[:, 24:], lengths, arena, table=table[:, :4])
    padded = extend(program, tokens[:, 24:], lengths, held)
    for got, want in zip((one, hidden, row, counters), padded):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a longer table: the same lane in a larger bucket, its last entry never followed
    wider = extend(program, tokens[:, 24:], lengths, arena, table=table)
    np.testing.assert_allclose(np.asarray(wider[0]), np.asarray(one), atol=2e-6, rtol=2e-6)
    whole, _, rows, _ = extend(program, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    np.testing.assert_allclose(np.asarray(one)[0, 0], np.asarray(whole)[0, 24], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(row)[:, 0, 0], np.asarray(rows)[:, 0, 24], atol=1e-5)
    named, before = _named(counters), _named(first)
    assert (before["mla_queries"], named["mla_queries"]) == (CFG.num_layers * 24, CFG.num_layers)
    assert before["mla_pairs_absorbed"] == CFG.num_layers * 24 * 25 // 2
    assert named["mla_pairs_absorbed"] == CFG.num_layers * 25
    assert named["mla_pairs_expanded"] == named["mla_rows_expanded"] == 0
    # the dense layer routes nothing: the expert layers' tokens alone
    assert (before["moe_tokens"], named["moe_tokens"]) == (CFG.expert_layers * 24, CFG.expert_layers)


@pytest.fixture
def on_the_chip(monkeypatch):
    """``extend`` as the chip traces it, on the CPU: ``backend.on_tpu`` answers
    yes, the chunk's attention kernel runs interpreted at small tiles with its
    64 heads' budget cut to two blocks of four, a decode call's runs interpreted over
    the pages it is handed, the grouped matmul is XLA's.
    Yields the shapes the chunk's kernel was called with and, as a pair whose first
    is ``"paged"``, those of the decode call's."""
    from ray_tpu.ops import backend

    real, real_paged = attention.latent_attention, attention.paged_attention
    seen = []

    def paged(q, k_pages, v_pages, at, table, lengths, k_own, v_own, **kw):
        seen.append(("paged", (q.shape, k_pages.shape, v_pages, table.shape, k_own.shape, v_own.shape)))
        return real_paged(q, k_pages, v_pages, at, table, lengths, k_own, v_own, interpret=True, **kw)

    def interpreted(q_nope, q_rope, rows, k_up, v_up, mask, kv_len, **kw):
        seen.append((q_nope.shape, q_rope.shape, rows.shape, k_up.shape, v_up.shape))
        return real(
            q_nope, q_rope, rows, k_up, v_up, mask, kv_len, interpret=True, block_q=16,
            block_k=32, **kw)

    def never(*a, **kw):
        raise AssertionError("a latent chunk on the chip attends in latent_attention")

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "grouped_matmul", lambda rows, w, sizes: jax.lax.ragged_dot(rows, w, sizes))
    monkeypatch.setattr(attention, "latent_attention", interpreted)
    monkeypatch.setattr(attention, "paged_attention", paged)
    monkeypatch.setattr(attention, "masked_attention", never)
    monkeypatch.setattr(attention, "MASKED_ACC_BYTES", 4 * 16 * (CFG.v_dim + 256) * 4)
    jax.clear_caches()
    yield seen
    jax.clear_caches()


def test_the_chips_kernel_gives_the_chunks_rows(program, chunked, on_the_chip):
    """What a prefill chunk runs on the chip: the same ``extend`` with the
    expanded form's kernel in its place (interpreted), every tile of rows put
    through ``W_kvb`` under two blocks of four heads. It gives the absorbed
    form's logits and rows, and counts its pairs as expanded."""
    _, tokens, want, held, _ = chunked
    assert attention._heads_a_tile(CFG.num_heads, 16, CFG.v_dim + 256) == 4 == CFG.num_heads // 2
    got, _, rows, counters = CFG.make_extend_fn()(
        program, tokens[:, :24], jnp.zeros((1,), jnp.int32), _cache(1, 64))
    h = CFG.num_heads
    assert on_the_chip and set(on_the_chip) == {(
        (1, 24, h, CFG.nope_dim), (1, 24, h, ROPE), (1, 64, ROW), (RANK, h, CFG.nope_dim),
        (RANK, h, CFG.v_dim))}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)
    # the rows are made as off the chip: layer 0's to the bit, the others' of a
    # stream that differs by the attend's rounding
    np.testing.assert_array_equal(np.asarray(rows)[0], np.asarray(held)[0, :, :24])
    np.testing.assert_allclose(np.asarray(rows), np.asarray(held)[:, :, :24], atol=3e-5, rtol=3e-5)
    named = _named(counters)
    assert named["mla_queries"] == CFG.num_layers * 24
    assert named["mla_pairs_expanded"] == CFG.num_layers * 24 * 25 // 2
    assert named["mla_pairs_absorbed"] == 0
    assert named["mla_rows_expanded"] == CFG.num_layers * 24


def test_on_the_chip_a_decode_call_stays_absorbed_and_a_chunk_counts_its_live_slots(
        program, chunked, on_the_chip):
    """Under the chip's answer a decode call through the block table stays absorbed
    (its pairs counted so) and attends in ``paged_attention``, once a layer, over the
    one arena as it lies and no value arena, the own row's first ``kv_rank`` features
    its value; a table entry past the lane's pages names NaN and is never fetched.
    A second chunk behind the first counts every live slot of the lane through
    ``W_kvb``, and no pair of padding."""
    extend, tokens, _, held, _ = chunked
    lengths = jnp.full((1,), 24, jnp.int32)
    want, _, row, counted = extend(program, tokens[:, 24:], lengths, held)
    chip = CFG.make_extend_fn()
    arena, table = _paged(held, np.nan)
    one, _, own, counters = chip(program, tokens[:, 24:], lengths, arena, table=table)
    h = CFG.num_heads
    assert set(on_the_chip) == {("paged", (
        (1, 1, h, ROW), arena.shape, None, (1, 5), (1, ROW), (1, RANK)))}
    on_the_chip.clear()
    np.testing.assert_array_equal(np.asarray(own)[0], np.asarray(row)[0])   # layer 0's, to the bit
    assert _named(counters) == _named(counted)
    assert _named(counters)["mla_pairs_absorbed"] == CFG.num_layers * 25
    assert _named(counters)["mla_pairs_expanded"] == _named(counters)["mla_rows_expanded"] == 0
    np.testing.assert_allclose(np.asarray(one), np.asarray(want), atol=2e-5, rtol=2e-5)
    # ten more tokens behind the 24 cached, six of the chunk's sixteen padding,
    # beside a lane of padding alone
    more = jnp.concatenate([_tokens(10, seed=4), jnp.full((1, 6), -1, jnp.int32)], 1)
    lanes = jnp.concatenate([more, jnp.full((1, 16), -1, jnp.int32)])
    both = jnp.concatenate([held, jnp.zeros_like(held)], 1)
    off, *_ = extend(program, lanes, jnp.asarray([24, 0], jnp.int32), both)
    got, _, _, counters = chip(program, lanes, jnp.asarray([24, 0], jnp.int32), both)
    assert on_the_chip and len(set(on_the_chip)) == 1
    np.testing.assert_allclose(np.asarray(got)[0, :10], np.asarray(off)[0, :10], atol=3e-5, rtol=3e-5)
    assert np.isfinite(np.asarray(got)).all()
    named = _named(counters)
    assert named["mla_queries"] == CFG.num_layers * 10
    assert named["mla_pairs_expanded"] == CFG.num_layers * sum(range(25, 35))
    assert named["mla_rows_expanded"] == CFG.num_layers * 34
    assert named["mla_pairs_absorbed"] == 0


def test_padding_changes_no_real_token_and_counts_nothing(program):
    extend = CFG.make_extend_fn()
    tokens = _tokens(10, seed=2)
    padded = jnp.concatenate([tokens, jnp.full((1, 6), -1, jnp.int32)], 1)
    lanes = jnp.concatenate([padded, jnp.full((1, 16), -1, jnp.int32)])     # a lane of padding
    want, *_, counted = extend(program, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    got, *_, counters = extend(program, lanes, jnp.zeros((2,), jnp.int32), _cache(2, 64))
    np.testing.assert_allclose(np.asarray(got)[0, :10], np.asarray(want)[0], atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    assert _named(counters) == _named(counted)
    assert _named(counters)["mla_pairs_absorbed"] == CFG.num_layers * 10 * 11 // 2


# -- YaRN -----------------------------------------------------------------------


def test_yarn_at_the_published_keys_is_the_closed_form():
    """``rope_theta`` 50000, factor 32 over an original 4096, both betas 1: the
    correction dimension is 64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16, so
    frequencies 0..19 are the base's own and 20..31 a 32nd of them; ``mscale``
    = 0.1 ln 32 + 1 enters the softmax scale squared, and cos and sin are not
    scaled."""
    cfg = kimi_k2.KimiK2Config()
    freqs = cfg.rope_frequencies
    own = 50000.0 ** (-np.arange(32) / 32.0)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000)) == pytest.approx(19.164, abs=1e-3)
    np.testing.assert_allclose(freqs[:20], own[:20], rtol=1e-12)
    np.testing.assert_allclose(freqs[20:], own[20:] / 32, rtol=1e-12)
    mscale = 0.1 * math.log(32) + 1
    assert mscale == pytest.approx(1.34657, abs=1e-5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert cfg.softmax_scale == pytest.approx(0.13086, abs=1e-5)


@pytest.mark.parametrize("betas,ramp", [((32, 1), "a ramp"), ((1, 1), "a step")])
def test_yarn_ramps_between_the_correction_dimensions(betas, ramp):
    freqs = kimi_k2.yarn_frequencies(64, 50000.0, 32.0, 4096, *betas)
    own = 50000.0 ** (-np.arange(32) / 32.0)
    share = (own - freqs) / (own - own / 32)         # 0: the base's own, 1: interpolated
    assert share[0] == 0 and share[-1] == pytest.approx(1) and (np.diff(share) >= -1e-12).all()
    between = ((share > 1e-9) & (share < 1 - 1e-9)).sum()
    assert (between > 3) if ramp == "a ramp" else (between == 0)
    # no scaling: plain rotary
    np.testing.assert_allclose(kimi_k2.yarn_frequencies(64, 50000.0, 1.0, 4096, 1, 1), own, rtol=1e-12)


# -- the router ------------------------------------------------------------------


def test_the_bias_chooses_and_does_not_weigh():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (200, 32))
    router = jax.random.normal(keys[1], (32, 16)) * 0.3
    bias = 0.05 * jax.random.normal(keys[2], (16,))
    plain_w, plain_e = moe.sigmoid_bias_top_k(h, router, jnp.zeros((16,)), 4, 2.827)
    w, e = moe.sigmoid_bias_top_k(h, router, bias, 4, 2.827)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(h, router, precision="highest")))
    # without a bias: sigmoid_top_k's choice and weights, times the scale
    base_w, base_e = moe.sigmoid_top_k(h, router, 4)
    assert np.array_equal(np.asarray(plain_e), np.asarray(base_e))
    np.testing.assert_allclose(np.asarray(plain_w), 2.827 * np.asarray(base_w), rtol=1e-6)
    # with it: the 4 largest of score + bias ...
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(e), -1), np.sort(want, -1))
    moved = (np.sort(np.asarray(e), -1) != np.sort(np.asarray(plain_e), -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95            # it changes some tokens' experts, not all
    # ... weighed by their scores alone: they sum to the scale, and a token
    # whose choice the bias left alone keeps every weight
    chosen = np.take_along_axis(scores, np.asarray(e), -1)
    np.testing.assert_allclose(
        np.asarray(w), 2.827 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-6)
    same = ~moved                                # (the order of a token's four may differ)
    by_expert = lambda w, e: np.take_along_axis(np.asarray(w), np.argsort(np.asarray(e), -1), -1)
    np.testing.assert_allclose(by_expert(w, e)[same], by_expert(plain_w, plain_e)[same], rtol=1e-6)


# -- the kernel under a latent ----------------------------------------------------


def _dense_attend(q, k, v, mask, scale):
    logit = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    weight = jax.nn.softmax(jnp.where(mask[:, None, None], logit, -1e30), axis=-1)
    return jnp.einsum("bhgqk,bkhv->bqhgv", weight.astype(v.dtype), v)


LATENT_CASES = {
    # K/V heads, query heads each, heads a block, d_k, d_v, starts, queries, query tile, key tile
    "one_latent_under_8_blocks_of_8": (1, 64, 8, 40, 32, (5,), 32, 16, 16),
    "one_latent_one_block": (1, 16, None, 40, 32, (0, 30), 32, 16, 32),
    "two_kv_heads_two_blocks_each": (2, 8, 4, 24, 16, (3, 11), 24, 16, 48),
    "value_wider_than_key": (1, 8, 2, 16, 24, (7,), 16, 512, 2048),
}


@pytest.mark.parametrize("case", list(LATENT_CASES))
def test_the_attention_kernel_with_a_value_narrower_than_its_key(case, monkeypatch):
    """``ops/attention.masked_attention``, interpreted, with ``d_k != d_v`` and a
    K/V head's query heads in several blocks, against the dense form; the value
    is the key's first features, as a latent's is. Keys past a lane's live
    bound hold NaN."""
    kv, groups, block, dk, dv, starts, tokens, block_q, block_k = LATENT_CASES[case]
    if block:
        # an accumulator with room for ``block`` heads' tile of queries and no more
        monkeypatch.setattr(attention, "MASKED_ACC_BYTES", block * min(block_q, tokens) * dv * 4)
        assert attention._heads_a_tile(groups, min(block_q, tokens), dv) == block
    rng = np.random.default_rng(len(case))
    lanes, cache = len(starts), 64
    q = rng.standard_normal((lanes, tokens, kv, groups, dk)).astype(np.float32)
    wide = rng.standard_normal((lanes, cache, kv, max(dk, dv))).astype(np.float32)
    positions = np.asarray(starts)[:, None] + np.arange(tokens)[None, :]
    mask = (np.arange(cache)[None, None, :] <= positions[:, :, None]) & (
        rng.random((lanes, tokens, cache)) < 0.6)
    mask[np.arange(lanes)[:, None], np.arange(tokens)[None, :], positions] = True
    kv_len = positions.max(1) + 1
    dirty = wide.copy()
    step = min(block_k, cache)
    for lane, n in enumerate(kv_len):
        dirty[lane, -(-n // step) * step:] = np.nan
    out = np.asarray(attention.masked_attention(
        jnp.asarray(q), jnp.asarray(dirty[..., :dk]), jnp.asarray(dirty[..., :dv]),
        jnp.asarray(mask), jnp.asarray(kv_len, jnp.int32), scale=0.2, block_q=block_q,
        block_k=block_k, interpret=True))
    want = np.asarray(_dense_attend(
        jnp.asarray(q), jnp.asarray(wide[..., :dk]), jnp.asarray(wide[..., :dv]),
        jnp.asarray(mask), 0.2))
    assert out.shape == want.shape == (lanes, tokens, kv, groups, dv)
    np.testing.assert_allclose(out, want, atol=3e-6, rtol=3e-6)


@pytest.mark.parametrize("groups, dv, heads", [
    (8, 128, 8),        # Keye: all of a K/V head's query heads, 2 MiB
    (64, 512, 8),       # a latent under 64 heads: 8 blocks of 8, 8 MiB each
    (6, 2048, 2),       # 3 would divide too and do not fit
    (5, 4096, 1),       # a single head is taken whatever it needs
])
def test_as_many_heads_meet_a_tile_as_the_accumulator_holds(groups, dv, heads):
    assert attention._heads_a_tile(groups, attention.MASKED_BLOCK_Q, dv) == heads


# -- the kernel that expands a tile of latents ---------------------------------------


def _dense_expanded(q_nope, q_rope, rows, k_up, v_up, mask, scale):
    """The expanded form written out: every head's keys and values of every
    slot, float32 throughout."""
    rank, rope = k_up.shape[0], q_rope.shape[-1]
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    latent, key = f32(rows)[..., :rank], f32(rows)[..., rank:rank + rope]
    k = jnp.einsum("bsc,chn->bshn", latent, f32(k_up), precision="highest")
    v = jnp.einsum("bsc,chv->bshv", latent, f32(v_up), precision="highest")
    logit = scale * (
        jnp.einsum("bthn,bshn->bhts", f32(q_nope), k, precision="highest")
        + jnp.einsum("bthr,bsr->bhts", f32(q_rope), key, precision="highest"))
    weight = jax.nn.softmax(jnp.where(mask[:, None], logit, -1e30), axis=-1)
    return jnp.einsum("bhts,bshv->bthv", weight, v, precision="highest")


EXPANDED_CASES = {
    # heads, heads a block, nope, rope, rank, d_v, row, starts, queries, query tile, key tile
    "two_blocks_of_four_small_tiles": (8, 4, 16, 8, 32, 16, 128, (5,), 24, 8, 16),
    "one_block_a_lane_cut_short": (4, None, 16, 8, 32, 16, 128, (0, 30), 16, 16, 16),
    "eight_blocks_of_one_head": (8, 1, 8, 8, 16, 24, 32, (3, 11), 24, 16, 48),
    "tiles_wider_than_the_call": (16, 8, 16, 16, 32, 8, 64, (7,), 16, 512, 2048),
}


@pytest.mark.parametrize("case", list(EXPANDED_CASES))
def test_the_latent_kernel_is_the_dense_expanded_form(case, monkeypatch):
    """``ops/attention.latent_attention``, interpreted, in float32 against the
    expanded form written out: heads in one or several blocks, a random mask
    under the causal one, key tiles past a lane's live bound holding NaN (never
    fetched), a query tile and a key tile that do not divide the call. A query
    whose mask is empty gets finite rubbish and changes no other row."""
    heads, block, nope, rope, rank, dv, row, starts, tokens, block_q, block_k = EXPANDED_CASES[case]
    if block:
        # room for ``block`` heads' accumulators, maxima and sums, and no more
        monkeypatch.setattr(
            attention, "MASKED_ACC_BYTES", block * min(block_q, tokens) * (dv + 256) * 4)
        assert attention._heads_a_tile(heads, min(block_q, tokens), dv + 256) == block
    rng = np.random.default_rng(len(case))
    lanes, cache = len(starts), 64
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    q_nope, q_rope = draw(lanes, tokens, heads, nope), draw(lanes, tokens, heads, rope)
    k_up, v_up = draw(rank, heads, nope) * rank ** -0.5, draw(rank, heads, dv) * rank ** -0.5
    rows = draw(lanes, cache, row)
    positions = np.asarray(starts)[:, None] + np.arange(tokens)[None, :]
    mask = (np.arange(cache)[None, None, :] <= positions[:, :, None]) & (
        rng.random((lanes, tokens, cache)) < 0.6)
    mask[np.arange(lanes)[:, None], np.arange(tokens)[None, :], positions] = True
    mask[0, 3] = False                               # a query that may read nothing
    kv_len = positions.max(1) + 1
    dirty = rows.copy()
    step = min(block_k, cache)
    for lane, n in enumerate(kv_len):
        dirty[lane, -(-n // step) * step:] = np.nan
    out = np.asarray(attention.latent_attention(
        *map(jnp.asarray, (q_nope, q_rope, dirty, k_up, v_up, mask)),
        jnp.asarray(kv_len, jnp.int32), scale=0.2, block_q=block_q, block_k=block_k,
        interpret=True))
    want = np.asarray(_dense_expanded(q_nope, q_rope, rows, k_up, v_up, jnp.asarray(mask), 0.2))
    assert out.shape == want.shape == (lanes, tokens, heads, dv)
    assert np.isfinite(out).all()
    real = mask.any(-1)
    np.testing.assert_allclose(out[real], want[real], atol=5e-6, rtol=5e-6)


def test_the_expanded_and_the_absorbed_form_agree_within_bfloat16():
    """The same rows, queries and ``W_kvb`` in bfloat16 through both kernels: the
    absorbed form (``q_nope W_kvb^K`` against the row, the summed latents through
    ``W_kvb^V``) and the expanded one differ by their roundings alone, and each
    from the float32 expanded form by as much."""
    heads, nope, rope, rank, dv, row, tokens, cache = 8, 16, 8, 32, 16, 128, 32, 64
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q_nope, q_rope = draw(1, tokens, heads, nope), draw(1, tokens, heads, rope)
    k_up, v_up = draw(rank, heads, nope) * rank ** -0.5, draw(rank, heads, dv) * rank ** -0.5
    rows = draw(1, cache, row).at[..., rank + rope:].set(0)
    positions = 20 + np.arange(tokens)[None, :]
    mask = jnp.asarray(np.arange(cache)[None, None, :] <= positions[:, :, None])
    kv_len = jnp.asarray([20 + tokens], jnp.int32)
    expanded = attention.latent_attention(
        q_nope, q_rope, rows, k_up, v_up, mask, kv_len, scale=0.2, block_q=16, block_k=32,
        interpret=True)
    met = jnp.concatenate([
        jnp.einsum("bthn,chn->bthc", q_nope, k_up), q_rope,
        jnp.zeros((1, tokens, heads, row - rank - rope), jnp.bfloat16)], -1)
    summed = attention.masked_attention(
        met[:, :, None], rows[:, :, None], rows[:, :, None, :rank], mask, kv_len, scale=0.2,
        block_q=16, block_k=32, interpret=True)[:, :, 0]
    absorbed = jnp.einsum("bthc,chv->bthv", summed, v_up)
    assert expanded.dtype == absorbed.dtype == jnp.bfloat16
    want = np.asarray(_dense_expanded(q_nope, q_rope, rows, k_up, v_up, mask, 0.2))
    both = [np.asarray(x, np.float32) for x in (expanded, absorbed)]
    assert 0 < np.abs(both[0] - both[1]).max() < 0.05 * np.abs(want).max()
    for got in both:
        assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


@pytest.mark.parametrize("heads, block_q, dv, block", [
    (64, 512, 128, 8),      # the published widths: eight blocks of eight heads
    (64, 128, 128, 32),     # a shorter chunk leaves room for more
    (8, 16, 16, 8),         # the tiny model's heads, all at once
])
def test_a_block_of_the_latent_kernels_heads_has_room_for_its_running_softmax(
        heads, block_q, dv, block):
    """Beside a head's float32 accumulator stand its running maximum and sum, a
    lane tile (128) each in VMEM whatever their one column."""
    assert attention._heads_a_tile(heads, block_q, dv + 2 * 128) == block


# -- the configuration ---------------------------------------------------------------


def test_the_configuration_counts_its_parameters_and_states_what_a_token_holds():
    params = CFG.init_params(0)
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    assert params["blocks"]["layers"]["moe"]["bias"].dtype == jnp.float32
    assert float(jnp.std(params["blocks"]["layers"]["moe"]["bias"])) == pytest.approx(
        CFG.bias_std, rel=0.3)
    # the published model whole, and one chip's seven layers of it
    whole = kimi_k2.KimiK2Config()
    assert whole.num_params() == pytest.approx(1.027e12, rel=2e-3)
    share = kimi_k2.KimiK2Config(vocab_size=20480, num_layers=7, num_experts=12)
    assert share.num_params() == 4_849_591_552
    assert share.cache_arrays == ((1, 640),) and share.expert_layers == 6
    with pytest.raises(ValueError, match="not among the 384"):
        kimi_k2.KimiK2Config(num_experts=12, expert_offset=380)
    with pytest.raises(ValueError, match="dense layers"):
        kimi_k2.KimiK2Config(num_layers=3, dense_layers=3)
