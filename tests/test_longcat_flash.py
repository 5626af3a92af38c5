"""``models/longcat_flash.py`` on the CPU at a tiny size: a token leaves two cached rows
a layer (the scaled normed latent, the rotated key), the layer is two sub-blocks and a
shortcut written out here from the weights, a decode lane through the block table and a
prefill chunk and the chip's forms (the expanded form's kernel and the paged one,
interpreted) give the same rows, the router's softmax is over all outputs, its bias
chooses and does not weigh and its weights are not normalised, a pick on a zero-compute
expert gives the token back and sorts into no group, **the shares add up** to the uncut
layer, and through ``LLMServer`` a pool of ``cache_layers`` = 2 x ``num_layers`` slabs
reuses, clones, evicts and decodes through ``table=`` to the bit. The comparison with the
plain reference's whole forward pass is the benchmark's
(``tests/benchmark/test_bench_longcat_flash.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers, longcat_flash, moe
from ray_tpu.ops import attention
from ray_tpu.serve import llm

CFG = longcat_flash.longcat_flash_nano()
RANK, ROPE, ROW = CFG.kv_rank, CFG.rope_dim, CFG.row_dim
SLABS = CFG.cache_layers


@pytest.fixture(scope="module")
def program():
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, CFG.init_params(5))


def _cache(lanes, cache):
    return jnp.zeros((SLABS, lanes, cache, 1, ROW), jnp.float32)


def _named(counters):
    return dict(zip(CFG.counters, np.asarray(counters).tolist()))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, CFG.vocab_size, size=(1, n)), jnp.int32)


# -- the layer ---------------------------------------------------------------------


def test_a_token_leaves_two_rows_a_layer_the_scaled_latent_and_the_rotated_key(program):
    extend = CFG.make_extend_fn()
    tokens = _tokens(24)
    logits, hidden, rows, counters = extend(
        program, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    assert (RANK, ROPE, ROW) == (32, 8, 128) and CFG.cache_arrays == ((1, ROW),)
    assert SLABS == 2 * CFG.num_layers == 6 and rows.shape == (SLABS, 1, 24, 1, ROW)
    assert not np.asarray(rows)[..., RANK + ROPE:].any()
    assert logits.shape == (1, 24, CFG.vocab_size) and hidden.shape == (1, 24, CFG.embed_dim)
    # normed and scaled: the latent's mean square is mla_scale_kv_lora^2 = hidden / rank
    latent, key = np.asarray(rows)[..., :RANK], np.asarray(rows)[..., RANK:RANK + ROPE]
    assert CFG.kv_scale ** 2 == pytest.approx(CFG.embed_dim / RANK) == 2.0
    np.testing.assert_allclose((latent ** 2).mean(-1), 2.0, rtol=1e-4)
    # rotated: the same tokens at another position leave layer 0's first latent as it
    # was and another key of the same length
    _, _, moved, _ = extend(program, tokens, jnp.full((1,), 7, jnp.int32), _cache(1, 64))
    moved = np.asarray(moved)
    np.testing.assert_allclose(moved[0, ..., :RANK], latent[0], rtol=1e-5, atol=1e-6)
    assert np.abs(moved[0, ..., RANK:RANK + ROPE] - key[0]).max() > 1e-2
    np.testing.assert_allclose(
        (moved[0, ..., RANK:RANK + ROPE] ** 2).sum(-1), (key[0] ** 2).sum(-1), rtol=1e-4)
    named = _named(counters)
    assert named["mla_queries"] == SLABS * 24 and named["mla_pairs_absorbed"] == SLABS * 24 * 25 // 2
    assert named["moe_tokens"] == CFG.num_layers * 24


def _layer_zero(program, tokens, shortcut=True):
    """Layer 0 from the weights in float64, the expanded form of its attention and its
    expert layer as the published equations write them: what the stream holds after it."""
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), program["blocks"]["layers"])
    x = np.asarray(program["wte"]["embedding"], np.float64)[np.asarray(tokens[0])]
    n = len(x)

    def rms(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + CFG.norm_eps) * scale

    def silu(v):
        return v / (1 + np.exp(-v))

    def gated(v, wi, wo):
        f = wo.shape[0]
        return (silu(v @ wi[:, :f]) * (v @ wi[:, f:])) @ wo

    def rotate(v):                              # [t, ..., rope_dim], half-split pairs
        half = ROPE // 2
        freqs = CFG.rope_base ** (-np.arange(half) / half)
        angles = (np.arange(n)[:, None] * freqs[None, :]).reshape(
            (n,) + (1,) * (v.ndim - 2) + (half,))
        a, b = v[..., :half], v[..., half:]
        return np.concatenate(
            [a * np.cos(angles) - b * np.sin(angles), b * np.cos(angles) + a * np.sin(angles)], -1)

    def mla(v, attn):
        q = CFG.q_scale * np.einsum(
            "tr,rhk->thk", rms(v @ attn["q_a"]["kernel"], attn["q_norm"]["scale"]),
            attn["q_b"]["kernel"])
        both = v @ attn["kv_a"]["kernel"]
        c = CFG.kv_scale * rms(both[:, :RANK], attn["kv_norm"]["scale"])
        k = np.concatenate([
            np.einsum("tc,chn->thn", c, attn["k_up"]["kernel"]),
            np.broadcast_to(rotate(both[:, RANK:])[:, None], (n, CFG.num_heads, ROPE))], -1)
        val = np.einsum("tc,chv->thv", c, attn["v_up"]["kernel"])
        q = np.concatenate([q[..., :CFG.nope_dim], rotate(q[..., CFG.nope_dim:])], -1)
        scores = np.einsum("qhd,khd->hqk", q, k) * CFG.softmax_scale
        scores = np.where(np.tril(np.ones((n, n), bool))[None], scores, -np.inf)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        return np.einsum("qhv,hvd->qd", np.einsum("hqk,khv->qhv", weights, val), attn["o"]["kernel"])

    def experts(u):
        logit = u @ p["moe"]["router"]
        prob = np.exp(logit - logit.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        chosen = np.argsort(-(prob + p["moe"]["bias"]), axis=-1, kind="stable")[:, :CFG.experts_per_token]
        out = np.zeros_like(u)
        for t in range(n):
            for c in chosen[t]:
                w = CFG.routed_scale * prob[t, c]
                held = c - CFG.expert_offset
                if c >= CFG.routed_experts:
                    out[t] += w * u[t]
                elif 0 <= held < CFG.num_experts:
                    out[t] += w * gated(u[t], p["moe"]["wi"][held], p["moe"]["wo"][held])
        return out

    for i, sub in enumerate(longcat_flash.SUB_BLOCKS):
        b = p[sub]
        h = x + mla(rms(x, b["ln_in"]["scale"]), b["attn"])
        u = rms(h, b["ln_post"]["scale"])
        if i == 0:
            s = experts(u)
        x = h + gated(u, b["mlp"]["wi"], b["mlp"]["wo"])
    return x + s if shortcut else x


def test_the_layer_is_two_sub_blocks_and_a_shortcut_across_the_second(program):
    """One layer of the program (a configuration of one layer over the same weights'
    layer 0) against the published equations in float64: the expert layer reads the first
    sub-block's normed stream and lands after the second; without it the stream differs."""
    one = longcat_flash.longcat_flash_nano(num_layers=1)
    params = {**program, "blocks": {"layers": jax.tree.map(
        lambda a: a[:1], program["blocks"]["layers"])}}
    tokens = _tokens(40, seed=3)
    _, hidden, rows, _ = one.make_extend_fn()(
        params, tokens, jnp.zeros((1,), jnp.int32), jnp.zeros((2, 1, 64, 1, ROW), jnp.float32))
    after = _layer_zero(program, tokens)

    def normed(v):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + CFG.norm_eps)

    np.testing.assert_allclose(np.asarray(hidden)[0], normed(after), atol=3e-4, rtol=3e-4)
    without = normed(_layer_zero(program, tokens, shortcut=False))
    assert np.abs(without - normed(after)).max() > 0.05
    assert rows.shape == (2, 1, 40, 1, ROW)


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
    """``held`` [slabs, 1, 64, 1, row] as a pool would keep it: an arena of nine blocks
    of 16 whose blocks 5, 2, 7, 0 are the lane's pages, the others ``rubbish``, and
    the lane's table with one more entry than it has pages, which names rubbish."""
    arena = np.full((SLABS, 9, BLOCK, 1, ROW), rubbish, np.float32)
    table = np.array([[5, 2, 7, 0, 8]], np.int32)
    arena[:, table[0, :4]] = np.asarray(held)[:, 0].reshape(SLABS, 4, BLOCK, 1, ROW)
    return jnp.asarray(arena), jnp.asarray(table)


def test_a_decode_lane_through_the_table_and_a_prefill_chunk_give_the_same_row(program, chunked):
    """The decode lane as the engine calls it: handed the pool's arena of six slabs and
    the lane's block table. Off the chip that is to the bit what the padded cache gives."""
    extend, tokens, _, held, first = chunked
    lengths = jnp.full((1,), 24, jnp.int32)
    arena, table = _paged(held, 1e3)
    one, hidden, row, counters = extend(program, tokens[:, 24:], lengths, arena, table=table[:, :4])
    padded = extend(program, tokens[:, 24:], lengths, held)
    for got, want in zip((one, hidden, row, counters), padded):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    whole, _, rows, _ = extend(program, tokens, jnp.zeros((1,), jnp.int32), _cache(1, 64))
    np.testing.assert_allclose(np.asarray(one)[0, 0], np.asarray(whole)[0, 24], atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(row)[:, 0, 0], np.asarray(rows)[:, 0, 24], atol=2e-5)
    named, before = _named(counters), _named(first)
    assert (before["mla_queries"], named["mla_queries"]) == (SLABS * 24, SLABS)
    assert named["mla_pairs_absorbed"] == SLABS * 25
    assert named["mla_pairs_expanded"] == named["mla_rows_expanded"] == 0
    assert (before["moe_tokens"], named["moe_tokens"]) == (CFG.num_layers * 24, CFG.num_layers)
    # every pick of a real token is held here, held elsewhere, or zero-compute
    assert 0 < before["moe_zero_assignments"] < CFG.experts_per_token * before["moe_tokens"]
    assert before["moe_zero_assignments"] + before["moe_assignments"] < (
        CFG.experts_per_token * before["moe_tokens"])


@pytest.fixture
def on_the_chip(monkeypatch):
    """``extend`` as the chip traces it, on the CPU: ``backend.on_tpu`` answers yes, the
    chunk's attention kernel runs interpreted at small tiles, a decode call's runs
    interpreted over the pages it is handed, the grouped matmul is XLA's. Yields the
    shapes the chunk's kernel was called with and, as a pair whose first is ``"paged"``,
    those of the decode call's."""
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

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "grouped_matmul", lambda rows, w, sizes: jax.lax.ragged_dot(rows, w, sizes))
    monkeypatch.setattr(attention, "latent_attention", interpreted)
    monkeypatch.setattr(attention, "paged_attention", paged)
    jax.clear_caches()
    yield seen
    jax.clear_caches()


def test_on_the_chip_a_chunk_expands_and_a_decode_call_reads_the_pages(program, chunked, on_the_chip):
    """Under the chip's answer a chunk attends in ``latent_attention`` (the scaled latent
    it cached goes through ``W_kvb`` inside the kernel: the constant is in the row) and
    counts its pairs as expanded, both sub-blocks'; a decode call through the block table
    stays absorbed in ``paged_attention``, over the one arena of six slabs and no value
    arena; a table entry past the lane's pages names NaN and is never fetched."""
    extend, tokens, want, held, _ = chunked
    chip = CFG.make_extend_fn()
    got, _, rows, counters = chip(
        program, tokens[:, :24], jnp.zeros((1,), jnp.int32), _cache(1, 64))
    h = CFG.num_heads
    assert set(on_the_chip) == {(
        (1, 24, h, CFG.nope_dim), (1, 24, h, ROPE), (1, 64, ROW), (RANK, h, CFG.nope_dim),
        (RANK, h, CFG.v_dim))}
    on_the_chip.clear()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, rtol=5e-5)
    # the first slab's rows are made as off the chip, to the bit
    np.testing.assert_array_equal(np.asarray(rows)[0], np.asarray(held)[0, :, :24])
    np.testing.assert_allclose(np.asarray(rows), np.asarray(held)[:, :, :24], atol=5e-5, rtol=5e-5)
    named = _named(counters)
    assert named["mla_pairs_expanded"] == SLABS * 24 * 25 // 2 and named["mla_pairs_absorbed"] == 0
    assert named["mla_rows_expanded"] == SLABS * 24
    lengths = jnp.full((1,), 24, jnp.int32)
    off, _, row, counted = extend(program, tokens[:, 24:], lengths, held)
    arena, table = _paged(held, np.nan)
    one, _, own, counters = chip(program, tokens[:, 24:], lengths, arena, table=table)
    assert set(on_the_chip) == {("paged", (
        (1, 1, h, ROW), arena.shape, None, (1, 5), (1, ROW), (1, RANK)))}
    np.testing.assert_array_equal(np.asarray(own)[0], np.asarray(row)[0])
    assert _named(counters) == _named(counted)
    np.testing.assert_allclose(np.asarray(one), np.asarray(off), atol=3e-5, rtol=3e-5)


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


# -- the router and the zero-compute experts ------------------------------------------


def test_the_bias_chooses_the_weights_are_six_p_and_do_not_sum_to_six():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (200, 32))
    router = jax.random.normal(keys[1], (32, 24)) * 0.3
    bias = 0.02 * jax.random.normal(keys[2], (24,))
    plain_w, plain_e = moe.softmax_bias_top_k(h, router, jnp.zeros((24,)), 6, 6.0)
    w, e = moe.softmax_bias_top_k(h, router, bias, 6, 6.0)
    prob = np.asarray(jax.nn.softmax(jnp.dot(h, router, precision="highest"), -1))
    # the 6 largest of p + bias over all 24 outputs ...
    want = np.argsort(-(prob + np.asarray(bias)), axis=-1, kind="stable")[:, :6]
    assert np.array_equal(np.sort(np.asarray(e), -1), np.sort(want, -1))
    moved = (np.sort(np.asarray(e), -1) != np.sort(np.asarray(plain_e), -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95            # it changes some tokens' experts, not all
    # ... weighed 6 p each, whatever the others: not over their sum
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.take_along_axis(prob, np.asarray(e), -1), rtol=1e-6)
    sums = np.asarray(w).sum(-1)
    assert (sums < 6.0 - 1e-3).all() and np.ptp(sums) > 0.1
    # a choice the bias left alone keeps its weight, in a token it moved too
    by_expert = np.zeros((200, 24))
    np.put_along_axis(by_expert, np.asarray(e), np.asarray(w), -1)
    plain = np.zeros((200, 24))
    np.put_along_axis(plain, np.asarray(plain_e), np.asarray(plain_w), -1)
    both = (by_expert > 0) & (plain > 0)
    assert both[moved].any()
    np.testing.assert_allclose(by_expert[both], plain[both], rtol=1e-6)


def test_a_pick_on_a_zero_compute_expert_gives_the_token_back_and_sorts_into_no_group():
    """A token whose every pick is zero-compute, one with none and padding: the held
    experts' part is zeros for the first and counts none of its pairs, the zero part is
    the token under the picks' summed weight, and ``moe_zero_assignments`` counts the
    first token's six and no padding."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    d, f, held, routed = 16, 8, 4, 16
    x = jax.random.normal(keys[0], (3, d))
    wi = 0.3 * jax.random.normal(keys[1], (held, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[2], (held, f, d))
    experts = jnp.asarray([
        [16, 17, 18, 19, 20, 23],           # all zero-compute
        [4, 5, 6, 7, 0, 15],                # none: four held here (offset 4), two elsewhere
        [16, 4, 17, 5, 18, 6],              # padding
    ], jnp.int32)
    weights = jnp.asarray(np.random.default_rng(0).uniform(0.1, 0.5, (3, 6)), jnp.float32)
    valid = jnp.asarray([True, True, False])
    part, counters = moe.held_experts_ffn(x, weights, experts, valid, wi, wo, offset=4)
    zero, fell = moe.zero_experts_part(x, weights, experts, valid, routed)
    assert not np.asarray(part)[0].any() and not np.asarray(part)[2].any()
    assert np.asarray(counters).tolist() == [2, 4, 4, 1]
    np.testing.assert_allclose(
        np.asarray(zero)[0], float(weights[0].sum()) * np.asarray(x)[0], rtol=1e-6)
    assert not np.asarray(zero)[1:].any() and int(fell) == 6
    want = sum(
        float(weights[1, c]) * np.asarray(layers.gated_mlp(x[1], wi[c], wo[c])) for c in range(4))
    np.testing.assert_allclose(np.asarray(part)[1], want, atol=1e-5, rtol=1e-5)
    # the last share (experts 12..15): its offset + held reaches no zero-compute index
    last, counted = moe.held_experts_ffn(x, weights, experts, valid, wi, wo, offset=12)
    assert np.asarray(counted).tolist() == [2, 1, 1, 1] and not np.asarray(last)[0].any()


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Every share's held experts' part (16 routed experts, 4 a chip, every
    ``expert_offset``) and the zero-compute picks' part, counted **once**, is what the
    plain reference gives for the uncut layer: one "share" that holds all 16."""
    from benchmark.reference import longcat_flash_reference as ref

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    n, d, f, routed, zeros, k, held = 40, 32, 16, 16, 8, 6, 4
    x = jax.random.normal(keys[0], (n, d))
    router = 0.5 * jax.random.normal(keys[1], (d, routed + zeros))
    bias = 0.01 * jax.random.normal(keys[2], (routed + zeros,))
    wi = 0.3 * jax.random.normal(keys[3], (routed, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[4], (routed, f, d))
    weights, chosen = moe.softmax_bias_top_k(x, router, bias, k, 6.0)
    valid = jnp.ones((n,), bool)
    total, fell = moe.zero_experts_part(x, weights, chosen, valid, routed)
    pairs = int(fell)
    assert 0 < pairs < n * k
    for lo in range(0, routed, held):
        part, counters = moe.held_experts_ffn(
            x, weights, chosen, valid, wi[lo:lo + held], wo[lo:lo + held], offset=lo)
        total, pairs = total + part, pairs + int(counters[1])
    assert pairs == n * k                       # every pick is someone's, once
    want = ref._experts(
        x, {"router": router, "bias": bias, "wi": wi, "wo": wo},
        {"moe_topk": k, "routed_scaling_factor": 6.0, "zero_expert_num": zeros}, None)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # a share alone is not the layer, and the zero part counted in every share is not either
    assert np.abs(np.asarray(part) - np.asarray(want)).max() > 1e-2


def test_the_seeded_bias_moves_the_twelfth_choice_and_leaves_the_load_near_even():
    """At the published router's sizes (6,144 x 768, the 0.02 init, a normed input) a
    bias of spread ``bias_std`` 0.001 moves a pick of most tokens, and the busiest of the
    16 held experts stays within a few times its even share (12 / 768 of the tokens)."""
    cfg = longcat_flash.LongcatFlashConfig()
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    n = 2048
    u = jax.random.normal(keys[0], (n, cfg.embed_dim))
    router = 0.02 * jax.random.normal(keys[1], (cfg.embed_dim, cfg.router_experts))
    bias = cfg.bias_std * jax.random.normal(keys[2], (cfg.router_experts,))
    _, plain = moe.softmax_bias_top_k(u, router, jnp.zeros_like(bias), 12, 6.0)
    w, biased = moe.softmax_bias_top_k(u, router, bias, 12, 6.0)
    moved = (np.sort(np.asarray(biased), -1) != np.sort(np.asarray(plain), -1)).any(-1)
    assert 0.5 < moved.mean() < 1.0
    load = np.bincount(np.asarray(biased).reshape(-1), minlength=cfg.router_experts)
    even = n * 12 / cfg.router_experts
    assert load[:16].max() < 3 * even and load.max() < 4 * even
    # a third of the picks fall on zero-compute experts
    assert 0.28 < (np.asarray(biased) >= cfg.routed_experts).mean() < 0.39
    # the twelve hold about a quarter of the softmax's mass: their weights sum to
    # 6 x that, not to 6
    assert 1.0 < float(np.asarray(w).sum(-1).mean()) < 2.5


# -- the configuration -------------------------------------------------------------


def test_the_configuration_counts_its_parameters_and_states_what_a_token_holds():
    params = CFG.init_params(0)
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.num_params()
    bias = params["blocks"]["layers"]["moe"]["bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (CFG.num_layers, CFG.router_experts)
    assert float(jnp.std(bias)) == pytest.approx(CFG.bias_std, rel=0.3)
    # the published model whole, and one chip's four layers of it
    whole = longcat_flash.LongcatFlashConfig()
    assert whole.num_params() == 560_664_980_480
    assert (whole.q_scale, whole.kv_scale) == (2.0, pytest.approx(12 ** 0.5))
    assert (whole.routed_experts, whole.cache_layers) == (512, 56)
    share = longcat_flash.LongcatFlashConfig(vocab_size=16384, num_layers=4, num_experts=16)
    assert share.num_params() == 5_172_749_312
    assert share.cache_arrays == ((1, 640),) and share.cache_layers == 8
    with pytest.raises(ValueError, match="not among the 512 routed"):
        longcat_flash.LongcatFlashConfig(num_experts=16, expert_offset=500)
    with pytest.raises(ValueError, match="zero-compute experts among"):
        longcat_flash.LongcatFlashConfig(zero_experts=768)


# -- through the engine: a pool of more slabs than layers --------------------------------

_ENGINE = dict(
    num_blocks=24, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(32, 64))


def _ask(n, seed, new=12, **kw):
    prompt = [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]
    return {"prompt": prompt, "max_new_tokens": new, "return_logits": True, **kw}


def test_eight_slabs_of_four_layers_reuse_clone_evict_and_decode_through_the_table(program):
    """``cache_layers`` past ``num_layers`` through ``LLMServer``: the pool's one arena
    has a slab a sub-block; a repeated prompt reuses its blocks (all slabs of them) and
    decodes, through ``table=``, bitwise what it decoded uncached; a prompt that shares a
    prefix which ends inside a block clones that block; and after other prompts have
    evicted the first one's blocks it is prefilled again to the same bits."""
    cfg = longcat_flash.longcat_flash_nano(num_layers=4)
    params = cfg.init_params(2)
    server = llm.LLMServer(cfg, params=params, **_ENGINE)
    eng = server._engine
    assert eng._reads_pages and eng.pool.layers == cfg.cache_layers == 8 == 2 * cfg.num_layers
    assert [a.shape for a in eng.pool.arenas] == [(8, 24, 8, 1, cfg.row_dim)]
    ask = _ask(29, 1)
    first, again = server(dict(ask)), server(dict(ask))
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 24)
    assert again["tokens"] == first["tokens"] and np.array_equal(again["logits"], first["logits"])
    # the uncached logits are those of one whole pass over padded caches, no pool
    fed = jnp.asarray([ask["prompt"] + first["tokens"][:-1]], jnp.int32)
    whole, *_ = cfg.make_extend_fn()(
        params, fed, jnp.zeros((1,), jnp.int32), jnp.zeros((8, 1, 64, 1, cfg.row_dim), jnp.float32))
    np.testing.assert_allclose(first["logits"], np.asarray(whole)[0, 28:], atol=3e-5, rtol=3e-5)
    # a prefix that ends inside the third block: two blocks shared, the third cloned
    stats = server.kv_stats()
    fork = dict(ask, prompt=ask["prompt"][:20] + [7, 7, 7, 7, 7, 7])
    forked = server(fork)
    assert forked["prefix_cached_tokens"] >= 16
    alone = llm.LLMServer(cfg, params=params, **_ENGINE)(dict(fork))
    assert forked["tokens"] == alone["tokens"] and np.array_equal(forked["logits"], alone["logits"])
    calls = server.kv_stats()["calls"]["decode"]
    assert calls["paged"] == calls["n"] > 0
    # fill the pool with other prompts until the first one's blocks are gone
    for seed in range(10, 16):
        server(_ask(40, seed, new=4))
    after = server.kv_stats()
    assert after["kv_blocks_in_use"] == after["prefix_cached_blocks"] <= 24
    once_more = server(dict(ask))
    assert once_more["prefix_cached_tokens"] < 24
    assert once_more["tokens"] == first["tokens"]
    assert np.array_equal(once_more["logits"], first["logits"])
    assert after["moe_zero_assignments"] > stats["moe_zero_assignments"] > 0
