"""``models/granitemoehybrid.py`` with routed experts (granite-4.0-h-small's
block) on the CPU at a tiny size: the state store and the dropless expert layer
in one ``extend``. Two periods, 8 routed experts of which 4 are held, 3 a token,
a sliced vocabulary; a prompt in chunks and decode through ``LLMEngine`` against
the plain reference's full forward pass (logits), one chunk against several, a
snapshot restored, the two halves' shares against the uncut layer, the counters
of both families against a hand-worked request, the configuration's own
arithmetic, and the block without experts left as it was. float32 throughout."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granitemoehybrid as hybrid, moe
from ray_tpu.serve import batching, llm

CFG = hybrid.granite_hybrid_nano(router_experts=8, vocab_size=128)
ENGINE = dict(
    num_blocks=48, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(64, 128), state_slots=14)


def _keys(cfg):
    return dict(
        num_hidden_layers=cfg.num_layers, layer_period=cfg.period,
        attention_layer_offset=cfg.attention_at, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.kv_heads, hidden_size=cfg.embed_dim, mamba_n_heads=cfg.ssm_heads,
        mamba_d_state=cfg.ssm_state, rms_norm_eps=cfg.norm_eps,
        embedding_multiplier=cfg.embedding_multiplier, residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier, logits_scaling=cfg.logits_scaling,
        num_experts_per_tok=cfg.experts_per_token, expert_offset=cfg.expert_offset)


def _scaled(params):
    # the init's 0.02 would leave every logit near 0 and every router weight
    # near 1 / k: make the projections and the router matter
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0
        if path[-1].key in ("kernel", "wi", "wo", "embedding", "router")
        and "conv" not in [getattr(k, "key", None) for k in path] else a, params)


@pytest.fixture(scope="module")
def program():
    return _scaled(CFG.init_params(5))


@pytest.fixture(scope="module")
def engine(program):
    return llm.LLMEngine(CFG, program, **ENGINE)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def _served(eng, seed, n, new):
    s = batching._Sequence(
        {"prompt": _prompt(seed, n), "max_new_tokens": new, "return_logits": True})
    steps = 0
    while not s.done:
        eng.step([s])
        steps += 1
        assert steps < 400
    assert s._error is None, s._error
    return s._result


def _empty_engine(eng):
    with eng.pool._lock:
        while eng.prefix._evict_snapshot():
            pass
    assert eng.pool.in_use() == 0 and eng.pool.slots_in_use() == 0


def _reference(program, cfg, fed, last, wrong=None):
    from benchmark.reference import granitemoehybrid_moe_reference as ref

    return np.asarray(ref.program_logits(program, fed, _keys(cfg), last, wrong))


def _zeros(cfg, lanes, cap, slots=2):
    caches = tuple(
        jnp.zeros((cfg.cache_layers, lanes, cap) + each, jnp.float32)
        for each in cfg.cache_arrays)
    arenas = tuple(
        jnp.zeros((layers, slots) + shape, dtype) for layers, shape, dtype in cfg.state_arrays)
    return caches + arenas


def _ints(*values):
    return jnp.asarray(values, jnp.int32)


def test_the_configuration_counts_what_the_published_cut_has():
    """granite-4.0-h-small's served share: one period, 36 of 72 experts, half
    the vocabulary (``ISSUE.md`` 47's table), and the whole model's 32 B."""
    small = dict(
        embed_dim=4096, mlp_dim=1536, expert_dim=768, router_experts=72, experts_per_token=10,
        head_dim=128, ssm_heads=128)
    cut = hybrid.GraniteMoeHybridConfig(num_layers=10, vocab_size=50176, num_experts=36, **small)
    assert cut.num_params() == 4_757_211_776
    assert cut.counters == hybrid.SSM_COUNTERS + moe.COUNTERS
    assert cut.cache_layers == 1 and cut.cache_arrays == ((1, 1024), (1, 1024))
    (layers, state, dtype), (_, tail, _) = cut.state_arrays
    assert (layers, state, tail) == (9, (128, 64, 128), (3, 8448)) and dtype == jnp.float32
    assert 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 38_204_928
    whole = hybrid.GraniteMoeHybridConfig(num_experts=72, **small)
    assert 32.0e9 < whole.num_params() < 32.5e9
    assert sum(a.size for a in jax.tree.leaves(CFG.init_params(0))) == CFG.num_params()
    assert hybrid.GraniteMoeHybridConfig().counters == hybrid.SSM_COUNTERS
    with pytest.raises(ValueError, match="not among the 8 the router scores"):
        hybrid.granite_hybrid_nano(router_experts=8, expert_offset=5)


def test_chunked_prefill_then_decode_is_the_references_full_forward(program, engine):
    """A prompt of 45 tokens over three chunks of 16 through the state slots
    and the expert layers, then 8 decode steps through the cache: logits."""
    _empty_engine(engine)
    prompt = _prompt(7, 45)
    out = _served(engine, 7, 45, 8)
    want = _reference(program, CFG, prompt + out["tokens"][:-1], 8)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    np.testing.assert_allclose(out["logits"], want, rtol=3e-4, atol=3e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    assert out["prefix_cached_tokens"] == 0


@pytest.mark.parametrize(
    "wrong", ["no_shared", "no_residual_multiplier", "uniform_weights", "wrong_offset"])
def test_the_reference_without_a_mechanism_is_another_model(program, engine, wrong):
    _empty_engine(engine)
    prompt = _prompt(7, 45)
    out = _served(engine, 7, 45, 8)
    other = _reference(program, CFG, prompt + out["tokens"][:-1], 8, wrong)
    assert np.abs(out["logits"] - other).max() > 30 * 3e-4


def test_a_prompt_in_one_chunk_and_in_several_gives_the_same_bits(program, engine):
    """48 tokens as three chunks of 16 through the engine, and as three calls
    of 16 by hand against one call of 48: a sub-chunk's result, and a token's
    experts, do not depend on where in a call they lie."""
    extend = CFG.make_extend_fn()
    prompt = _prompt(8, 48)
    whole = extend(
        program, jnp.asarray([prompt], jnp.int32), _ints(0), *_zeros(CFG, 1, 64), _ints(1),
        _ints(0), _ints(0))
    state = _zeros(CFG, 1, 64)
    for at in range(0, 48, 16):
        logits, _, k, v, ssm, conv, _ = extend(
            program, jnp.asarray([prompt[at:at + 16]], jnp.int32), _ints(at), *state, _ints(1),
            _ints(0), _ints(0))
        caches = tuple(
            c.at[:, :, at:at + 16].set(new) for c, new in zip(state[:2], (k, v)))
        state = caches + (ssm, conv)
    np.testing.assert_array_equal(np.asarray(logits[0]), np.asarray(whole[0][0, 32:]))
    np.testing.assert_array_equal(np.asarray(ssm[:, 1]), np.asarray(whole[4][:, 1]))
    _empty_engine(engine)
    out = _served(engine, 8, 48, 1)
    np.testing.assert_array_equal(out["logits"][0], np.asarray(whole[0][0, 47]))


@pytest.mark.parametrize("n,reused", [(45, 40), (33, 32)], ids=["mid", "end"])
def test_a_snapshot_restored_gives_bitwise_the_uncached_logits(engine, n, reused):
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


def test_the_two_halves_shares_add_up_to_the_uncut_layer(program):
    """The routed parts the two chips of the pair give (offsets 0 and 4), with
    the shared MLP counted once, sum to the whole layer: computed by the
    program, share by share, and by the reference given all eight experts."""
    from benchmark.reference import granitemoehybrid_moe_reference as ref

    rng = np.random.default_rng(3)
    n = jnp.asarray(rng.normal(size=(24, CFG.embed_dim)), jnp.float32)
    mlp = jax.tree.map(lambda a: a[0], program["periods"]["mlp"][1])
    held = jax.tree.map(lambda a: a[0], program["experts"][1])
    other = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.12, jnp.float32), held)
    valid = jnp.ones((24,), bool)
    weights, chosen = moe.softmax_top_k(n, mlp["router"], CFG.experts_per_token)
    parts = [
        moe.held_experts_ffn(n, weights, chosen, valid, half["wi"], half["wo"], offset)
        for half, offset in ((held, 0), (other, 4))]
    assert sum(int(counted[1]) for _, counted in parts) == 24 * CFG.experts_per_token
    whole = {k: jnp.concatenate([held[k], other[k]]) for k in ("wi", "wo")}
    keys = {**_keys(CFG), "expert_offset": 0}
    want = ref._second_half(n, mlp, whole, keys, None)
    shared = ref.block._mlp(n, mlp, False)
    np.testing.assert_allclose(
        np.asarray(parts[0][0] + parts[1][0] + shared), np.asarray(want), rtol=2e-4, atol=2e-5)
    # ... and each share alone is what the reference given that share computes
    for (y, _), half, offset in zip(parts, (held, other), (0, 4)):
        np.testing.assert_allclose(
            np.asarray(y + shared),
            np.asarray(ref._second_half(n, mlp, half, {**keys, "expert_offset": offset}, None)),
            rtol=2e-4, atol=2e-5)


def test_the_published_order_of_the_router_is_the_programs():
    """Top-k of the logits and a softmax over the chosen (the reference, the
    published gating) against a softmax over all and its largest over their sum
    (``moe.softmax_top_k``)."""
    from benchmark.reference import granitemoehybrid_moe_reference as ref

    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 72)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_w, want_e = ref.route(n, router, 10)
    got_w, got_e = moe.softmax_top_k(n, router, 10)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=1e-5, atol=1e-7)
    assert float(np.asarray(want_w).std()) > 0.05               # nowhere near uniform


def test_the_counters_count_what_a_hand_worked_request_says(program, engine):
    """19 prompt tokens in two chunks (16 + 3) and 4 decode steps: 6 calls.
    ``ssm_tokens`` and ``ssm_state_passes`` over the 6 Mamba layers, the four
    ``moe_*`` over the 8 expert layers; the pairs held against the reference's
    own routing of the same tokens."""
    from benchmark.reference import granitemoehybrid_moe_reference as ref

    _empty_engine(engine)
    before = engine.stats()
    out = _served(engine, 31, 19, 5)
    after = engine.stats()
    delta = {k: after[k] - before[k] for k in CFG.counters}
    tokens = 19 + 4                                             # the last token is not fed
    assert delta["ssm_tokens"] == tokens * CFG.ssm_layers
    assert delta["ssm_state_passes"] == 6 * CFG.ssm_layers
    assert delta["moe_tokens"] == tokens * CFG.num_layers
    # the reference routes the same tokens: every choice that falls on experts 0..3
    fed = _prompt(31, 19) + out["tokens"][:-1]
    keys = _keys(CFG)
    x = keys["embedding_multiplier"] * program["wte"]["embedding"][jnp.asarray(fed)]
    pairs, calls = 0, [range(0, 16), range(16, 19)] + [range(t, t + 1) for t in range(19, 23)]
    hit = busiest = 0
    for layer in range(CFG.num_layers):
        at, i = divmod(layer, CFG.period)
        if i == CFG.attention_at:
            p = ref.block._layer_of(program["periods"]["attn"], at)
            mixed = ref.block._attention(
                ref.block._norm(x, p["ln"]["scale"], CFG.norm_eps), p, CFG.num_heads,
                CFG.kv_heads, float(CFG.attention_multiplier), False)
        else:
            p = ref.block._layer_of(program["periods"]["mamba"][i - (i > CFG.attention_at)], at)
            mixed = ref.block._mamba(
                ref.block._norm(x, p["ln"]["scale"], CFG.norm_eps), p, CFG.ssm_heads,
                CFG.ssm_state, CFG.norm_eps, None)
        x = x + CFG.residual_multiplier * mixed
        mlp = ref.block._layer_of(program["periods"]["mlp"][i], at)
        n = ref.block._norm(x, mlp["ln"]["scale"], CFG.norm_eps)
        _, chosen = ref._route(n, mlp["router"], CFG.experts_per_token, None, False)
        chosen = np.asarray(chosen)
        held = chosen < CFG.num_experts
        pairs += int(held.sum())
        for call in calls:
            load = np.bincount(chosen[list(call)][held[list(call)]], minlength=CFG.num_experts)
            hit, busiest = hit + int((load > 0).sum()), busiest + int(load.max())
        x = x + CFG.residual_multiplier * ref._second_half(
            n, mlp, ref.block._layer_of(program["experts"][i], at), keys, None)
    assert delta["moe_assignments"] == pairs
    assert delta["moe_experts_hit"] == hit and delta["moe_load_max"] == busiest
    assert 0.3 < pairs / (tokens * CFG.num_layers * CFG.experts_per_token) < 0.7


def test_padding_computes_no_expert_and_is_not_counted(program):
    extend = CFG.make_extend_fn()
    tokens = jnp.asarray([_prompt(1, 11) + [-1] * 5, [-1] * 16], jnp.int32)
    *_, counters = extend(
        program, tokens, _ints(0, 0), *_zeros(CFG, 2, 64), _ints(1, 0), _ints(0, 0), _ints(0, 0))
    counted = dict(zip(CFG.counters, np.asarray(counters).tolist()))
    assert counted["ssm_tokens"] == 11 * CFG.ssm_layers
    assert counted["ssm_state_passes"] == CFG.ssm_layers
    assert counted["moe_tokens"] == 11 * CFG.num_layers
    assert counted["moe_assignments"] <= 11 * CFG.num_layers * CFG.experts_per_token


def test_the_block_without_experts_lowers_as_it_did():
    """``router_experts == 0`` is micro's block: no router, no expert and no
    expert counter in its parameters, its outputs or its lowered text."""
    plain = hybrid.granite_hybrid_nano()
    assert plain.counters == hybrid.SSM_COUNTERS
    params = jax.eval_shape(lambda: plain.init_params(0))
    assert "experts" not in params and set(params["periods"]["mlp"][0]) == {"ln", "wi", "wo"}
    extend = plain.make_extend_fn()
    state = tuple(jax.eval_shape(lambda: _zeros(plain, 1, 64)))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    text = extend.lower(params, tokens, one, *state, one, one, one).as_text(debug_info=True)
    assert "extend.moe" not in text and "extend.mlp" in text
    out = jax.eval_shape(extend, params, tokens, one, *state, one, one, one)
    assert out[-1].shape == (2,)
    with_experts = CFG.make_extend_fn().lower(
        jax.eval_shape(lambda: CFG.init_params(0)), tokens, one,
        *jax.eval_shape(lambda: _zeros(CFG, 1, 64)), one, one, one).as_text(debug_info=True)
    for scope in ("extend.moe.route", "extend.moe.experts", "extend.moe.shared"):
        assert scope in with_experts
    assert "extend.mlp" not in with_experts
    assert dataclasses.replace(CFG, router_experts=0, num_experts=0).counters == hybrid.SSM_COUNTERS
