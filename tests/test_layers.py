"""``models/layers.py``: each shared piece once against its formula in plain
``numpy`` / ``jax.numpy`` float32, and the rule that keeps it the one home: an
architecture file imports from the kit and from ``moe.py``, never from a sibling."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers

MODELS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ray_tpu", "models")
ARCHITECTURES = ("cohere2_moe", "keye_vl2", "kimi_k2", "granitemoehybrid", "lfm2_moe")


def _random(seed, shape, dtype=np.float32):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


def test_rms_norm_is_float32_over_the_last_axis():
    x = jnp.asarray(_random(0, (2, 5, 16)), jnp.bfloat16)
    scale = jnp.asarray(_random(1, (16,)), jnp.bfloat16)
    xf, sf = np.asarray(x, np.float32), np.asarray(scale, np.float32)
    want = xf / np.sqrt((xf * xf).mean(-1, keepdims=True) + 1e-5) * sf
    got = layers.rms_norm(x, scale, 1e-5)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_layer_norm_subtracts_the_mean_and_has_no_bias():
    x, scale = _random(2, (3, 4, 32)) + 3.0, _random(3, (32,))
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * scale
    got = layers.layer_norm(jnp.asarray(x), jnp.asarray(scale), 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    plain = layers.layer_norm(jnp.asarray(x), jnp.ones(32), 1e-5)
    assert abs(float(np.asarray(plain).mean())) < 1e-5


def _rotated(x, positions, rotary_dim, freqs):
    """Feature ``i`` turned with ``i + rotary_dim / 2`` by ``position * freqs[i]``."""
    half = rotary_dim // 2
    angles = positions[:, :, None, None] * freqs[None, None, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return np.concatenate([
        a * np.cos(angles) - b * np.sin(angles), b * np.cos(angles) + a * np.sin(angles),
        x[..., rotary_dim:]], -1)


def test_rotary_with_a_base_turns_the_first_features_and_keeps_the_rest():
    x = _random(4, (2, 6, 3, 16))
    positions = np.array([[0, 1, 2, 3, 4, 5], [7, 8, 9, 10, 11, 12]], np.int32)
    freqs = 1.0 / 50000.0 ** (np.arange(4, dtype=np.float32) / 4)
    got = layers.rotary(jnp.asarray(x), jnp.asarray(positions), 8, 50000.0)
    np.testing.assert_allclose(
        np.asarray(got), _rotated(x, positions.astype(np.float32), 8, freqs), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(np.asarray(got)[0, 0], x[0, 0])       # position 0 turns nothing


def test_rotary_with_given_frequencies_ignores_the_base():
    x = _random(5, (1, 4, 2, 8))
    positions = np.array([[3, 4, 5, 6]], np.int32)
    freqs = np.array([1.0, 0.3, 0.01, 0.002], np.float32)
    got = layers.rotary(
        jnp.asarray(x), jnp.asarray(positions), 8, base=7.0, freqs=jnp.asarray(freqs))
    np.testing.assert_allclose(
        np.asarray(got), _rotated(x, positions.astype(np.float32), 8, freqs), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tc, blocks", [(64, [32, 32]), (40, [40]), (1, [1])])
def test_by_query_block_goes_block_by_block_and_a_ragged_chunk_as_one(tc, blocks):
    assert layers.query_block(tc) == blocks[0]
    q, w = _random(6, (2, tc, 3, 4)), _random(7, (2, tc))
    seen = []

    def fn(qb, wb):
        seen.append(qb.shape[1])
        return qb * wb[:, :, None, None] + 1.0

    got = layers.by_query_block(fn, jnp.asarray(q), jnp.asarray(w))
    assert set(seen) == set(blocks)     # one size of block, whatever the chunk
    np.testing.assert_allclose(np.asarray(got), q * w[:, :, None, None] + 1.0, rtol=1e-5)


@pytest.mark.parametrize("groups", [1, 4])
def test_plain_attend_is_a_dense_softmax_under_the_mask(groups):
    b, n, kv, hd, cache, scale = 2, 5, 2, 8, 12, 0.25
    q = _random(8, (b, n, kv, groups, hd))
    k, v = _random(9, (b, cache, kv, hd)), _random(10, (b, cache, kv, hd))
    mask = np.random.default_rng(11).random((b, n, cache)) < 0.5
    mask[..., 0] = True                 # every query reads something
    got = layers.plain_attend(*map(jnp.asarray, (q, k, v, mask)), scale)
    assert got.shape == (b, n, kv, groups, hd)
    for h in range(kv):
        for g in range(groups):
            logit = np.einsum("bqd,bkd->bqk", q[:, :, h, g], k[:, :, h]) * scale
            logit = np.where(mask, logit, -np.inf)
            weight = np.exp(logit - logit.max(-1, keepdims=True))
            weight /= weight.sum(-1, keepdims=True)
            np.testing.assert_allclose(
                np.asarray(got)[:, :, h, g], np.einsum("bqk,bkd->bqd", weight, v[:, :, h]),
                rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("on_the_chip", [False, True], ids=["off-the-chip", "kernel-interpreted"])
def test_paged_attend_is_plain_attend_over_the_tables_pages_and_the_own_row(
        on_the_chip, monkeypatch):
    """A decode call's attend through the block table against ``plain_attend`` over
    caches gathered by hand (the table's pages of the layer side by side, the own row
    written at the lane's length): off the chip the same bits, on it (the kernel,
    interpreted here) the same numbers; a table entry past a lane's pages is followed
    by neither."""
    from ray_tpu.ops import attention, backend

    b, kv, groups, hd, vd, block, n, blocks, at = 3, 2, 4, 24, 16, 8, 4, 10, 1
    k_pages = jnp.asarray(_random(0, (2, blocks, block, 1, kv * hd)))
    v_pages = jnp.asarray(_random(1, (2, blocks, block, 1, kv * vd)))
    q = jnp.asarray(_random(2, (b, 1, kv, groups, hd)))
    k, v = jnp.asarray(_random(3, (b, 1, 1, kv * hd))), jnp.asarray(_random(4, (b, 1, 1, kv * vd)))
    lengths = np.array([11, 0, 31], np.int32)
    table = np.array([[4, 7, 0, 0], [0, 0, 0, 0], [2, 9, 1, 5]], np.int32)
    positions = jnp.asarray(lengths)[:, None]
    visible = layers.visible_keys(positions, jnp.ones((b, 1), bool), n * block)
    if on_the_chip:
        real = attention.paged_attention
        monkeypatch.setattr(backend, "on_tpu", lambda: True)
        monkeypatch.setattr(
            attention, "paged_attention", lambda *a, **kw: real(*a, interpret=True, **kw))
    got = layers.paged_attend(
        q, k, v, k_pages, v_pages, jnp.int32(at), jnp.asarray(table), positions, visible, 0.2)

    def gathered(pages, own, width):
        rows = pages[at][table].reshape(b, n * block, 1, -1)
        return layers.write_rows(rows, jnp.arange(b)[:, None], positions, own).reshape(
            b, n * block, kv, width)

    want = layers.plain_attend(q, gathered(k_pages, k, hd), gathered(v_pages, v, vd), visible, 0.2)
    assert got.shape == want.shape == (b, 1, kv, groups, vd)
    if on_the_chip:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    else:
        assert np.array_equal(got, want)


def test_cache_slots_are_the_padded_caches_or_the_tables_pages():
    assert layers.cache_slots(jnp.zeros((2, 3, 64, 1, 8))) == 64
    assert layers.cache_slots(jnp.zeros((2, 10, 8, 1, 8)), jnp.zeros((3, 4), jnp.int32)) == 32


def test_write_rows_drops_a_position_past_the_capacity():
    cache = jnp.zeros((2, 4, 3))
    rows = jnp.asarray(_random(12, (2, 2, 3)))
    positions = jnp.array([[1, 2], [3, 4]], jnp.int32)      # lane 1's second row has no slot
    got = np.asarray(layers.write_rows(cache, jnp.arange(2)[:, None], positions, rows))
    want = np.zeros((2, 4, 3), np.float32)
    want[0, 1], want[0, 2], want[1, 3] = rows[0, 0], rows[0, 1], rows[1, 0]
    np.testing.assert_array_equal(got, want)                # slot 3 is not overwritten by row 4


def test_frame_and_what_a_query_may_read_with_padded_lanes():
    tokens = jnp.array([[5, 6, -1], [-1, -1, -1], [7, 8, 9]], jnp.int32)
    positions, valid = layers.frame(tokens, jnp.array([2, 0, 4], jnp.int32))
    np.testing.assert_array_equal(np.asarray(positions), [[2, 3, 4], [0, 1, 2], [4, 5, 6]])
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(tokens) >= 0)
    visible = np.asarray(layers.visible_keys(positions, valid, 6))
    assert visible.shape == (3, 3, 6)
    np.testing.assert_array_equal(visible[0, 0], [1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(visible[0, 1], [1, 1, 1, 1, 0, 0])
    assert not visible[0, 2].any() and not visible[1].any()         # padding reads nothing
    assert visible[2, 2].all()                                      # position 6 of a cache of 6
    np.testing.assert_array_equal(np.asarray(layers.live_keys(positions, valid)), [4, 0, 7])


def test_look_up_reads_a_row_for_padding_and_for_an_id_past_the_table():
    table = jnp.asarray(_random(13, (10, 4)))
    got = np.asarray(layers.look_up(table, jnp.array([[3, -1, 12]], jnp.int32)))
    np.testing.assert_array_equal(got[0], np.asarray(table)[[3, 0, 9]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_seeded_draw_is_normal_times_002_with_the_keys_in_the_shapes_order(dtype):
    shapes = {"wte": (8, 4), "q": (2, 4, 2, 3), "router": (2, 4, 6)}
    keys = jax.random.split(jax.random.PRNGKey(7), len(shapes))
    got = jax.jit(lambda keys: layers.drawn(keys, shapes, dtype))(keys)
    assert set(got) == set(shapes)
    for key, (name, shape) in zip(keys, shapes.items()):
        want = jax.jit(lambda k: jax.random.normal(k, shape, dtype) * jnp.asarray(0.02, dtype))(key)
        assert got[name].dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got[name], np.float32), np.asarray(want, np.float32))
    ones = layers.ones_scale(dtype, 3, 5)
    assert list(ones) == ["scale"] and ones["scale"].dtype == dtype
    np.testing.assert_array_equal(np.asarray(ones["scale"], np.float32), np.ones((3, 5)))


def test_gated_mlp_multiplies_the_silu_of_the_gate_by_the_up_projection():
    x, wi, wo = _random(14, (2, 3, 8)), _random(15, (8, 24)), _random(16, (12, 8))
    gate, up = (x @ wi)[..., :12], (x @ wi)[..., 12:]
    want = (gate / (1.0 + np.exp(-gate)) * up) @ wo
    got = layers.gated_mlp(jnp.asarray(x), jnp.asarray(wi), jnp.asarray(wo))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    low = layers.gated_mlp(jnp.asarray(x, jnp.bfloat16), jnp.asarray(wi), jnp.asarray(wo))
    assert low.dtype == jnp.float32                         # summed and handed back in float32


def test_rms_head_norms_then_multiplies_under_the_logits_scope():
    x, scale, kernel = _random(17, (2, 3, 8)), _random(18, (8,)), _random(19, (8, 11))
    logits, hidden = layers.rms_head(
        *map(jnp.asarray, (x, scale)), 1e-6, jnp.asarray(kernel), jnp.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale
    np.testing.assert_allclose(np.asarray(hidden), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), want @ kernel, rtol=1e-4, atol=1e-5)
    traced = jax.make_jaxpr(lambda x: layers.rms_head(x, scale, 1e-6, kernel, jnp.float32))(x)
    assert all("extend.logits" in str(eqn.source_info.name_stack) for eqn in traced.eqns)


def test_without_experts_splits_the_scanned_tree_and_leaves_it_as_it_was():
    stacked = {"ln": 1, "attn": 2, "moe": {"router": 3, "bias": 4, "wi": 5, "wo": 6}}
    scanned, routing, experts = layers.without_experts(stacked)
    assert scanned == {"ln": 1, "attn": 2}
    assert routing == {"router": 3, "bias": 4} and experts == {"wi": 5, "wo": 6}
    assert stacked["moe"] == {"router": 3, "bias": 4, "wi": 5, "wo": 6}


def test_the_kit_has_no_jit_of_its_own():
    with open(os.path.join(MODELS, "layers.py")) as f:
        tree = ast.parse(f.read())
    assert not [
        node for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and node.attr in ("jit", "pjit")]


@pytest.mark.parametrize("name", ARCHITECTURES)
def test_an_architecture_imports_from_the_kit_and_from_no_sibling(name):
    """``gpt.TrainModel`` is the trainer's contract, not a helper: the one name a
    file may take from ``gpt.py``. And it types none of the kit's decisions again."""
    with open(os.path.join(MODELS, name + ".py")) as f:
        source = f.read()
    taken = {
        (node.module, alias.name)
        for node in ast.walk(ast.parse(source)) if isinstance(node, ast.ImportFrom)
        for alias in node.names if (node.module or "").startswith("ray_tpu.models")}
    assert taken <= {
        ("ray_tpu.models", "layers"), ("ray_tpu.models", "moe"),
        ("ray_tpu.models.gpt", "TrainModel")}
    assert ("ray_tpu.models", "layers") in taken
    for typed_again in (
            'mode="drop"', "QUERY_BLOCK =", "def _by_block", "def _rms", "def _ln", "-1e30"):
        assert typed_again not in source
