"""Flash-attention kernel vs XLA reference (pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers
from ray_tpu.ops.attention import (
    _attention_xla,
    _flash_attention_tpu,
    dot_product_attention,
    flash_attention,
    paged_attention,
)


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _flash(q, k, v, causal, bq=64, bk=64):
    d = q.shape[-1]
    out, _ = _flash_attention_tpu(
        q, k, v, causal=causal, scale=1.0 / d**0.5,
        block_q=bq, block_k=bk, interpret=True,
    )
    return out


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = (_rand((2, 2, 128, 128), s) for s in (0, 1, 2))
    ref = _attention_xla(q, k, v, causal=causal)
    out = _flash(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_flash_chunked_prefill_offset():
    # q shorter than kv: q rows are the suffix of the context
    q = _rand((1, 2, 64, 128), 0)
    k, v = (_rand((1, 2, 256, 128), s) for s in (1, 2))
    ref = _attention_xla(q, k, v, causal=True)
    out = _flash(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_flash_ragged_kv_noncausal():
    # kv not a multiple of block_k: padded columns must not leak
    q = _rand((1, 1, 64, 128), 0)
    k, v = (_rand((1, 1, 72, 128), s) for s in (1, 2))
    ref = _attention_xla(q, k, v, causal=False)
    out = _flash(q, k, v, causal=False, bk=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_grad_flows_through_dispatcher():
    q, k, v = (_rand((1, 2, 64, 64), s) for s in (0, 1, 2))
    g = jax.grad(lambda q: dot_product_attention(q, k, v, causal=True).sum())(q)
    assert g.shape == q.shape and bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 2, 128, 128), (1, 2, 192, 128)])
def test_flash_backward_matches_xla(causal, shape):
    """Pallas dq/dk/dv kernels (interpret mode) vs the XLA vjp."""
    q, k, v = (_rand(shape, s) for s in (0, 1, 2))
    scale = 1.0 / q.shape[-1] ** 0.5

    def loss_ref(q, k, v):
        o = _attention_xla(q, k, v, causal=causal, scale=scale)
        return jnp.sum(o * jnp.cos(o))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, scale, 64, 64, True)
        return jnp.sum(o * jnp.cos(o))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3, err_msg=f"d{name}")


def test_flash_backward_q_longer_than_kv():
    # causal with t_q > t_kv: leading q rows attend to nothing; their lse is
    # the NEG_INF sentinel and must not leak p=1 into the backward. The XLA
    # reference's softmax returns uniform probs for such rows (finite
    # NEG_INF), so compare under a cotangent that zeroes the empty rows —
    # there the two conventions' gradients provably agree.
    q = _rand((1, 1, 160, 128), 0)
    k, v = (_rand((1, 1, 64, 128), s) for s in (1, 2))
    scale = 1.0 / 128**0.5
    w = (jnp.arange(160) >= 160 - 64).astype(jnp.float32)[None, None, :, None]
    g_ref = jax.grad(
        lambda a: (_attention_xla(*a, causal=True, scale=scale) * w).sum(), 0
    )((q, k, v))
    g_out = jax.grad(
        lambda a: (flash_attention(*a, True, scale, 64, 64, True) * w).sum(), 0
    )((q, k, v))
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3, err_msg=f"d{name}")
    # and the flash-convention grads must at least be finite with a full
    # cotangent (the p=1 leak produced O(10) garbage here)
    g_full = jax.grad(lambda a: flash_attention(*a, True, scale, 64, 64, True).sum(), 0)(
        (q, k, v)
    )
    for g in g_full:
        assert bool(jnp.isfinite(g).all())


def test_flash_backward_ragged_q_blocks():
    # q_len not a multiple of block_q: padded rows must not poison dk/dv
    q = _rand((1, 1, 96, 128), 0)
    k, v = (_rand((1, 1, 96, 128), s) for s in (1, 2))
    scale = 1.0 / 128**0.5
    g_ref = jax.grad(
        lambda k: _attention_xla(q, k, v, causal=True, scale=scale).sum(), 0
    )(k)
    g_out = jax.grad(
        lambda k: flash_attention(q, k, v, True, scale, 64, 64, True).sum(), 0
    )(k)
    np.testing.assert_allclose(g_out, g_ref, atol=5e-5, rtol=1e-3)


# -- a decode lane over its pages, where the pool keeps them -------------------


#: (K/V heads, query heads a K/V head, key width, value width): the adopting
#: configurations' full-attention layers at their published head shapes
PAGED_SHAPES = {
    "mimo-v2-flash": (4, 16, 192, 128),     # keys no whole number of the chip's 128 lanes
    "qwen3-next": (2, 8, 256, 256),
    "granite-4h-micro": (8, 4, 64, 64),      # a head of half a lane tile
    "granite-4h-small": (8, 4, 128, 128),
    # a latent row: the key and, in its first 512 features, the value (no value arena)
    "kimi-k2": (1, 64, 640, 512),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(PAGED_SHAPES))
def test_paged_attention_reads_a_lanes_live_pages_and_its_own_row(shape, dtype):
    """``paged_attention`` (interpreted) against ``layers.plain_attend`` over the same
    rows laid side by side with the own row written among them, in one call of five
    lanes over layer 1 of two: a lane that ends mid-page, a padded lane of length 0
    (its own row alone), a lane that fills every page but one slot, a lane of exactly
    one page, and a lane that shares its first page with lane 0 (a cached prefix). Every
    table entry past a lane's live pages names a block of NaN, as does all of layer 0:
    the result is finite and equal, so they are neither fetched into the sum nor read.
    Slots past a lane's length inside its last page hold other rows' finite values,
    which the mask weighs 0. Kimi's shape has no value arena: the kernel is handed the
    one arena once and sums the first ``dv`` features of the rows it scored."""
    kv, groups, d, dv = PAGED_SHAPES[shape]
    latent = shape == "kimi-k2"
    block, n, blocks, lanes, at = 16, 4, 12, 5, 1
    rng = np.random.default_rng(61)
    nan = blocks - 1
    k_pages = rng.normal(size=(2, blocks, block, 1, kv * d)).astype(np.float32)
    v_pages = rng.normal(size=(2, blocks, block, 1, kv * dv)).astype(np.float32)
    for pages in (k_pages, v_pages):
        pages[0], pages[:, nan] = np.nan, np.nan
    lengths = np.array([2 * block + 5, 0, n * block - 1, block, block + 3], np.int32)
    table = np.full((lanes, n), nan, np.int32)
    table[0, :3], table[2], table[3, :1], table[4, :2] = [3, 1, 7], [9, 2, 5, 6], [4], [3, 8]
    q = jnp.asarray(rng.normal(size=(lanes, kv, groups, d)), dtype)
    k_own = jnp.asarray(rng.normal(size=(lanes, kv * d)), dtype)
    v_own = jnp.asarray(rng.normal(size=(lanes, kv * dv)), dtype)
    k_pages, v_pages = jnp.asarray(k_pages, dtype), jnp.asarray(v_pages, dtype)
    if latent:
        v_pages, v_own = k_pages[..., :dv], k_own[:, :dv]
    out = paged_attention(
        q, k_pages, None if latent else v_pages, jnp.int32(at), jnp.asarray(table),
        jnp.asarray(lengths), k_own, v_own, interpret=True)
    assert out.shape == (lanes, kv, groups, dv) and out.dtype == dtype
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())

    # the plain form: the live pages side by side (a dead entry reads block 0, finite,
    # under the mask), one more page of room for the own row of a full lane
    live = np.arange(n)[None] < -(-lengths[:, None] // block)
    cap = (n + 1) * block

    def side_by_side(pages, own, width):
        rows = pages[at][np.where(live, table, 0)].reshape(lanes, n * block, 1, kv * width)
        rows = jnp.pad(rows, ((0, 0), (0, block), (0, 0), (0, 0)))
        rows = layers.write_rows(
            rows, jnp.arange(lanes)[:, None], jnp.asarray(lengths)[:, None], own[:, None, None])
        return rows.reshape(lanes, cap, kv, width)

    mask = jnp.asarray(np.arange(cap)[None, None] <= lengths[:, None, None])
    want = layers.plain_attend(
        q[:, None], side_by_side(k_pages, k_own, d), side_by_side(v_pages, v_own, dv), mask,
        1.0 / np.sqrt(d))[:, 0]
    tol = dict(atol=2e-5, rtol=1e-4) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol)
    # the padded lane gets its own row's value, each head its K/V head's columns
    np.testing.assert_allclose(
        np.asarray(out[1], np.float32),
        np.broadcast_to(np.asarray(v_own[1], np.float32).reshape(kv, 1, dv), (kv, groups, dv)),
        **tol)
