"""Fused causal attention for TPU.

A blocked flash-attention (online-softmax) Pallas kernel for the MXU, with a
pure-XLA fallback for CPU tests and odd shapes. The reference framework has
no attention kernels at all — its only attention is RLlib's GTrXL model code
(reference: rllib/models/torch/attention_net.py:37), and long-context work is
delegated to external libraries (SURVEY.md §5); here fused attention is a
first-class op that the ring/context-parallel layer composes with.

Layout: [batch, heads, seq, head_dim]. The kernel runs a grid of
(batch*heads, q_blocks, kv_blocks) with the kv dimension innermost (sequential
on TPU), keeping the running max/denominator and the output accumulator in
VMEM scratch across kv steps.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import backend

NEG_INF = -1e30

# flash-attention kernel tiles (v5e sweep at 2048 tokens: 1024/1024 was ~6%
# faster on a 1B model, 2048 overflows VMEM; no cell has run anything but 512)
BLOCK_Q = 512
BLOCK_K = 512


# ---------------------------------------------------------------------------
# XLA reference path (CPU tests, fallback, and the vjp reference)
# ---------------------------------------------------------------------------


def _attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    *_, t_q, d = q.shape
    t_kv = k.shape[-2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = None
    if causal:
        q_pos = jnp.arange(t_q)[:, None] + (t_kv - t_q)
        k_pos = jnp.arange(t_kv)[None, :]
        mask = q_pos >= k_pos
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg if mask is None else (mask[None, None] & seg)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_offset=0,
) -> Tuple[jax.Array, jax.Array]:
    """(out, logsumexp) over [b,h,t_q,d] — the merge-ready block primitive
    for ring attention (online-softmax combining across kv blocks).
    ``kv_offset`` is the global position of k/v's first row when
    the block is a slice of a longer sequence; with the default, a shorter
    q is treated as the suffix of the context (chunked-prefill layout).
    Differentiable end to end (plain XLA ops)."""
    *_, t_q, d = q.shape
    t_kv = k.shape[-2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        if isinstance(kv_offset, int) and kv_offset == 0:
            q_pos = jnp.arange(t_q)[:, None] + (t_kv - t_q)
        else:
            q_pos = jnp.arange(t_q)[:, None]
        k_pos = kv_offset + jnp.arange(t_kv)[None, :]
        logits = jnp.where((q_pos >= k_pos)[None, None], logits, NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", jnp.exp(logits - lse[..., None]).astype(v.dtype), v
    )
    return out, lse


def merge_attention(o, lse, o_new, lse_new, valid=True):
    """Online-softmax merge of two normalized partial attentions
    (o in f32, lse from attention_with_lse); the single source of the
    logaddexp rule of ring attention."""
    valid = jnp.asarray(valid)
    lse_out = jnp.where(valid, jnp.logaddexp(lse, lse_new), lse)
    w_old = jnp.exp(lse - lse_out)[..., None]
    w_new = jnp.where(valid, jnp.exp(lse_new - lse_out), 0.0)[..., None]
    return o * w_old + o_new.astype(jnp.float32) * w_new, lse_out


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, causal, scale, block_q, block_k, q_len, kv_len
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            # q row i attends to kv positions <= i + (kv_len - q_len), i.e.
            # a shorter q block is the *suffix* of the context (chunked
            # prefill) — matches the XLA fallback's offset mask.
            q_pos = (
                qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                + (kv_len - q_len)
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if kv_len % block_k != 0:
            # mask padded kv columns in the ragged last block; v must be
            # zeroed too (p is 0 there, but 0 * uninitialized = NaN)
            s = jnp.where(k_pos < kv_len, s, NEG_INF)
            kv_valid = (
                ki * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            ) < kv_len
            v = jnp.where(kv_valid, v, 0.0)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    if causal:
        # Skip fully-masked kv blocks (the whole block is above the diagonal).
        first_masked = (qi * block_q + block_q - 1 + (kv_len - q_len)) < ki * block_k

        @pl.when(jnp.logical_not(first_masked))
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / denom[:, None]).astype(o_ref.dtype)
        # logsumexp over the scaled+masked logits; rows with no valid kv
        # (cannot happen for causal self-attention) would be -inf.
        lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(denom)


def _flash_attention_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
):
    """Returns (out [b,h,t_q,d], lse [b,h,t_q] float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, t_q, d = q.shape
    t_kv = k.shape[-2]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    bh = b * h
    qr = q.reshape(bh, t_q, d)
    kr = k.reshape(bh, t_kv, d)
    vr = v.reshape(bh, t_kv, d)
    grid = (bh, pl.cdiv(t_q, block_q), pl.cdiv(t_kv, block_k))
    kernel = functools.partial(
        _flash_fwd_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_len=t_q,
        kv_len=t_kv,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bhi, qi, ki: (bhi, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bhi, qi, ki: (bhi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            # [bh, t_q, 1]: trailing dim of 1 equals the full array dim,
            # which keeps the block shape legal for TPU (8,128) tiling.
            pl.BlockSpec((1, block_q, 1), lambda bhi, qi, ki: (bhi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, t_q, d), lse.reshape(b, h, t_q)


def _row_block_specs(block_q, transposed_grid=False):
    """BlockSpec for [bh, t_q, 1] row statistics (lse/delta)."""
    from jax.experimental import pallas as pl

    if transposed_grid:  # grid (bh, kv, q): q index is the 3rd grid axis
        return pl.BlockSpec((1, block_q, 1), lambda bhi, j, i: (bhi, i, 0))
    return pl.BlockSpec((1, block_q, 1), lambda bhi, i, j: (bhi, i, 0))


# ---------------------------------------------------------------------------
# Pallas flash kernels (backward)
#
# FlashAttention-2 style: recompute P = exp(S - lse) per block; one kernel
# accumulates dQ (kv innermost), a second accumulates dK/dV (q innermost).
# delta = rowsum(dO * O) is computed in plain XLA beforehand.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, causal, scale, block_q, block_k, q_len, kv_len
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if kv_len % block_k != 0:
            kv_valid = (
                ki * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            ) < kv_len
            k = jnp.where(kv_valid, k, 0.0)
            v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = (
                qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                + (kv_len - q_len)
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if kv_len % block_k != 0:
            s = jnp.where(k_pos < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if causal and q_len > kv_len:
            # Rows with no visible kv (possible when q extends past kv) have
            # lse == NEG_INF, making exp(s - lse) == 1 instead of 0.
            p = jnp.where(lse[:, None] > NEG_INF / 2, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        fully_masked = (qi * block_q + block_q - 1 + (kv_len - q_len)) < ki * block_k

        @pl.when(jnp.logical_not(fully_masked))
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, causal, scale, block_q, block_k, q_len, kv_len
):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        ragged_q = q_len % block_q != 0
        if ragged_q:
            q_valid = (
                qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
            ) < q_len
            q = jnp.where(q_valid, q, 0.0)
            do = jnp.where(q_valid, do, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            q_pos = (
                qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                + (kv_len - q_len)
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if kv_len % block_k != 0:
            s = jnp.where(k_pos < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if causal and q_len > kv_len:
            # Same NEG_INF-sentinel guard as the dq kernel: empty rows must
            # not contribute to dk/dv.
            p = jnp.where(lse[:, None] > NEG_INF / 2, p, 0.0)
        if ragged_q:
            # lse/delta of padded q rows are undefined (possibly nan) —
            # zero those rows explicitly before they touch the MXU.
            p = jnp.where(q_valid, p, 0.0)
        dv_acc_ref[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        if ragged_q:
            ds = jnp.where(q_valid, ds, 0.0)
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        fully_masked = (qi * block_q + block_q - 1 + (kv_len - q_len)) < ki * block_k

        @pl.when(jnp.logical_not(fully_masked))
        def _run():
            _body()
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_attention_tpu_bwd(
    q, k, v, o, lse, g, *, causal, scale, block_q, block_k, interpret=False
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, t_q, d = q.shape
    t_kv = k.shape[-2]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    bh = b * h
    qr = q.reshape(bh, t_q, d)
    kr = k.reshape(bh, t_kv, d)
    vr = v.reshape(bh, t_kv, d)
    dor = g.reshape(bh, t_q, d)
    lser = lse.reshape(bh, t_q, 1)
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(bh, t_q, 1)

    common = dict(
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        q_len=t_q, kv_len=t_kv,
    )
    q_spec = pl.BlockSpec((1, block_q, d), lambda bhi, i, j: (bhi, i, 0))
    row_spec = _row_block_specs(block_q)
    kv_spec_dq = pl.BlockSpec((1, block_k, d), lambda bhi, i, j: (bhi, j, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        name="flash_bwd_dq",
        grid=(bh, pl.cdiv(t_q, block_q), pl.cdiv(t_kv, block_k)),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    # dk/dv pass: kv block is the resident tile; iterate q blocks innermost.
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bhi, j, i: (bhi, i, 0))
    row_spec2 = _row_block_specs(block_q, transposed_grid=True)
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda bhi, j, i: (bhi, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        name="flash_bwd_dkv",
        grid=(bh, pl.cdiv(t_kv, block_k), pl.cdiv(t_q, block_q)),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)
    return (
        dq.reshape(b, h, t_q, d),
        dk.reshape(b, h, t_kv, d),
        dv.reshape(b, h, t_kv, d),
    )


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "scale", "use_pallas"))
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Fused attention over [batch, heads, seq, head_dim] inputs.

    Differentiable everywhere: forward and backward both run as Pallas
    flash kernels (custom_vjp) on TPU. ``use_pallas=None`` picks the kernel
    on TPU wherever it can tile the shape and the O(T²) XLA path otherwise
    (CPU tests, ``segment_ids``, odd shapes); ``use_pallas=True`` demands
    the kernel and raises for a shape it cannot tile.
    """
    scale_val = float(scale) if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    d = q.shape[-1]
    supported = (
        segment_ids is None
        and (d % 128 == 0 or d == 64)
        and q.shape[-2] % 8 == 0
        and k.shape[-2] % 8 == 0
    )
    if use_pallas and not supported:
        raise ValueError(
            f"flash attention cannot tile q{q.shape} k{k.shape}"
            + (" with segment_ids" if segment_ids is not None else "")
            + ": needs head_dim % 128 == 0 (or 64), seq lens % 8 == 0"
        )
    use = use_pallas if use_pallas is not None else backend.on_tpu()
    if use and supported:
        return flash_attention(
            q, k, v, causal, scale_val, BLOCK_Q, BLOCK_K, False
        )
    return _attention_xla(q, k, v, causal=causal, scale=scale_val, segment_ids=segment_ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal, scale, block_q, block_k, interpret):
    """Flash attention with a full Pallas forward+backward (custom_vjp)."""
    out, _ = _flash_attention_tpu(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


# what the forward kernel returns, by name: a remat that keeps both
# (``models/gpt.py`` ``ScannedBlocks``) hands them to the backward kernels, and
# one that keeps neither, or only one, runs the forward kernel again for them
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """The forward rule: the kernel's output and logsumexp, named as the kernel
    lays them out ([b, h, t, d] and [b, h, t], before the model transposes
    anything) and both of them: it makes them in one call."""
    out, lse = _flash_attention_tpu(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    out, lse = (checkpoint_name(x, name) for x, name in zip((out, lse), FLASH_RESIDUALS))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_attention_tpu_bwd(
        q, k, v, out, lse, g,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Pallas kernel for serving: attention under a given mask (forward only)
# ---------------------------------------------------------------------------

# a chunk of 512 queries meets each tile of its K/V head once, for all its query
# heads. On a v5e, 4 x 8 heads x 512 queries over 32768 keys: 3.77 ms at 512 x
# 512, 2.42 at 512 x 1024, 2.45 at 512 x 2048, 2.44 at 512 x 4096 (PERF.md, PR 33)
MASKED_BLOCK_Q = 512
MASKED_BLOCK_K = 1024
MASKED_VMEM_BYTES = 64 * 2**20     # of the chip's 128 MiB; the default scope is 16
# room for the float32 accumulator of the query heads that meet a tile together:
# 8 heads x 512 queries x a value of 512, beside 19 MiB of their queries and
# results, each held twice; 16 such heads would take 55 MiB of the 64
MASKED_ACC_BYTES = 8 * 2**20


def _heads_a_tile(groups: int, block_q: int, dv: int) -> int:
    """How many of a K/V head's ``groups`` query heads meet a tile of it
    together: the most that divide ``groups`` and whose accumulator fits."""
    return max(
        h for h in range(1, groups + 1)
        if groups % h == 0 and (h == 1 or h * block_q * dv * 4 <= MASKED_ACC_BYTES))


# K and V are handed to the kernel copied by K/V head, ``[b, kv, s, width]``. Measured on a
# v5e (PERF.md, PR 59: a layer's slice, its rows written, the attend; Command A+'s chunk,
# 8 x 16 heads x 256 queries over 8,192 slots, and Keye's, 4 x 8 x 512 over 32,768) that copy
# is kept in VMEM and costs nothing that shows: 0.767 and 2.256 ms copied, 0.757 and 2.173
# with a head read as a column block of the rows ``[b, s, kv x width]`` (itself a re-layout
# of ``[b, s, kv, width]``, whose device tile is heads x lanes), 0.763 and 2.177 with all
# heads' tile fetched and a head's sublane rows picked in VMEM. Neither in-place form moved
# a chunk (22.05 -> 22.00 ms), so neither was kept.


def laid_out_by_head(x: jax.Array) -> jax.Array:
    """``x`` [b, t, heads, d] as it is, held on the device with its heads outermost
    ([b, heads, t, d] in memory), which is how :func:`masked_attention` takes its
    queries. For a projection's result, before the rotation: left to choose, the
    compiler carries the kernel's layout back through the elementwise rotation to the
    product and there picks one with the chunk's tokens in the lanes, for which it
    re-lays the layer's **weights** out on every call (Command A+: 134 MB of ``q``
    kernel copied a layer and chunk, ``copy.114``, 0.45 ms of a 5 ms layer). Told
    here, the product writes by head from the weights as they lie: the same product,
    the same bits (``PERF.md``, PR 59)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(x, Layout(major_to_minor=(0, 2, 1, 3)))


def _masked_fwd_kernel(
    blocks_ref, *refs, scale, groups, sinks=False
):
    from jax.experimental import pallas as pl

    if sinks:
        sinks_ref, *refs = refs
    q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref = refs
    ki = pl.program_id(3)
    first = pl.program_id(1) * groups if sinks else None    # this tile's first query head

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if sinks:
            # a head's sink is a key every query sees, with a logit of its own and
            # no value: the maximum starts there and the sum at exp(sink - sink)
            for g in range(groups):
                m_ref[g] = jnp.full(m_ref.shape[1:], sinks_ref[first + g], jnp.float32)
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ki < blocks_ref[pl.program_id(0)])
    def _run():
        k, v = k_ref[0, 0], v_ref[0, 0]                   # [block_k, d]
        keep = mask_ref[0].astype(jnp.int32) > 0          # [block_q, block_k]

        def head(g, carry):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            # a row that has met no key of its own yet sits at NEG_INF and
            # sums rubbish; its first key sets alpha to an exact 0, and a
            # block it has no key in leaves it as it was (alpha 1, p 0): a
            # row's result depends on its own keys alone
            m_prev = m_ref[g]                              # [block_q, 1]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new
            return carry

        # the query heads of this K/V head, one after another over the same K,
        # V and mask tiles. A loop and not eight copies: unrolled, the heads
        # overlap (2.19 ms for 2.45) but are 1.9 MB of code a program, which
        # the device holds beside the weights
        jax.lax.fori_loop(0, groups, head, None)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[:]
        o_ref[0, 0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def masked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
    kv_len: jax.Array,
    *,
    scale: Optional[float] = None,
    sinks: Optional[jax.Array] = None,
    block_q: int = MASKED_BLOCK_Q,
    block_k: int = MASKED_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """``softmax(where(mask, q . k * scale, -1e30)) . v`` per query head, for
    a serving call over its padded caches, as one kernel: the scores of a tile
    stay in VMEM (float32; running maximum, sum and accumulator too), the
    operands go to the MXU as they come and the weights in ``v``'s type.

    ``q`` [b, t, kv, groups, d] are the queries, ``groups`` query heads to a
    K/V head, which share its tiles of ``k`` [b, s, kv, d] and ``v`` [b, s, kv,
    dv] and of ``mask`` [b, t, s] (bool, no head axis: what a query may read,
    whatever decided it); both are copied by K/V head, [b, kv, s, width], for the
    kernel, which costs nothing that shows (the note above
    :func:`laid_out_by_head`). ``dv`` need not be ``d``: a latent cache is scored
    over all of a row and summed over its first features (``models/kimi_k2.py``:
    one row of 576 under 64 query heads, 512 of it the value). As many query
    heads meet a tile together as their float32 accumulator (heads x ``block_q``
    x ``dv``) has room for in VMEM (:func:`_heads_a_tile`: all of Keye's 8, 8 of
    a latent's 64), and a K/V head's tiles are fetched once for each such block
    of its heads. ``kv_len`` [b] int32 promises that no query of lane ``i`` reads a
    key at or past ``kv_len[i]``: key blocks past it are neither fetched nor
    computed, whatever they hold. ``sinks`` [kv, groups] float32, where given, is a
    learned logit a query head that stands in every query's denominator and
    brings no value (``exp(sink)`` beside the keys' ``exp(q . k * scale)``): the
    running maximum starts at it and the running sum at 1, nothing else; a query
    whose mask is empty then gets zeros. Returns [b, t, kv, groups, dv] in ``q``'s
    type. Without sinks a query whose mask is empty gets finite rubbish."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, kv, groups, d = q.shape
    s, dv = k.shape[1], v.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    block_q, block_k = min(block_q, t), min(block_k, s)
    heads = _heads_a_tile(groups, block_q, dv)
    tiles = groups // heads         # the grid's head axis: these blocks, K/V head by K/V head
    # whole tiles: padded queries read nothing, padded keys are read by nobody
    pad_q, pad_k = -t % block_q, -s % block_k
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q)) + ((0, 0),) * 3)
        k, v = (jnp.pad(x, ((0, 0), (0, pad_k), (0, 0), (0, 0))) for x in (k, v))
        mask = jnp.pad(mask, ((0, 0), (0, pad_q), (0, pad_k)))
    nq, nk = (t + pad_q) // block_q, (s + pad_k) // block_k
    blocks = jnp.clip((kv_len.astype(jnp.int32) + block_k - 1) // block_k, 0, nk)

    # what is prefetched to scalar memory: the lanes' live blocks and, where
    # given, the sinks, flat in the order of the query heads
    prefetched = (blocks,) if sinks is None else (
        blocks, sinks.astype(jnp.float32).reshape(kv * groups))

    def kv_block(bi, ki, blocks):
        # past the lane's last live block: the block already there, no fetch
        return jnp.maximum(jnp.minimum(ki, blocks[bi] - 1), 0)

    def q_spec(width):
        return pl.BlockSpec(
            (1, 1, heads, block_q, width), lambda bi, hi, qi, ki, *_: (bi, hi, 0, qi, 0))

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda bi, hi, qi, ki, blocks, *_: (bi, hi // tiles, kv_block(bi, ki, blocks), 0))

    out = pl.pallas_call(
        functools.partial(
            _masked_fwd_kernel, scale=scale, groups=heads, sinks=sinks is not None),
        name="masked_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, kv * tiles, nq, nk),
            in_specs=[
                q_spec(d), kv_spec(d), kv_spec(dv),
                pl.BlockSpec(
                    (1, block_q, block_k),
                    lambda bi, hi, qi, ki, blocks, *_: (bi, qi, kv_block(bi, ki, blocks))),
            ],
            out_specs=q_spec(dv),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, dv), jnp.float32),
                pltpu.VMEM((heads, block_q, 1), jnp.float32),
                pltpu.VMEM((heads, block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv * tiles, heads, t + pad_q, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=MASKED_VMEM_BYTES),
        interpret=interpret,
    )(
        *prefetched, q.transpose(0, 2, 3, 1, 4).reshape(b, kv * tiles, heads, t + pad_q, d),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask.astype(jnp.int8))
    return out.reshape(b, kv, groups, t + pad_q, dv).transpose(0, 3, 1, 2, 4)[:, :t]


# ---------------------------------------------------------------------------
# Pallas kernel for serving: a chunk over cached latents, in the expanded form
# ---------------------------------------------------------------------------


def _latent_fwd_kernel(
    blocks_ref, qn_ref, qr_ref, rows_ref, wk_ref, wv_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale
):
    from jax.experimental import pallas as pl

    ki = pl.program_id(3)
    heads, nope, rope = qn_ref.shape[2], qn_ref.shape[-1], qr_ref.shape[-1]
    rank, dv = wk_ref.shape[-2], o_ref.shape[-1]

    def of_head(ref, g, width):
        """Head ``g``'s [rank, width] of one half of W_kvb: its columns of the heads
        side by side, or its slab where the heads lie outermost (a width that is no
        whole number of lane tiles: ``latent_attention``'s ``up_spec``)."""
        if len(ref.shape) == 3:
            return ref[g]
        return ref[:, pl.ds(pl.multiple_of(g * width, width), width)]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ki < blocks_ref[pl.program_id(0)])
    def _run():
        rows = rows_ref[0]                                 # [block_k, row]
        latent, k_rope = rows[:, :rank], rows[:, rank:rank + rope]
        keep = mask_ref[0].astype(jnp.int32) > 0          # [block_q, block_k]

        def head(g, carry):
            # the head's own key and value of this tile, made here and gone
            # with it: W_kvb's two halves as they are stored, the head's columns
            k_nope = jnp.dot(
                latent, of_head(wk_ref, g, nope),
                preferred_element_type=jnp.float32).astype(rows.dtype)
            v = jnp.dot(
                latent, of_head(wv_ref, g, dv),
                preferred_element_type=jnp.float32).astype(rows.dtype)
            s = (
                jax.lax.dot_general(
                    qn_ref[0, 0, g], k_nope, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[0, 0, g], k_rope, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)) * scale
            s = jnp.where(keep, s, NEG_INF)
            # the running softmax as ``_masked_fwd_kernel`` keeps it
            m_prev = m_ref[g]                              # [block_q, 1]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new
            return carry

        # a loop over the block's heads, as in ``_masked_fwd_kernel`` and for
        # its reason: one copy of the code a program
        jax.lax.fori_loop(0, heads, head, None)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[:]
        o_ref[0, 0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def latent_attention(
    q_nope: jax.Array,
    q_rope: jax.Array,
    rows: jax.Array,
    k_up: jax.Array,
    v_up: jax.Array,
    mask: jax.Array,
    kv_len: jax.Array,
    *,
    scale: float,
    block_q: int = MASKED_BLOCK_Q,
    block_k: int = MASKED_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """A prefill chunk over a lane's cached latents in the **expanded** form
    (``models/kimi_k2.py``), as one kernel: ``softmax(where(mask, (q_nope .
    k_nope + q_rope . k_rope) * scale, -1e30)) . v`` per head with ``[k_nope ; v]
    = c_kv W_kvb`` made tile by tile in VMEM, so that a head's key and value
    never reach HBM (1.34 GB a layer over a cache of 32768 if they did).

    ``q_nope`` [b, t, heads, nope] and ``q_rope`` [b, t, heads, rope] (rotated)
    are the queries as ``W_qb`` leaves them; ``rows`` [b, s, row] the cache as it
    lies, a row's first ``rank`` features the normed latent ``c_kv`` and the
    next ``rope`` the rotated key all heads share; ``k_up`` [rank, heads, nope]
    and ``v_up`` [rank, heads, dv] the two halves of ``W_kvb`` as the parameters
    hold them; ``mask`` [b, t, s] and ``kv_len`` [b] as in
    :func:`masked_attention`: key tiles at or past a lane's ``kv_len`` are neither
    fetched, expanded nor computed. Operands go to the MXU in ``rows``' type
    (a head's key and value are rounded to it as the published model's own
    code rounds them), products are summed and the scores, running maximum, sum
    and accumulator kept in float32. A block of heads meets a tile together,
    one after another: as many as :func:`_heads_a_tile` finds room for beside
    their accumulators' running maximum and sum, which take a lane tile each.
    Returns [b, t, heads, dv] in ``q_nope``'s type: what goes into ``W_o``.

    Against the absorbed form in :func:`masked_attention` (one 640-wide row
    under 64 heads, a value of 512) a pair costs 2 x 320 operations a head
    where it cost 2 x 1088, and a slot 2 x 512 x 256 a head once a chunk: at 512
    queries 37.8 M operations a slot for 71.3 M. On a v5e, 512 queries x 64
    heads over 4096, 16384 and 32768 live slots of a 32768 cache: 1.37, 4.52
    and 8.75 ms where ``masked_attention`` takes 2.42, 7.73 and 14.81 (4.3 us a
    head and tile of 1024 keys, of which the MXU's passes are 3.4). Tiles of
    2048 keys, 16 heads a block, the score as one dot over a joined key and the
    heads unrolled two, four or eight at a time each move that by under 3 %
    (PERF.md, PR 51)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, n_heads, nope = q_nope.shape
    rope, s, row = q_rope.shape[-1], rows.shape[1], rows.shape[2]
    rank, dv = k_up.shape[0], v_up.shape[-1]
    block_q, block_k = min(block_q, t), min(block_k, s)
    # float32 lanes a head and query: the accumulator's, and a tile each for m and l
    heads = _heads_a_tile(n_heads, block_q, dv + 2 * 128)
    tiles = n_heads // heads
    pad_q, pad_k = -t % block_q, -s % block_k
    if pad_q or pad_k:
        q_nope, q_rope = (
            jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0))) for x in (q_nope, q_rope))
        rows = jnp.pad(rows, ((0, 0), (0, pad_k), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad_q), (0, pad_k)))
    nq, nk = (t + pad_q) // block_q, (s + pad_k) // block_k
    blocks = jnp.clip((kv_len.astype(jnp.int32) + block_k - 1) // block_k, 0, nk)

    def kv_block(bi, ki, blocks):
        # past the lane's last live block: the block already there, no fetch
        return jnp.maximum(jnp.minimum(ki, blocks[bi] - 1), 0)

    def q_spec(width):
        return pl.BlockSpec(
            (1, 1, heads, block_q, width), lambda bi, hi, qi, ki, blocks: (bi, hi, 0, qi, 0))

    def up_spec(width):                 # the block's heads' columns of one half of W_kvb
        if width % 128:
            # a head's columns would start inside a 128-lane tile, which the kernel
            # cannot slice (GLM-5's nope of 192): the heads outermost, a head a slab
            return pl.BlockSpec((heads, rank, width), lambda bi, hi, qi, ki, blocks: (hi, 0, 0))
        return pl.BlockSpec((rank, heads * width), lambda bi, hi, qi, ki, blocks: (0, hi))

    def by_columns(up):                 # [rank, n_heads, width] as ``up_spec`` reads it
        width = up.shape[-1]
        return up.transpose(1, 0, 2) if width % 128 else up.reshape(rank, n_heads * width)

    def by_head(q):
        return q.transpose(0, 2, 1, 3).reshape(b, tiles, heads, t + pad_q, q.shape[-1])

    out = pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=float(scale)),
        name="latent_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, tiles, nq, nk),
            in_specs=[
                q_spec(nope), q_spec(rope),
                pl.BlockSpec(
                    (1, block_k, row),
                    lambda bi, hi, qi, ki, blocks: (bi, kv_block(bi, ki, blocks), 0)),
                up_spec(nope), up_spec(dv),
                pl.BlockSpec(
                    (1, block_q, block_k),
                    lambda bi, hi, qi, ki, blocks: (bi, qi, kv_block(bi, ki, blocks))),
            ],
            out_specs=q_spec(dv),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, dv), jnp.float32),
                pltpu.VMEM((heads, block_q, 1), jnp.float32),
                pltpu.VMEM((heads, block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, tiles, heads, t + pad_q, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=MASKED_VMEM_BYTES),
        interpret=interpret,
    )(
        blocks, by_head(q_nope), by_head(q_rope), rows,
        by_columns(k_up), by_columns(v_up),
        mask.astype(jnp.int8))
    return out.reshape(b, n_heads, t + pad_q, dv).transpose(0, 2, 1, 3)[:, :t]


# ---------------------------------------------------------------------------
# Pallas kernel for serving: a decode lane over its pages, where the pool keeps them
# ---------------------------------------------------------------------------


def _paged_fwd_kernel(
    at_ref, table_ref, pages_ref, lengths_ref, q_ref, k_ref, v_ref, k_own_ref, v_own_ref,
    o_ref, acc_ref, m_ref, l_ref, *, scale
):
    from jax.experimental import pallas as pl

    lane, page = pl.program_id(0), pl.program_id(1)
    block = k_ref.shape[0]

    @pl.when(page == 0)
    def _init():
        # the call's own row, which no page holds yet, is the key every lane starts
        # from: the maximum at its score, the sum at exp(0), the accumulator at its value
        own = (q_ref[0].astype(jnp.float32) * k_own_ref[0].astype(jnp.float32)).sum(
            -1, keepdims=True) * scale
        m_ref[:] = own
        l_ref[:] = jnp.ones_like(l_ref)
        acc_ref[:] = jnp.broadcast_to(v_own_ref[0].astype(jnp.float32), acc_ref.shape)

    @pl.when(page < pages_ref[lane])
    def _run():
        k, v = k_ref[:], v_ref[:]                         # [block, kv x width]
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        slot = page * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < lengths_ref[lane], s, NEG_INF)
        m_prev = m_ref[:]                                  # [heads, 1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = alpha * l_ref[:] + p.sum(-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(page == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def _paged_latent_kernel(
    at_ref, table_ref, pages_ref, lengths_ref, q_ref, rows_ref, k_own_ref, v_own_ref, *rest,
    scale
):
    """:func:`_paged_fwd_kernel` over pages whose rows are key and value at once: the value
    block is the first columns of the key block where it lies in VMEM (whole 128-lane
    tiles: a view, no copy), as wide as the own row's value."""
    _paged_fwd_kernel(
        at_ref, table_ref, pages_ref, lengths_ref, q_ref, rows_ref,
        rows_ref.at[:, :v_own_ref.shape[-1]], k_own_ref, v_own_ref, *rest, scale=scale)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: Optional[jax.Array],
    at,
    table: jax.Array,
    lengths: jax.Array,
    k_own: jax.Array,
    v_own: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """A decode call's attend through the block table, as one kernel: each lane's one
    query a head over the ``lengths[lane]`` cached rows that the first pages of
    ``table[lane]`` hold, **where the pool keeps them**, and over the call's own row,
    which is in no page yet. Nothing is gathered and nothing written before the attend.

    ``q`` [lanes, kv, groups, d]; ``k_pages`` [layers, blocks, block, 1, kv x d] and
    ``v_pages`` [layers, blocks, block, 1, kv x dv] the pool's arenas as they lie (all
    K/V heads of a row side by side) and ``at`` the layer's index in them (traced: a
    scan's layer); ``table`` [lanes, n] int32 the lanes' block ids and ``lengths``
    [lanes] int32, both prefetched to scalar memory, where the index map reads them;
    ``k_own`` [lanes, kv x d] and ``v_own`` [lanes, kv x dv] the row the call brings for
    each lane, at position ``lengths[lane]``. A grid step is one page of one lane: all
    K/V heads' rows of it, fetched once as one block (256 x 768 bfloat16 at MiMo's
    sizes: 393 KB). A step past the lane's last live page names the page already there
    (no fetch) and computes nothing, so an entry of ``table`` past it is never
    followed, whatever it names; a slot at or past ``lengths[lane]`` inside the last
    page is masked, and must hold something finite (the pool's arenas do: zeros, or
    what a model wrote). The running maximum, sum and accumulator start from the own
    row the way :func:`masked_attention`'s start from a sink.

    All query heads meet a page in one product: the queries go in **block-diagonal**,
    ``[kv x groups, kv x d]`` with a head's ``d`` features in its K/V head's columns
    and zeros elsewhere, so ``q . page^T`` scores every head against its own K/V head's
    columns of the rows as they lie, whatever ``d`` is (MiMo's 192 is no whole number
    of the chip's 128 lanes: no slice, no re-layout); ``p . page`` gives every head all
    heads' values, of which its own columns are picked from the result. The MXU's
    time on a decode lane is loading a page as the stationary operand, the same
    tiles either way; the zeros cost passes of at most 64 rows. Scores, softmax and
    accumulator float32, the weights in ``v``'s type, as :func:`masked_attention`.
    Returns [lanes, kv, groups, dv] in ``q``'s type. A lane of length 0 (padding)
    gets its own row's value.

    **A row that is key and value at once** (``v_pages`` None: a latent row, one K/V
    head; ``models/kimi_k2.py``): every head scores the whole row of ``k_pages`` and sums
    its first ``dv`` features, ``dv`` the width of ``v_own``, a whole number of the
    chip's 128 lanes. The same grid and the same running softmax over **one** fetch of
    a page a step (256 x 640 bfloat16 at Kimi's sizes: 328 KB; the arena handed in
    twice would be two): the value block is a view of the key block in VMEM, and the
    accumulator is ``[heads, dv]``, not a row wide and cut afterwards."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, kv, groups, d = q.shape
    block, n = k_pages.shape[2], table.shape[1]
    latent = v_pages is None
    dv = v_own.shape[-1] // kv
    heads = kv * groups
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    lengths = lengths.astype(jnp.int32)
    pages = jnp.clip((lengths + block - 1) // block, 0, n)
    # a head's features in its K/V head's columns of a row, zeros in the others'
    diagonal = jnp.einsum("bhgd,hk->bhgkd", q, jnp.eye(kv, dtype=q.dtype)).reshape(
        lanes, heads, kv * d)

    def page_of(lane, page, at, table, pages, lengths):
        # past the lane's last live page: the page already there, no fetch
        live = jnp.maximum(jnp.minimum(page, pages[lane] - 1), 0)
        return at[0], table[lane * n + live], 0, 0

    def per_lane(rows, width):
        return pl.BlockSpec((1, rows, width), lambda lane, page, *_: (lane, 0, 0))

    def paged(width):
        return pl.BlockSpec((None, None, block, width), page_of)

    def as_it_lies(pages):
        # an arena without its axis of one, which the device keeps behind a block's
        # tokens: the same bytes
        return pages.reshape(pages.shape[:3] + pages.shape[4:])

    arenas = (k_pages,) if latent else (k_pages, v_pages)
    out = pl.pallas_call(
        functools.partial(_paged_latent_kernel if latent else _paged_fwd_kernel, scale=scale),
        name="paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(lanes, n),
            in_specs=[
                per_lane(heads, kv * d), *(paged(a.shape[-1]) for a in arenas),
                per_lane(1, kv * d), per_lane(1, kv * dv)],
            out_specs=per_lane(heads, kv * dv),
            scratch_shapes=[
                pltpu.VMEM((heads, kv * dv), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, heads, kv * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        jnp.asarray(at, jnp.int32).reshape(1), table.astype(jnp.int32).reshape(lanes * n),
        pages, lengths, diagonal, *map(as_it_lies, arenas),
        k_own.reshape(lanes, 1, kv * d), v_own.reshape(lanes, 1, kv * dv))
    # a head's own columns of what it summed over all heads' values
    out = out.reshape(lanes, kv, groups, kv, dv)
    return jnp.stack([out[:, h, :, h] for h in range(kv)], axis=1)
