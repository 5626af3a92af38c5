"""The kit the architecture files compose (``gpt.py``, ``cohere2_moe.py``,
``keye_vl2.py``, ``kimi_k2.py``, ``granitemoehybrid.py``, ``lfm2_moe.py``,
``minicpm_sala.py``, ``glm_moe_dsa.py``, ``longcat_flash.py``, ``mimo_v2_flash.py``, whose per-sequence state is made of
cached rows: a window's K and V; ``qwen3_next.py``, whose norms are zero-centred: it
hands :func:`rms_norm` the scale ``1 + g``; ``nemotron_h.py``, which also takes
``granitemoehybrid.ssm_scan``, the one function an architecture has from a sibling:
ROADMAP.md D19 moves the chunked recurrences here): every
decision they share, written once. Plain functions of their arguments: no
configuration, no ``jit`` and no scope of their own but ``extend.logits`` round
:func:`rms_head` and ``extend.attention.latent`` round :func:`latent_attend`'s
un-absorption, because the readers of a device trace key on the path of scopes
the *caller* builds (``jit(extend)/extend.attention/...``). An architecture imports
from here and from ``moe.py``, never from a sibling. What only one file does, or
two do in different operations, stays in its file (``CHANGES.md``, PR 46).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention, backend

#: queries scored or attended at a time in a prefill chunk: the float32 scores of
#: one block are ``lanes x heads x QUERY_BLOCK x cache`` (134 MB a lane at 128 heads
#: and an 8192 cache, or at 32 heads and a 32768 cache; a whole 256-token chunk of
#: the former at once would be 1.07 GB a lane)
QUERY_BLOCK = 32

#: what a masked score reads as before the float32 softmax
MASKED = -1e30


def rotary(x: jax.Array, positions: jax.Array, rotary_dim: int,
           base: float = 10000.0, freqs=None) -> jax.Array:
    """RoPE of frequency base ``base``, or at the ``rotary_dim / 2`` given ``freqs``
    (``kimi_k2.py``'s are YaRN's), on the first ``rotary_dim`` features of [b, t, h, d];
    feature ``i`` turns with ``i + rotary_dim / 2`` (the half-split pairing)."""
    if rotary_dim <= 0:
        return x
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half)) if freqs is None else freqs
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [b, t, half]
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    r1, r2 = rot[..., :half], rot[..., half:]
    rotated = jnp.concatenate([r1 * cos - r2 * sin, r2 * cos + r1 * sin], axis=-1)
    return jnp.concatenate([rotated, keep], axis=-1)


def rms_norm(x, scale, eps):
    """In float32 whatever comes in, the scale cast: so is :func:`layer_norm`. The plain
    form, ``x / rms(x) * scale``: every architecture file's; a zero-centred norm
    (``qwen3_next.py``) is this with ``1 + g`` for the scale, made by its caller."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def layer_norm(x, scale, eps):
    """Subtracts the mean, scales, and has no bias."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def frame(tokens, lengths):
    """Where an ``extend`` call's ``tokens`` [b, tc] stand and which are real:
    ``positions`` [b, tc] (``lengths`` [b] tokens lie before each lane's first) and
    ``valid`` (a negative id is padding)."""
    tc = tokens.shape[1]
    positions = (
        lengths[:, None].astype(jnp.int32) + jnp.arange(tc, dtype=jnp.int32)[None, :])
    return positions, tokens >= 0


def look_up(table, tokens):
    """The rows of ``table`` [vocab, d] for ``tokens``; padding reads a row too."""
    return table[jnp.clip(tokens, 0, table.shape[0] - 1)]


def visible_keys(positions, valid, cache: int):
    """What each query may read of ``cache`` slots, [b, tc, cache]: every slot up to
    its own, and nothing for padding. That hides what was never written too:
    anything past a lane's frontier is acausal."""
    kpos = jnp.arange(cache, dtype=jnp.int32)
    return (kpos[None, None, :] <= positions[:, :, None]) & valid[:, :, None]


def cache_slots(cache, table=None):
    """The slots a lane's cache has in an ``extend`` call: those of the padded caches
    ``cache`` [layers, lanes, slots, ...], or, where the call is handed a block
    ``table`` [lanes, n] with the pool's arena [layers, blocks, block, ...] in the
    caches' place, its ``n`` pages'."""
    return cache.shape[2] * (1 if table is None else table.shape[1])


def live_keys(positions, valid):
    """A bound [b] past each lane's farthest real query: no key from there on is in
    any mask, and ``ops/attention.masked_attention`` stops there."""
    return jnp.where(valid, positions + 1, 0).max(1)


def write_rows(cache, lane, positions, rows):
    """``rows`` [b, tc, ...] into ``cache`` [b, slots, ...] at ``positions``; ``lane``
    is ``arange(b)[:, None]``, which a caller with several caches makes once.
    Out-of-capacity writes drop instead of clamping onto slot T-1."""
    return cache.at[lane, positions].set(rows, mode="drop")


def without_experts(stacked):
    """A scanned tree of layers as ``(the tree without "moe", what "moe" holds beside
    the experts, {"wi", "wo"})``. The routed experts stay out of the scan: every
    layer's grouped matmul reads them in place from the whole stack
    (``moe.held_experts_ffn``)."""
    layers = dict(stacked)
    routing = dict(layers.pop("moe"))
    return layers, routing, {"wi": routing.pop("wi"), "wo": routing.pop("wo")}


def query_block(tc: int) -> int:
    """Queries a block: a chunk that is no whole number of ``QUERY_BLOCK`` goes as one."""
    return QUERY_BLOCK if tc % QUERY_BLOCK == 0 else tc


def by_query_block(fn, *per_query):
    """``fn`` over blocks of :func:`query_block` queries (axis 1) of each argument
    [b, tc, ...], one block after the other; the results side by side again."""
    b, tc = per_query[0].shape[:2]
    n = query_block(tc)
    split = tuple(
        x.reshape((b, tc // n, n) + x.shape[2:]).swapaxes(0, 1) for x in per_query)
    out = jax.lax.map(lambda block: fn(*block), split)
    return out.swapaxes(0, 1).reshape((b, tc) + out.shape[3:])


def plain_attend(q, k, v, mask, scale):
    """Grouped attention densely: ``q`` [b, n, kv, g, hd] (``g`` query heads a K/V
    head) over ``k``, ``v`` [b, cache, kv, hd] under ``mask`` [b, n, cache]; scores and
    softmax float32, the weights cast to the values' type. The path off the chip,
    and on it a decode lane's whose model is handed padded caches (one handed the
    pool's pages attends in :func:`paged_attend`)."""
    logit = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    weight = jax.nn.softmax(
        jnp.where(mask[:, None, None], logit, jnp.float32(MASKED)), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", weight.astype(v.dtype), v)


def table_pages(pages, at, table):
    """The pages that ``table`` [b, n] names in layer ``at`` of the pool's arena ``pages``
    ``[layers, blocks, block, ...]``, side by side: ``[b, n x block, ...]``, a lane's
    padded cache as a gather would hand it over. The dense form of a read through the
    block table, off the chip."""
    slab = jax.lax.dynamic_index_in_dim(pages, at, 0, keepdims=False)
    return slab[table].reshape((table.shape[0], -1) + slab.shape[2:])


def paged_attend(q, k, v, k_pages, v_pages, at, table, positions, visible, scale):
    """A decode call's attend (one token a lane) **through the block table**: ``q`` [b, 1,
    kv, g, hd] over the rows that ``table`` [b, n] names in layer ``at`` of the pool's
    arenas ``k_pages``, ``v_pages`` ``[layers, blocks, block, 1, kv x width]`` as they lie,
    and over the call's own rows ``k``, ``v`` [b, 1, 1, kv x width], which stand at
    ``positions`` [b, 1] and are in no page yet (the engine pages them back after the
    call). ``visible`` [b, 1, n x block] is :func:`visible_keys` of the lanes' padded
    caches. On the chip ``ops/attention.paged_attention``: a lane's live pages are read
    once where they lie, nothing is gathered, written or re-laid out. Off it the same
    contract densely: the table's pages taken from the arena, the own row written among
    them, :func:`plain_attend`, which is bit for bit what a call over gathered caches
    computes. Returns [b, 1, kv, g, value width] in ``q``'s type."""
    b, _, kv = q.shape[:3]
    if backend.on_tpu():
        return attention.paged_attention(
            q[:, 0], k_pages, v_pages, at, table, positions[:, 0], k[:, 0, 0], v[:, 0, 0],
            scale=scale)[:, None]
    lane = jnp.arange(b)[:, None]

    def taken(pages, new):
        cache = table_pages(pages, at, table)
        return write_rows(cache, lane, positions, new).reshape(b, cache.shape[1], kv, -1)

    return plain_attend(q, taken(k_pages, k), taken(v_pages, v), visible, scale)


# -- latent attention (``kimi_k2.py``, ``longcat_flash.py``; ``glm_moe_dsa.py``'s queries) --


#: what latent attention counts over the real queries of a device call, summed
#: over the layers (``kimi_k2.py``, ``glm_moe_dsa.py``): queries, query-key pairs
#: attended in the absorbed and in the expanded form (a call's pairs all under the
#: form it attends in), and latent rows put through ``W_kvb``: the live slots of
#: every lane of a chunk on the chip, 0 for any other call
MLA_COUNTERS = ("mla_queries", "mla_pairs_absorbed", "mla_pairs_expanded", "mla_rows_expanded")


def latent_expands(tc: int) -> bool:
    """Whether a call of ``tc`` tokens a lane attends its cached latent rows in the
    expanded form: a chunk on the chip. (Its 512 queries a slot are three times the 171
    at which ``W_kvb`` over the slot is paid for; a decode call's one is not.)"""
    return tc > 1 and backend.on_tpu()


def latent_row(latent, rotary, row_dim: int):
    """``[latent ; rotary ; zeros]`` up to ``row_dim`` features: a cached row, or a query
    as it meets one in the absorbed form."""
    spare = jnp.zeros(
        latent.shape[:-1] + (row_dim - latent.shape[-1] - rotary.shape[-1],), latent.dtype)
    return jnp.concatenate([latent, rotary, spare], -1)


def latent_queries(p, hidden, positions, rotate, *, nope_dim: int, rank: int, row_dim: int,
                   eps: float, expanded: bool, q_scale=None, kv_scale=None):
    """Latent attention's projections of the normed ``hidden`` [b, t, d] under the
    parameters ``p`` (``q_a``, ``q_norm``, ``q_b``, ``kv_a``, ``kv_norm``, ``k_up``):
    ``(c_q, q, row)``. ``c_q`` [b, t, q_rank] is the normed query latent; ``row`` [b, t, 1,
    row_dim] the token's own cached row, the normed latent and the rotated rotary key
    behind it (``rotate(x, positions)``, the model's own rotation); ``q`` the queries as
    they meet a cached row [b, t, heads, row_dim] (``q_nope`` through ``W_kvb^K`` into the
    latent's space, the rotated rotary features behind, zeros), or for the ``expanded``
    form as ``W_qb`` leaves them: ``(q_nope, q_rope)``, the second rotated. ``q_scale``
    multiplies the normed query latent and ``kv_scale`` the normed cached one, in float32
    before the cast (``longcat_flash.py``'s two constants: folded into what is cached,
    whichever form reads it). The caller's scope (``extend.attention.latent``)."""
    dtype = hidden.dtype

    def kernel(name):
        return p[name]["kernel"].astype(dtype)

    def normed(x, name, by):
        x = rms_norm(x, p[name]["scale"], eps)
        return (x if by is None else x * jnp.float32(by)).astype(dtype)

    c_q = normed(hidden @ kernel("q_a"), "q_norm", q_scale)
    q = jnp.einsum("btr,rhk->bthk", c_q, kernel("q_b"))
    both = hidden @ kernel("kv_a")
    c_kv = normed(both[..., :rank], "kv_norm", kv_scale)
    if expanded:
        q = (q[..., :nope_dim], rotate(q[..., nope_dim:], positions))
    else:
        absorbed = jnp.einsum("bthn,chn->bthc", q[..., :nope_dim], kernel("k_up"))
        q = latent_row(absorbed, rotate(q[..., nope_dim:], positions), row_dim)
    return c_q, q, latent_row(
        c_kv[:, :, None], rotate(both[:, :, None, rank:], positions), row_dim)


def latent_attend(p, q, row, positions, visible, live, kc, paged, *, rank: int, scale: float):
    """The attend of :func:`latent_queries`' ``q`` (a pair: the expanded form) and the
    call's own ``row`` over a lane's cached rows, before ``W_o``: [b, t, heads, v_dim].
    ``kc`` is the layer's slab of the padded cache [b, cache, 1, row_dim], into which the
    call's rows are written; or, with ``paged`` (the layer's index and the lanes' block
    table: a call of one token a lane), the pool's arena itself, read where it lies, not
    written and not copied: on the chip ``ops/attention.paged_attention`` (one K/V head
    of all the query heads over the lanes' pages and the call's own row; a row is the key
    and, in its first ``rank`` features, the value), off it the table's pages side by side,
    to the bit what a gather hands over. ``visible`` [b, t, cache] is what each query may
    read, ``live`` [b] a bound past the lane's farthest real query. A chunk's expanded
    form is the kernel ``ops/attention.latent_attention`` (a head's own key and value,
    made of each tile of rows inside it); the absorbed form's un-absorption through
    ``W_kvb^V`` stands under ``extend.attention.latent``."""
    dtype = row.dtype

    def through_v_up(attended):             # each head's sum of latents [b, t, heads, rank]
        with jax.named_scope("extend.attention.latent"):
            return jnp.einsum("bthc,chv->bthv", attended, p["v_up"]["kernel"].astype(dtype))

    if paged is not None and backend.on_tpu():
        return through_v_up(attention.paged_attention(
            q[:, 0, None], kc, None, *paged, positions[:, 0], row[:, 0, 0],
            row[:, 0, 0, :rank], scale=scale)[:, None, 0])
    if paged is not None:
        kc = table_pages(kc, *paged)
    kc = write_rows(kc, jnp.arange(kc.shape[0])[:, None], positions, row)
    if isinstance(q, tuple):
        return attention.latent_attention(
            *q, kc[:, :, 0], p["k_up"]["kernel"].astype(dtype), p["v_up"]["kernel"].astype(dtype),
            visible, live, scale=scale)

    def attend_block(qb, mask):             # [b, n, heads, row_dim], [b, n, cache]
        logit = jnp.einsum(
            "bqhc,bkc->bhqk", qb, kc[:, :, 0], preferred_element_type=jnp.float32) * scale
        weight = jax.nn.softmax(jnp.where(mask[:, None], logit, jnp.float32(MASKED)), axis=-1)
        # over the whole row: what is behind the latent is cut from the
        # result and not from the cache, which would be copied for it
        return jnp.einsum("bhqk,bkc->bqhc", weight.astype(dtype), kc[:, :, 0])[..., :rank]

    return through_v_up(by_query_block(attend_block, q, visible))


def latent_counted(sites: int, positions, valid, cap: int, live, expanded: bool):
    """:data:`MLA_COUNTERS` of a call whose every one of ``sites`` attention sites reads
    every visible row: int32 [4]."""
    seen = jnp.where(valid, jnp.minimum(positions + 1, cap), 0)
    queries, pairs = valid.sum(dtype=jnp.int32), seen.sum(dtype=jnp.int32)
    if expanded:
        # every live slot of a lane goes through W_kvb once a site
        slots = jnp.minimum(live, cap).sum(dtype=jnp.int32)
        by_form = (jnp.int32(0), pairs, slots)
    else:
        by_form = (pairs, jnp.int32(0), jnp.int32(0))
    return sites * jnp.stack([queries, *by_form])


# -- a learned indexer's selection (``keye_vl2.py``, ``glm_moe_dsa.py``) -------------

#: what an indexer counts over the real queries of a device call, summed over
#: the layers: queries that passed an indexer, live causal query-key pairs it
#: scored, keys attended (``min(topk, visible)`` a query), and cache slots of
#: the call's padded caches that at least one query selected
SPARSE_COUNTERS = (
    "sparse_queries", "sparse_keys_scored", "sparse_keys_attended", "sparse_slots_read")

def _one_zero(scores, visible):
    """``scores`` with -0 as +0 (equal, so a tie) and -inf where not visible."""
    return jnp.where(visible, jnp.where(scores == 0, 0.0, scores), -jnp.inf)


def _ordered_bits(scores):
    """Float32 ``scores`` as uint32 that order as the scores do."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)


def select_mask(scores, visible, k: int):
    """For each query (a row of ``scores`` [..., cache], float32) the ``k``
    visible keys with the largest score, ties to the lower position, as a mask
    [..., cache]; all the visible ones where they are at most ``k``. No sort:
    the ``k``-th largest score is found bit by bit (the largest value that at
    least ``k`` scores reach), then the ties at it are counted off."""
    u = _ordered_bits(_one_zero(scores, visible))

    def next_bit(i, kth):
        candidate = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reached = (u >= candidate[..., None]).sum(-1, dtype=jnp.int32)
        return jnp.where(reached >= k, candidate, kth)

    kth = jax.lax.fori_loop(
        0, 32, next_bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, ties = u > kth, u == kth
    room = k - above.sum(-1, dtype=jnp.int32, keepdims=True)
    # only a row with more ties than room needs them counted off, and a
    # running count over the cache costs as much as the search: skip it where
    # no row of the call does
    tied = jax.lax.cond(
        (ties.sum(-1, dtype=jnp.int32, keepdims=True) > room).any(),
        lambda: ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room),
        lambda: ties)
    return (above | tied) & visible


def select_rows(scores, visible, k: int):
    """The same keys as positions: ``(positions [..., k'], chosen [..., k'])``
    with ``k' = min(k, cache)``; where fewer than ``k'`` keys are visible the
    rest are not ``chosen`` (``jax.lax.top_k`` puts the lower position first
    among equals)."""
    top, positions = jax.lax.top_k(_one_zero(scores, visible), min(k, scores.shape[-1]))
    return positions, top > -jnp.inf


def gated_mlp(x, wi, wo):
    """``wo (silu(gate x) * up x)``, gate and up side by side in ``wi``; operands in
    ``x``'s type, the last product summed and handed back in float32."""
    f = wo.shape[0]
    gate_up = x @ wi.astype(x.dtype)
    return jnp.dot(
        jax.nn.silu(gate_up[..., :f]) * gate_up[..., f:], wo.astype(x.dtype),
        preferred_element_type=jnp.float32)


#: copies of a read row that go through the head under :func:`read_rows`' ``cond``: a
#: sublane tile. A product of one row the TPU compiler turns into a multiply-reduce over
#: the kernel, and under a conditional it first re-lays out a kernel that the runtime
#: keeps columns-major (a vocabulary that is no whole number of the chip's 128 lanes:
#: MiniCPM-SALA's 73,448, GPT-J's 50,400): 600 MB copied, and held, by every call whose
#: head runs (2.65 ms where all 512 rows took 1.70; ``PERF.md``, PR 57). Eight rows stay
#: a matmul, which reads the kernel once as it lies (0.82 ms)
READ_TILE = 8


def read_rows(x, last, head):
    """``head`` (the last norm and the head: ``(logits, hidden)`` of rows ``[b, n, d]``
    of the residual stream) of the rows of ``x`` [b, tc, d] that are read.
    Without ``last``: every row, ``[b, tc, ...]`` each (a caller that compares all
    positions). With ``last`` [b] (the serve engine, always): lane ``i``'s row
    ``last[i]`` alone, picked before the norm, ``[b, vocab]`` and ``[b, d]``; a negative
    ``last[i]`` says nobody reads lane ``i``, and where that is every lane of a chunk
    (a prompt's chunks but its last) ``head`` does not run and both are zeros: decided
    on the device from the operand, in the one program the shape has. A call of one
    token a lane is the decode call's: each lane's row is read, whatever ``last`` says,
    and nothing is decided."""
    if last is None:
        return head(x)
    if x.shape[1] == 1:
        return tuple(out[:, 0] for out in head(x))
    rows = jnp.take_along_axis(x, jnp.maximum(last, 0)[:, None, None], axis=1)

    def read(rows):
        tile = jnp.broadcast_to(rows, (rows.shape[0], READ_TILE, rows.shape[2]))
        return tuple(out[:, 0] for out in head(tile))

    return jax.lax.cond(
        (last >= 0).any(), read,
        lambda rows: tuple(jnp.zeros(o.shape, o.dtype) for o in jax.eval_shape(read, rows)),
        rows)


def rms_head(x, scale, eps, kernel, dtype, last=None):
    """The last norm and an untied head, of the rows :func:`read_rows` reads:
    ``(logits, normed hidden)``, both float32."""
    def head(rows):
        rows = rms_norm(rows, scale, eps)
        return jnp.dot(
            rows.astype(dtype), kernel.astype(dtype), preferred_element_type=jnp.float32), rows

    with jax.named_scope("extend.logits"):
        return read_rows(x, last, head)


def normal(key, shape, dtype):
    """A seeded weight: normal with stddev 0.02, drawn in the type it is served in
    (no float32 copy of gigabytes)."""
    return jax.random.normal(key, shape, dtype) * jnp.asarray(0.02, dtype)


def drawn(keys, shapes, dtype):
    """``{name: normal}`` for ``shapes`` ``{name: shape}``, a key each in their order."""
    return {name: normal(key, shape, dtype) for (name, shape), key in zip(shapes.items(), keys)}


def ones_scale(dtype, *shape):
    """A norm's parameters: a scale of ones."""
    return {"scale": jnp.ones(shape, dtype)}
