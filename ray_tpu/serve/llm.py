"""LLM inference engine on the serve plane: paged KV-cache, prefill/decode
split, prefix caching, and LoRA-scale multiplexing over a real model's
forward pass: ``ray_tpu.models.gpt``, or any architecture whose configuration
answers what the engine asks of it (``make_extend_fn()``, ``init_params(seed)``
and ``cache_arrays``: what a cached token holds, as ``(heads, dim)`` per array,
or ``(heads, dim, tokens a row)`` for an array kept at a coarser grain: a row for
every so many tokens, which belongs to the last of them (a compressed key);
beside the sizes ``num_layers``, ``embed_dim``, ``vocab_size``, ``max_seq_len``
and ``dtype``; where its ``extend`` counts something, ``counters`` names what;
where it keeps state per sequence and not per token, ``state_arrays`` names
that, ``state_chunk`` how often a state can be kept, and ``cache_layers`` in how
many layers a token is cached (``cached_layers``: in which); ``models/cohere2_moe.py``,
``models/keye_vl2.py``, ``models/kimi_k2.py``, ``models/granitemoehybrid.py``,
``models/minicpm_sala.py``, ``models/mimo_v2_flash.py``, ``models/qwen3_next.py``,
``models/glm_moe_dsa.py``: two arenas a token, a latent row all heads share and an
indexer's key, of which a query reads the rows its indexer selects; nothing new in this
file; ``models/longcat_flash.py``: a layer of two attention sub-blocks, so a token is
cached in **more** slabs than the model has layers, ``cache_layers`` = 2 x ``num_layers``,
and the pool's arenas, the gathers and the page-back have that many: nothing new here
either). A state need not be a recurrence's: ``mimo_v2_flash.py``'s is made of **cached rows**,
the newest 128 rows of K and V of each layer that sees a window and nothing else, which
the engine keeps, hands over, snapshots and restores as it does any state, and never
pages. Nor need a recurrence be additive: ``qwen3_next.py``'s gated delta rule writes
what its state does not hold yet for the key, a 2 MB state a layer and sequence beside a
convolution's tail, through the same slots, snapshots and restores and nothing new in
this file; its cell is the first to decode sixteen lanes a call (``lane_buckets`` up to
16 is this engine's own default, ``LANE_BUCKETS``: a deployment takes that many executing
slots from :meth:`LLMServer.concurrent_queries`).

What PR 9 proved with synthetic step functions (continuous batching,
admission control, multiplexing) this module composes on an actual model
(reference: serve/llm + vLLM's paged attention, and the Gemma-on-TPU
serving setup from PAPERS.md):

* :class:`KVBlockPool` — the KV cache is paged into fixed-size token
  blocks in arenas that live on the device, one for each array a cached token
  holds (K and V; an indexer's keys beside them; or no K and V at all but
  one latent and one rotary key for all heads); sequences lease blocks on
  admission and a :class:`KVLease` frees them **exactly once** on finish /
  cancel / shed / step poison (the same accounting discipline the handle
  enforces for concurrency slots). ``ray_tpu_llm_kv_blocks_in_use`` tracks
  the pool; exhaustion sheds with :class:`~ray_tpu.serve.handle.
  BackPressureError` *before* anything is written.
* a device call never moves K/V through the host, and crosses the link
  twice: everything the host decides for the call (tokens, lengths, block
  table, page-back slots, the row of logits each lane reads) goes up as one
  int32 buffer (:func:`_sections`); one jitted program gathers the padded
  caches ``extend`` takes from the arenas (not for a decode call of a model
  whose ``extend`` reads a lane's pages where the pool keeps them, below);
  ``extend`` runs, and makes the last
  norm, the head and the float32 hidden row for **the one row a lane that is
  read** (``_LAST``: a lane's last valid token where the lane emits; a chunk in
  which no lane does, as a prompt's chunks but its last, runs no head at all:
  ``models/layers.read_rows``), so it hands back ``[lanes, vocabulary]`` and
  ``[lanes, d]`` whatever the tokens; and one donating program pages the new
  rows back into the arenas and takes the argmax of each lane's row: the
  sampled ids (and an expert layer's counters) are one int32 array, which is
  all that comes home. The rows follow only for a lane that asked for its
  logits or has an adapter. Every such program is compiled when the engine is
  built.
* a decode call attends through the block table — where a model's ``extend``
  offers ``table=`` (:func:`reads_pages`: ``models/mimo_v2_flash.py``,
  ``models/qwen3_next.py``, ``models/granitemoehybrid.py``, ``models/kimi_k2.py`` and
  ``models/longcat_flash.py``, whose pages hold latent rows: key and value in one
  arena), a call of one token
  a lane is handed the pool's arenas themselves (not donated: the page-back
  donates them after, in launch order) and the ``table`` section of the operand
  buffer it already uploads, and runs no gather: each full-attention layer reads a
  lane's live pages once where they lie (``ops/attention.paged_attention``), the
  call's own row beside them, and nothing of the call grows with ``lanes x
  bucket`` but the grid's dead steps. Same programs' names
  (``extend_decode_<b>x1x<cap>``); ``llm.dispatch`` and
  ``stats()["calls"][form]`` say ``paged``. A chunk gathers as ever.
* every program under a name of its own — JAX names a compiled module after
  the function it was traced from, and an instruction's name is unique in its
  module only, so one ``jax.jit`` run in sixteen shapes is sixteen modules a
  profile cannot tell apart. The engine's are families
  (``accelerator.Programs``): a ``jax.jit`` a name, and the name made of the
  sizes that shape the program where it is called: ``extend_decode_8x1x8192`` /
  ``extend_prefill_1x256x8192`` (lanes x tokens x cache; :func:`_extend_name`),
  ``gather_8x8192`` (lanes x cache), ``page_back_8x1`` (lanes x tokens;
  :class:`KVBlockPool`), ``clone``, ``state_copy``. No name has a dot: it is a
  component of every ``op_name`` of its program, where a dot marks a scope.
* the chip never waits for the host between calls: a call is *launched*
  (upload, gather, ``extend``, page-back: nothing waits for the device) and
  *landed* a call later (its ids fetched, its tokens emitted), once its
  successor has been launched. The array a call leaves for the host is the
  next call's argument too: it reads a lane's last token from it on the
  device (``_FROM`` in the operand buffer), so one call is in flight whenever
  there is work (:class:`LLMEngine`; ``calls_ahead``, ``tokens_fed_on_device``).
* prefill/decode split — prefill runs as its own bucketed extend call
  (prompt chunks padded via :func:`~ray_tpu.serve.batching.
  bucket_pad_size`), decode as a tc=1 call; every engine iteration runs
  at most one prefill chunk *and* one decode step, so a long prompt can
  never stall in-flight decode lanes for more than one bounded chunk.
* prefix caching — full prompt blocks are keyed by a rolling (chained)
  hash; a new request reuses the longest cached chain copy-on-write
  (shared blocks are refcounted and cloned before any write), skipping
  their prefill FLOPs entirely. Reused KV is bitwise-identical to a
  fresh prefill because the extend fn is deterministic per shape.
* state per sequence — a recurrent layer leaves nothing behind per token but
  one state per sequence (and a layer that sees a window only, the window's
  rows: a state of a fixed size too). The pool holds slots of it beside the block arenas
  (``state_arrays``): a sequence takes one at admission and gives it back with
  its lease. Nothing copies a state into or out of a call: ``extend`` is handed
  the state arenas themselves (donated, and the ones it returns take their
  place, as ``page_back`` treats the block arenas) and each lane's slot
  (``extend(params, tokens, lengths, *caches, *states, slots, snap_at,
  snap_slots)``), reads a lane's state where the pool keeps it and writes the
  new one to the same place, on the device and in launch order, so a lane's
  state follows its token across the call in flight. A lane that ended in the
  call in flight has had its slot advanced once more by the call behind it: the
  slot is free by then, and its next owner starts from zeros or from a copy
  launched later. Cached blocks are only as good as the state at their end:
  ``extend`` writes the state at the block boundary the engine names (the
  reusable end of the prompt, as a rule inside its last chunk) to a slot of its
  own, the prefix cache keeps that as a snapshot with the chain, copies it to
  the sequence's slot on a hit (the one copy of a state the engine makes:
  ``state_bytes_moved``) and drops it with the chain on eviction.
* LoRA multiplexing — base weights load once per replica; per-model
  low-rank logit deltas ``(A [d,r], B [r,vocab])`` are registered on the
  object plane via :func:`ray_tpu.serve.register_model` and streamed to
  replicas on miss through the PR 9 multiplex LRU, so thousands of model
  ids share one resident base model.
* phases — every part of an engine step runs inside ``LLMEngine._phase``:
  a span ``llm.<phase>`` on the profiler's clock (recorded while a
  profiler session runs in the replica, so a device idle gap names the
  host phase under it; ``accelerator.span``) and, always, wall time and counts that
  ``stats()`` / ``kv_stats`` return beside the bytes, lanes and cache
  slots each device call moved.
* a record per device call — ``llm.dispatch`` says what the call is (its
  number, ``prefill`` or ``decode``, the ``program`` it runs as: the name of
  the module the device then shows, its lanes, tokens and cache tokens
  beside the slots of its padded shape, ``heads``: the lanes whose row of
  logits is read, 0 where the head did not run; ``paged``: 1 where it read its
  lanes' pages through the block table and ran no gather; whether a call was in
  flight) and
  ``llm.fetch`` which call it lands and what ``extend`` counted in it; always
  on, ``stats()["calls"]`` sums the same per form of call, with the time the
  device spent on each (``busy_s``), and ``stats()["programs"]`` the calls and
  that time per program. A program first called inside traffic (a shape
  ``warm()`` left out) says ``cold=1`` on its span and is counted, with the
  seconds its first call took, in ``programs_cold`` / ``programs_cold_s``:
  which step compiled, and for how long.
* the counters a second time, over recorded steps — while a profiler session
  runs, ``stats()["traced"]`` takes every counter of every step that begins
  and ends in it, a call's own counts with the step that launched it: a trace
  taken in a live replica comes with the exact work of the steps it holds.
"""

from __future__ import annotations

import contextlib
import copy
import functools
import hashlib
import inspect
import math
import queue as queue_mod
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu._private import accelerator, internal_metrics
from ray_tpu.serve import batching
from ray_tpu.serve.handle import BackPressureError
from ray_tpu.serve.multiplex import _MultiplexWrapper

__all__ = [
    "KVBlockPool", "KVLease", "NoKVBlocksError", "PrefixCache",
    "LLMEngine", "LLMServer", "live_engines", "make_params", "register_lora", "random_lora",
]

_STREAM_KEY = "_stream"
_CANCEL_KEY = "_cancel"

#: the lanes a decode call is padded to where an engine is given no buckets
LANE_BUCKETS = (1, 2, 4, 8, 16)
#: the sequences one ``LLMServer.generate`` step is handed: a decode call's most lanes
MAX_LANES = 16

_LIVE: "weakref.WeakSet[LLMEngine]" = weakref.WeakSet()


def live_engines() -> List["LLMEngine"]:
    """The engines alive in this process, for a probe that runs beside one and
    is not handed it: a benchmark's plain reference reads the state a replica
    holds back through here (:meth:`LLMEngine.held_snapshot`,
    :attr:`LLMEngine.last_finished`, :meth:`KVBlockPool.read_state`)."""
    return list(_LIVE)


class NoKVBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation even after evicting every
    idle prefix-cache block — the admission-control signal."""


def make_params(cfg=None, seed: int = 0):
    """Deterministically initialized params of ``cfg``'s architecture
    (default ``gpt_nano``) — every replica builds bitwise-identical base
    weights from the same seed."""
    from ray_tpu.models import gpt

    return (cfg or gpt.gpt_nano()).init_params(seed)


# ---------------------------------------------------------------------------
# paged KV block pool + exactly-once lease
# ---------------------------------------------------------------------------


#: A device call's small operands, one int32 buffer ``[lanes, width]``: a lane's
#: row holds its cache length, the index of the fed token whose row of logits
#: is read (its last, where the lane emits; -1 where nobody reads one: a lane
#: whose prompt goes on, a padded lane), (lane 0 alone)
#: the count of tokens to page back and where its first token is (its lane in
#: the call before, whose ids are still on the device; -1: the host knew it and
#: it stands in the buffer), then four sections of one width (:func:`_sections`).
#: The width is the engine's, whatever the call's buckets: a program is shaped
#: by the lanes and by its own bucket, as it was.
_LENGTH, _LAST, _COUNT, _FROM, _SCALARS = 0, 1, 2, 3, 4
#: Behind the sections, for a configuration with per-sequence state alone
#: (fewer than four, so :func:`_sections` cuts the same with them or without):
#: the lane's state slot, after how many of the call's tokens ``extend`` is to
#: keep a state for the prefix cache (0: none), and the slot that state goes to
#: (0, nobody's, where none). Counted from the buffer's end.
_SLOT, _SNAP_AT, _SNAP_SLOT, _STATE_COLUMNS = -3, -2, -1, 3


def cache_grain(each) -> int:
    """Tokens a row of one entry of a configuration's ``cache_arrays``: its third
    element where it has one (an array kept at a coarser grain), else 1."""
    return each[2] if len(each) > 2 else 1


def _operand_width(tokens: int, blocks: int, stateful: bool = False) -> int:
    """The width of the operand buffer of an engine whose widest call feeds
    ``tokens`` a lane over a cache of ``blocks`` blocks."""
    return _SCALARS + 4 * max(tokens, blocks) + (_STATE_COLUMNS if stateful else 0)


def _sections(operands):
    """``tokens``, ``rows``, ``slots``, ``table`` of an operand buffer (numpy
    on the host: views to write through; traced on the device): the token ids
    a lane is fed (-1 is padding), the page-back's token rows and arena slots
    (flat over lanes x tokens, laid ``[lanes, tokens]``), the lane's block ids.
    A call with ``tc`` tokens over ``n`` blocks uses ``[:, :tc]`` and ``[:, :n]``."""
    each = (operands.shape[1] - _SCALARS) // 4
    return tuple(
        operands[:, _SCALARS + i * each:_SCALARS + (i + 1) * each] for i in range(4))


@functools.lru_cache(maxsize=None)
def _paging_programs():
    """The three jitted programs that touch a pool's arenas, a tuple of arrays
    ``[layers, num_blocks, block_size, heads, dim]`` that differ in their last
    two sizes alone; jax is imported here because processes that must stay off
    it load this module too. They are shaped by their arguments alone, so every
    pool of a process shares them. ``gather`` and ``page_back`` are families
    (``accelerator.Programs``): called with the program's name first, which the
    pool makes of the sizes that shape it (``gather_<lanes>x<cache>``,
    ``page_back_<lanes>x<tokens>``; :meth:`KVBlockPool.gather`), so that each
    compiled module has a name of its own in a profile. Two pools of one
    process whose arenas differ share a name where those sizes agree: one
    ``jax.jit`` holds both programs then, which the name cannot tell apart.
    ``clone`` has one shape and one name."""
    import types

    import jax
    import jax.numpy as jnp

    # Both loops move one slab at a time with a dynamic slice and an in-place
    # dynamic update. Written as ``arena[:, table]`` / ``.at[:, slots].set``
    # the TPU compiler first copies a whole arena into a temporary, on every
    # call: 1.17 GB at GPT-J's serve sizes, where 1 GB is free beside the
    # weights (``tests/test_chip_compile_serve.py`` holds the programs to this).

    def lies_tokens_last(a):
        """Whether the runtime lays the arena out with a block's tokens along the
        lanes: rows that are no whole number of the chip's 128 lanes (an
        indexer's key: 1 x 64). Both programs then move and write it as it
        lies, ``[layers, blocks, heads, dim, block]``."""
        return bool(a.shape[-1] % 128)

    @functools.partial(accelerator.Programs, static_argnums=2)
    @jax.named_scope("paging.gather")
    def gather(arenas, operands, n):
        layers, blocks, block = arenas[0].shape[:3]
        b = operands.shape[0]
        flat = _sections(operands)[3][:, :n].reshape(-1)
        # an arena that lies with its tokens last (``lies_tokens_last``), and so
        # its padded cache, are moved as they lie, a block's slab to its place
        # along the cache's tokens (moved as ``[.., block, heads, dim]`` the
        # compiler pads every row to the lanes inside the loop and holds the
        # padded copy, eight times the cache; moved as one flat row a block it
        # re-laid the whole arena out twice on every call, 2 x 100 MB at Keye's
        # sizes, and the cache once more). K and V are moved as they lie;
        # an arena of one wide row a token (a latent's: 1 x 640) without its
        # heads axis, which the compiler lays out behind the block's tokens: a
        # block moved with that axis in place leaves the loop in another
        # layout than the caches have, and the copy is twice the caches.
        def as_moved(a):
            if lies_tokens_last(a):
                return a.transpose(0, 1, 3, 4, 2)
            return a.reshape(a.shape[:3] + a.shape[4:]) if a.shape[3] == 1 else a

        narrow = tuple(map(lies_tokens_last, arenas))
        sources = tuple(as_moved(a) for a in arenas)
        # every block of the caches is written below; one buffer each, because
        # the compiler copies a value that starts two loop carries
        empty = tuple(
            jax.lax.empty(
                (layers, b) + a.shape[2:4] + (n * a.shape[4],) if lies
                else (layers, b * n) + a.shape[2:], a.dtype)
            for a, lies in zip(sources, narrow))

        def copy_block(i, caches):
            zero = jnp.zeros((), flat.dtype)
            return tuple(
                jax.lax.dynamic_update_slice(
                    out, jax.lax.dynamic_slice_in_dim(arena, flat[i], 1, axis=1),
                    (zero, i // n, zero, zero, i % n * arena.shape[4])) if lies
                else jax.lax.dynamic_update_slice_in_dim(
                    out, jax.lax.dynamic_slice_in_dim(arena, flat[i], 1, axis=1),
                    i, axis=1)
                for out, arena, lies in zip(caches, sources, narrow))

        caches = jax.lax.fori_loop(0, b * n, copy_block, empty)
        # an arena at a coarser grain (a row for every so many tokens) has fewer
        # rows a block, and its padded cache as many fewer
        return tuple(
            c.transpose(0, 1, 4, 2, 3) if lies
            else c.reshape((layers, b, n * a.shape[2]) + a.shape[3:])
            for c, a, lies in zip(caches, arenas, narrow))

    @functools.partial(accelerator.Programs, donate_argnums=0, static_argnums=5)
    @jax.named_scope("paging.page_back")
    def page_back(arenas, news, operands, logits, counted, width):
        layers, blocks, block = arenas[0].shape[:3]
        b, tc = news[0].shape[1:3]
        news = tuple(x.reshape((layers, b * x.shape[2]) + x.shape[3:]) for x in news)
        rows, slots = (x[:, :tc].reshape(-1) for x in _sections(operands)[1:3])
        # tokens a row of each arena: 1, but for one kept at a coarser grain
        grains = tuple(block // a.shape[2] for a in arenas)
        narrow = tuple(lies_tokens_last(a) and grain == 1 for a, grain in zip(arenas, grains))

        def write_coarse(i, tokens, new, grain):
            """An arena with one row for every ``grain`` tokens: the row belongs to
            the last of them, so only a token that ends a group writes one, to
            slot ``slots[i] // grain``. ``new`` holds a lane's rows in the order of
            their tokens: the one this token ends is as far in as groups end
            between the lane's first token and this one."""
            lane, at = rows[i] // tc, rows[i] % tc
            length = operands[lane, _LENGTH]
            source = lane * (new.shape[1] // b) + (length + at + 1) // grain - length // grain - 1
            to = slots[i] // grain
            return jax.lax.dynamic_update_slice_in_dim(
                tokens, jnp.where(
                    (slots[i] + 1) % grain == 0,
                    jax.lax.dynamic_slice_in_dim(new, jnp.maximum(source, 0), 1, axis=1),
                    jax.lax.dynamic_slice_in_dim(tokens, to, 1, axis=1)),
                to, axis=1)

        def write_narrow(i, arena, new):
            """A token's row into an arena that lies with its tokens last
            (``lies_tokens_last``): flat over its blocks the program re-laid all of it
            out on the way in and again on the way out (2 x 100 MB at Keye's
            sizes, for one row written: 0.9 ms of every call). The token's block
            is read, the row put in its place along the lanes, and written back
            (a row handed to the update as the model leaves it, dim innermost,
            makes the compiler re-lay the arena out to match the row)."""
            row = jax.lax.dynamic_slice_in_dim(new, rows[i], 1, axis=1)     # [layers, 1, heads, dim]
            at, offset = slots[i] // block, slots[i] % block
            held = jax.lax.dynamic_slice_in_dim(arena, at, 1, axis=1)       # the token's block
            held = jnp.where(jnp.arange(block) == offset, row[..., None], held)
            return jax.lax.dynamic_update_slice_in_dim(arena, held, at, axis=1)

        def write_token(i, tokens_of):
            return tuple(
                write_coarse(i, tokens, new, grain) if grain > 1
                else write_narrow(i, tokens, new) if lies
                else jax.lax.dynamic_update_slice_in_dim(
                    tokens, jax.lax.dynamic_slice_in_dim(new, rows[i], 1, axis=1),
                    slots[i], axis=1)
                for tokens, new, grain, lies in zip(tokens_of, news, grains, narrow))

        # the count is traced: a loop the compiler cannot unroll, whatever the
        # shapes (unrolled at one token it re-lays the arenas out and back)
        written = jax.lax.fori_loop(0, operands[0, _COUNT], write_token, tuple(
            a.transpose(0, 1, 3, 4, 2) if lies
            else a.reshape((layers, blocks * a.shape[2]) + a.shape[3:])
            for a, lies in zip(arenas, narrow)))
        with jax.named_scope("paging.sample"):
            # greedy, as ``np.argmax`` is: the first of equal maxima
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # the next call reads a lane's token here too: the ids have one
            # width whatever the lanes, so ``extend`` has no program more for it
            home = jnp.concatenate([
                jnp.pad(ids, (0, width - b)), *(c.astype(jnp.int32) for c in counted)])
        return tuple(
            w.transpose(0, 1, 4, 2, 3) if lies else w.reshape(a.shape)
            for w, a, lies in zip(written, arenas, narrow)), home

    @functools.partial(jax.jit, donate_argnums=0)
    @jax.named_scope("paging.clone")
    def clone(arenas, src, dst):
        def copy(arena):
            one = jax.lax.dynamic_slice_in_dim(arena, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(arena, one, dst, axis=1)

        return tuple(copy(a) for a in arenas)

    return types.SimpleNamespace(gather=gather, page_back=page_back, clone=clone)


@functools.lru_cache(maxsize=None)
def _state_programs():
    """The one jitted program beside ``extend`` that touches a pool's state
    arenas, a tuple of arrays ``[layers, slots, ...]``: what a sequence holds
    where a model keeps state per sequence and not per token. As
    :func:`_paging_programs`: one dynamic slice and one in-place update, shaped
    by its arguments alone. (A call's lanes' states are read and written by
    ``extend`` itself, where they lie.)"""
    import types

    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    @jax.named_scope("paging.state_copy")
    def state_copy(arenas, src, dst):      # the module's name in a profile
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                a, jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1), dst, axis=1)
            for a in arenas)

    return types.SimpleNamespace(copy=state_copy)


class KVBlockPool:
    """Fixed-size token blocks of per-token state in refcounted arenas that
    live on the device, one for each array a cached token holds.

    Layout: ``arenas`` is a tuple of device arrays ``[layers, num_blocks,
    block_size, heads, dim]`` in the model's dtype, one per entry ``(heads,
    dim)`` of the configuration's ``cache_arrays``: K and V over the heads a
    cache stores (fewer than the query heads where attention is grouped), and
    whatever else the model leaves behind for a token (an indexer's key). An
    entry with a third element, ``(heads, dim, tokens a row)``, is kept at that
    grain (``grains``): its arena is ``[layers, num_blocks, block_size // tokens
    a row, heads, dim]``, a row for every so many tokens, which belongs to the
    page of the last of them (a compressed key made of keys that may begin in
    the page before): ``extend`` hands back the rows whose last token a call
    brings, in the order of their tokens, the page-back writes each with that
    token, and gather, clone, sharing and eviction carry it with its page. A
    sequence owns an
    ordered list of block ids whose concatenation is its cache, so the
    blocks a table names, side by side, are the padded caches ``extend``
    takes. Blocks are refcounted so the prefix cache can share full prompt
    blocks across sequences; a block returns to the free list when its last
    reference drops. Allocation, refcounts and leases are host bookkeeping
    and per block, whatever a block holds;
    the arenas are only ever touched by the three programs of
    :func:`_paging_programs` (``page_back`` and ``clone`` donate them), each
    compiled by :meth:`warm` before a request is served.

    Where a model keeps state per **sequence** (a recurrent layer's; the
    configuration's ``state_arrays``, ``(layers, shape, dtype)`` each), the pool
    holds that too: ``states``, one device array ``[layers, state_slots, ...]``
    each, a slot a sequence and a slot a snapshot the prefix cache keeps, under
    the same lock, leases and shedding as the blocks, touched by the model's
    ``extend`` (which is handed them, donated, with its lanes' slots, and whose
    returned arenas take their place) and the copy of :func:`_state_programs`
    alone. Slot 0 is never handed out: a padded lane's. And where its tokens are
    cached in some layers only, or in several places a layer (``cache_layers``: fewer
    than ``num_layers``, or more), the arenas hold that many slabs."""

    def __init__(self, cfg, *, num_blocks: int = 128, block_size: int = 16,
                 state_slots: int = 0, deployment: str = "llm"):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.deployment = deployment
        self.dtype = jnp.dtype(jnp.float32 if cfg.dtype is None else cfg.dtype)
        self.layers = getattr(cfg, "cache_layers", cfg.num_layers)
        self.state_arrays = tuple(getattr(cfg, "state_arrays", ()))
        self.state_slots = int(state_slots) if self.state_arrays else 0
        #: tokens a row of each arena: an entry of ``cache_arrays`` with a third
        #: element is kept at that grain (a row for every so many tokens, which
        #: belongs to the last of them), any other has a row a token
        self.grains = tuple(map(cache_grain, cfg.cache_arrays))
        if any(self.block_size % grain for grain in self.grains):
            raise ValueError(
                f"blocks of {self.block_size} tokens do not hold whole rows of "
                f"{self.grains} tokens: {cfg.cache_arrays}")
        #: the bytes one sequence's state takes, over all layers and arrays
        self.state_bytes = sum(
            layers * math.prod(shape) * jnp.dtype(dtype).itemsize
            for layers, shape, dtype in self.state_arrays)
        try:
            self.arenas = tuple(
                jnp.zeros(
                    (self.layers, self.num_blocks, self.block_size // grain) + tuple(each[:2]),
                    self.dtype)
                for each, grain in zip(cfg.cache_arrays, self.grains))
            self.states = tuple(
                jnp.zeros((layers, self.state_slots) + tuple(shape), dtype)
                for layers, shape, dtype in self.state_arrays)
            jax.block_until_ready((self.arenas, self.states))
        except Exception as e:  # noqa: BLE001 — the runtime's out-of-memory
            raise MemoryError(
                f"the KV pool does not fit on the device: {self.num_blocks} "
                f"blocks of {self.block_size} tokens x {self.layers} layers "
                f"x {cfg.cache_arrays} (heads, dim) an array in {self.dtype} are "
                f"{self.cache_bytes(self.num_blocks * self.block_size)} bytes, and "
                f"{self.state_slots} slots of state {self.state_slots * self.state_bytes}, "
                f"beside {accelerator.device_report()}: {e!r}"
            ) from e
        self._free: List[int] = list(range(self.num_blocks))
        self._ref: Dict[int, int] = {}
        self._free_slots: List[int] = list(range(self.state_slots - 1, 0, -1))
        self._lock = threading.RLock()
        self._evict_cb: Optional[Callable[[int], None]] = None
        self._evict_slot_cb: Optional[Callable[[], bool]] = None
        self.freed_total = 0

    @property
    def k_data(self):
        """The first arena (K), for a reader of the pool's layout."""
        return self.arenas[0]

    @property
    def v_data(self):
        return self.arenas[1]

    def cache_bytes(self, tokens: int) -> int:
        """The bytes ``tokens`` cached tokens take, over all layers and arrays."""
        values = sum(
            each[0] * each[1] * (tokens // grain)
            for each, grain in zip(self.cfg.cache_arrays, self.grains))
        return self.layers * values * self.dtype.itemsize

    # -- the arenas: device programs only ----------------------------------

    def gather(self, operands, n: int):
        """The padded caches ``[layers, b, n * block_size, heads, dim]``, one
        per arena, of
        the lanes whose block ids are the first ``n`` of each row of the
        ``table`` section of ``operands`` (a device array ``[b, width]``,
        :func:`_sections`; every entry a valid block), built on the device."""
        return _paging_programs().gather(
            f"gather_{operands.shape[0]}x{n * self.block_size}", self.arenas, operands, n)

    def page_back(self, news, operands, logits, counted, width: int):
        """Write token ``rows[i]`` (an index into lanes x tokens) of each of
        ``news`` ``[layers, b, tc, heads, dim]`` (one per arena) into its arena at
        token slot ``slots[i]`` (block x block_size + offset) for the first
        ``count`` entries of the ``rows`` / ``slots`` sections of ``operands``
        (of an arena at a coarser grain, ``[layers, b, ceil(tc / grain), heads,
        dim]``: the row a token ends, where it ends one, into slot ``slots[i] //
        grain``) and, in the same program, sample each lane's row of ``logits``
        ``[b, vocab]``: the one row a lane ``extend`` made, the row the lane reads
        (zeros for a lane that reads none: its id is never read either). Returns
        one int32 array, for the host and for the next call to read on the
        device (the ``b`` greedy ids padded to ``width``, then the int32
        arrays of ``counted``), on the device. The arenas are donated: nothing
        is copied but the new rows."""
        lanes, tokens = news[0].shape[1:3]
        self.arenas, home = _paging_programs().page_back(
            f"page_back_{lanes}x{tokens}",
            self.arenas, tuple(news), operands, logits, tuple(counted), width)
        return home

    def clone_block(self, src: int, dst: int) -> None:
        """Copy block ``src`` onto block ``dst``, on the device."""
        self.arenas = _paging_programs().clone(
            self.arenas, np.int32(src), np.int32(dst))

    def copy_state(self, src: int, dst: int) -> None:
        """Copy slot ``src`` onto slot ``dst``, on the device."""
        self.states = _state_programs().copy(self.states, np.int32(src), np.int32(dst))

    def read_block(self, b: int):
        """Block ``b`` on the host: one array ``[layers, block_size, heads,
        dim]`` per arena (K, V, ...). For tests and debugging, not for the step
        path (eager indexing compiles)."""
        return tuple(np.asarray(a[:, b]) for a in self.arenas)

    def read_state(self, slot: int):
        """State slot ``slot`` on the host: one array ``[layers, ...]`` per entry
        of ``state_arrays``, in the dtype the pool keeps it in. Like
        :meth:`read_block`, for tests and probes and not for the step path."""
        return tuple(np.asarray(a[:, slot]) for a in self.states)

    def warm(self, extend_shapes: Dict[Any, Any], cache_buckets, gathered=None) -> None:
        """Compile every paging program the engine's buckets allow, on zeros
        made on the device: the gather per (lanes, cache bucket), for the
        lanes in ``gathered`` where given (an engine whose decode calls read
        pages gathers for its chunks' lanes alone), the
        page-back per (lanes, tokens) of ``extend_shapes`` (that extend
        call's output shapes), the clone; where the pool holds states, their
        copy. The page-back writes no token here, so the arenas keep their
        contents."""
        import jax
        import jax.numpy as jnp

        width = _operand_width(
            max(tc for _, tc in extend_shapes),
            max(cache_buckets) // self.block_size, bool(self.states))
        lanes = max(b for b, _ in extend_shapes)

        def zeros(shapes):
            return tuple(jnp.zeros(x.shape, x.dtype) for x in shapes)

        for b in sorted({b for b, _ in extend_shapes}) if gathered is None else gathered:
            operands = jnp.zeros((b, width), jnp.int32)
            for cap in cache_buckets:
                jax.block_until_ready(
                    self.gather(operands, cap // self.block_size))
        for (b, tc), (logits, _, *rest) in extend_shapes.items():
            news, _, counted = self.split_outputs(rest)
            operands = jnp.zeros((b, width), jnp.int32)
            jax.block_until_ready(self.page_back(
                zeros(news), operands, jnp.zeros(logits.shape, logits.dtype), zeros(counted),
                lanes))
        self.clone_block(0, 0)
        if self.states:
            self.copy_state(0, 0)
        jax.block_until_ready((self.arenas, self.states))

    def split_outputs(self, rest):
        """What ``extend`` returns behind the logits and the hidden rows, apart:
        its caches' new rows, the state arenas (where the pool holds any) and
        what it counted."""
        rows, states = len(self.arenas), len(self.arenas) + len(self.states)
        return tuple(rest[:rows]), tuple(rest[rows:states]), tuple(rest[states:])

    # -- host bookkeeping ---------------------------------------------------

    def set_evict_cb(self, cb: Callable[[int], None]) -> None:
        """Hook called (under the pool lock) with the shortfall when an
        allocation would fail — the prefix cache drops idle entries here."""
        self._evict_cb = cb

    def allocate(self, n: int) -> List[int]:
        with self._lock:
            if len(self._free) < n and self._evict_cb is not None:
                self._evict_cb(n - len(self._free))
            if len(self._free) < n:
                raise NoKVBlocksError(
                    f"need {n} KV blocks, {len(self._free)} free "
                    f"of {self.num_blocks}"
                )
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
            self._gauge_locked()
            return out

    def set_evict_slot_cb(self, cb: Callable[[], bool]) -> None:
        """Hook called (under the pool lock) when no state slot is free: the
        prefix cache drops a snapshot here, and says whether it had one."""
        self._evict_slot_cb = cb

    def take_slot(self) -> int:
        """A state slot, the prefix cache's oldest snapshot's where none is
        free; like a block, it goes back through a lease or :meth:`free_slot`."""
        with self._lock:
            if not self._free_slots and self._evict_slot_cb is not None:
                self._evict_slot_cb()
            if not self._free_slots:
                raise NoKVBlocksError(
                    f"need a state slot, none free of {self.state_slots - 1}")
            return self._free_slots.pop()

    def free_slot(self, slot: int) -> None:
        with self._lock:
            self._free_slots.append(slot)

    def slots_in_use(self) -> int:
        with self._lock:
            return max(self.state_slots - 1, 0) - len(self._free_slots)

    def incref(self, blocks: Sequence[int]) -> None:
        with self._lock:
            for b in blocks:
                self._ref[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        with self._lock:
            for b in blocks:
                self._decref_locked(b)
            self._gauge_locked()

    def _decref_locked(self, b: int) -> None:
        r = self._ref.get(b)
        if r is None:
            return
        if r <= 1:
            del self._ref[b]
            self._free.append(b)
            self.freed_total += 1
        else:
            self._ref[b] = r - 1

    def refcount(self, b: int) -> int:
        with self._lock:
            return self._ref.get(b, 0)

    def in_use(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    def ensure_private(self, blocks: List[int], idx: int) -> int:
        """Copy-on-write: make ``blocks[idx]`` safe to mutate. A block
        shared with the prefix cache (or another sequence) is cloned into
        a fresh block — in place in the caller's block list, which the
        owning lease aliases — and the shared original is decrefed."""
        with self._lock:
            b = blocks[idx]
            if self._ref.get(b, 0) <= 1:
                return b
            new = self.allocate(1)[0]
            self.clone_block(b, new)
            self._decref_locked(b)
            blocks[idx] = new
            self._gauge_locked()
            return new

    def _gauge_locked(self) -> None:
        internal_metrics.set_gauge(
            "ray_tpu_llm_kv_blocks_in_use",
            self.num_blocks - len(self._free),
            {"deployment": self.deployment},
        )


class KVLease:
    """Exactly-once ownership of a sequence's KV blocks (the KV analogue
    of ``DeploymentResponse._finish_once``): however many of finish, fail,
    cancel-drop, step-poison and shutdown fire for one sequence, the
    blocks are decrefed once."""

    def __init__(self, pool: KVBlockPool):
        self.pool = pool
        self.blocks: List[int] = []
        #: the sequence's state slots (its own; a snapshot's until the prefix
        #: cache takes it), where the model keeps state per sequence
        self.slots: List[int] = []
        self._released = False
        self._lock = threading.Lock()

    def add(self, blocks: Sequence[int]) -> None:
        with self._lock:
            if self._released:
                # late add after release (shouldn't happen): don't leak
                self.pool.free(list(blocks))
                return
            self.blocks.extend(blocks)

    def add_slot(self) -> int:
        """Take a state slot of the pool for this lease (NoKVBlocksError where
        there is none)."""
        slot = self.pool.take_slot()
        with self._lock:
            if self._released:
                self.pool.free_slot(slot)
            else:
                self.slots.append(slot)
        return slot

    def give_slot(self, slot: int) -> bool:
        """Hand ``slot`` over to another owner; False where the lease has gone
        (and the slot with it)."""
        with self._lock:
            if self._released or slot not in self.slots:
                return False
            self.slots.remove(slot)
            return True

    @property
    def released(self) -> bool:
        return self._released

    def release(self) -> None:
        with self._lock:
            if self._released:
                return
            self._released = True
            blocks, self.blocks = list(self.blocks), []
            slots, self.slots = self.slots, []
        self.pool.free(blocks)
        for slot in slots:
            self.pool.free_slot(slot)


# ---------------------------------------------------------------------------
# prefix cache: rolling hash over full prompt blocks, LRU under pressure
# ---------------------------------------------------------------------------


def chain_hashes(prompt: Sequence[int], block_size: int) -> List[bytes]:
    """One hash per FULL prompt block, each chained on its predecessor —
    block i's key commits to tokens [0, (i+1)*block_size), so two prompts
    share exactly their common full-block prefix and a divergent token
    anywhere invalidates every later block."""
    h = b"ray_tpu-llm-prefix-v1"
    out: List[bytes] = []
    for i in range(len(prompt) // block_size):
        blk = np.asarray(
            prompt[i * block_size:(i + 1) * block_size], np.int64
        ).tobytes()
        h = hashlib.sha1(h + blk).digest()
        out.append(h)
    return out


class PrefixCache:
    """hash -> block id, LRU-ordered. The cache holds its own reference on
    every cached block; entries whose block is otherwise idle (refcount 1)
    are evictable when the pool runs dry.

    Where the pool holds state per sequence, cached blocks are only as good as
    the state at their end: a chain is inserted with a **snapshot** (a state
    slot the cache then owns, keyed by the chain's last hash), a hit ends at the
    last block of the chain that has one, and eviction is by snapshot, oldest
    first: its slot goes back, and each block of its chain that no other
    snapshot's chain holds. Such a cache holds no block without a snapshot."""

    def __init__(self, pool: KVBlockPool, deployment: str = "llm"):
        self.pool = pool
        self.deployment = deployment
        self._map: "OrderedDict[bytes, int]" = OrderedDict()
        #: last hash of a chain -> (its state slot, the chain's hashes), LRU
        self._snap: "OrderedDict[bytes, tuple]" = OrderedDict()
        self._held: Dict[bytes, int] = {}       # hash -> snapshots whose chain has it
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        pool.set_evict_cb(self._evict_for)
        pool.set_evict_slot_cb(self._evict_snapshot)

    def match(self, hashes: Sequence[bytes]) -> List[int]:
        """Block ids of the longest cached prefix chain (where the pool holds
        states: the longest that ends in a snapshot, :meth:`snapshot`),
        increfed for the caller (release through the caller's lease)."""
        with self.pool._lock:
            out: List[int] = []
            for h in hashes:
                b = self._map.get(h)
                if b is None:
                    break
                self._map.move_to_end(h)
                out.append(b)
            if self.pool.states:
                out = out[:max(
                    (i + 1 for i in range(len(out)) if hashes[i] in self._snap), default=0)]
                if out:
                    self._snap.move_to_end(hashes[len(out) - 1])
            if out:
                self.pool.incref(out)
                self.hits += len(out)
                internal_metrics.inc(
                    "ray_tpu_llm_prefix_cache_hits_total", len(out),
                    {"deployment": self.deployment},
                )
            if len(out) < len(hashes):
                self.misses += len(hashes) - len(out)
            return out

    def snapshot(self, h: bytes) -> int:
        """The slot of the state after the chain that ends in hash ``h``."""
        return self._snap[h][0]

    def insert(self, hashes: Sequence[bytes], blocks: Sequence[int],
               snapshot: Optional[int] = None) -> bool:
        """Cache a freshly prefilled chain. First writer wins per hash;
        the cache takes its own reference on each newly cached block. Where
        the pool holds states, ``snapshot`` is the slot of the state after the
        chain's last block; True where the cache now owns it."""
        with self.pool._lock:
            if self.pool.states:
                if snapshot is None or not hashes or hashes[-1] in self._snap or any(
                        h not in self._map and self.pool._ref.get(b, 0) <= 0
                        for h, b in zip(hashes, blocks)):
                    return False
                self._snap[hashes[-1]] = (snapshot, tuple(hashes))
                for h in hashes:
                    self._held[h] = self._held.get(h, 0) + 1
            for h, b in zip(hashes, blocks):
                if h in self._map:
                    continue
                if self.pool._ref.get(b, 0) <= 0:
                    continue  # lease already released (cancelled mid-insert)
                self._map[h] = b
                self.pool.incref([b])
            return snapshot is not None

    def _evict_snapshot(self) -> bool:
        # called under the pool lock by KVBlockPool.take_slot, and below
        if not self._snap:
            return False
        _, (slot, chain) = self._snap.popitem(last=False)
        self.pool.free_slot(slot)
        for h in chain:
            self._held[h] -= 1
            if not self._held[h]:
                del self._held[h]
                self.pool._decref_locked(self._map.pop(h))
                self.evictions += 1
        return True

    def _evict_for(self, shortfall: int) -> None:
        # called under the pool lock by KVBlockPool.allocate
        if self.pool.states:
            target = len(self.pool._free) + shortfall
            while len(self.pool._free) < target and self._evict_snapshot():
                pass
            return
        freed = 0
        for h in list(self._map):
            if freed >= shortfall:
                break
            b = self._map[h]
            if self.pool._ref.get(b, 0) == 1:  # only the cache holds it
                del self._map[h]
                self.pool._decref_locked(b)
                self.evictions += 1
                freed += 1

    def __len__(self) -> int:
        with self.pool._lock:
            return len(self._map)

    def snapshots(self) -> int:
        with self.pool._lock:
            return len(self._snap)


# ---------------------------------------------------------------------------
# LoRA adapters: low-rank logit deltas over the pinned base model
# ---------------------------------------------------------------------------


def random_lora(cfg, rank: int = 4, seed: int = 0, scale: float = 1.0):
    """A deterministic random adapter ``{"A","B","scale"}`` for tests and
    benches — ``logits += scale * (hidden @ A) @ B``."""
    rng = np.random.RandomState(seed)
    return {
        "A": rng.randn(cfg.embed_dim, rank).astype(np.float32) * 0.1,
        "B": rng.randn(rank, cfg.vocab_size).astype(np.float32) * 0.1,
        "scale": float(scale),
    }


def register_lora(model_id: str, adapter: Dict[str, Any], **kw):
    """Publish a LoRA adapter on the object plane under ``model_id`` —
    replicas stream it on first use through their multiplex LRU."""
    from ray_tpu import serve

    return serve.register_model(model_id, adapter, **kw)


def _fetch_lora(model_id: str):
    from ray_tpu import serve

    a = serve.fetch_model(model_id)
    return (
        np.asarray(a["A"], np.float32),
        np.asarray(a["B"], np.float32),
        float(a.get("scale", 1.0)),
    )


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def _operand_extend(extend, caches: int = 0, states: int = 0):
    """``extend`` as a step calls it: the tokens and lengths are read on the
    device from the call's operand buffer (:func:`_sections`), ``tc`` tokens a
    lane, and a lane's first token from ``home`` (what the call before left
    for the host, its lanes' ids first) where its row names a lane there
    (``_FROM``): the host need not have seen a token to feed it. One program
    per (lanes, tokens, cache), as ``extend`` alone has, and each under a name
    of its own: what is returned is a family (``accelerator.Programs``), called
    with the program's name first, which the engine makes of those three sizes
    (:func:`_extend_name`), so a profile's module line, the ``PjitFunction``
    event on the host and the engine's record of the call (``llm.dispatch``'s
    ``program``, ``stats()["programs"]``) are one string. ``extend`` is told which
    row of each lane is read (``last``, the buffer's ``_LAST`` column) and returns
    logits ``[lanes, vocabulary]`` and hidden rows ``[lanes, d]`` for those rows
    alone, zeros from a chunk in which no lane reads one (decided on the device:
    still one program a shape). Where the model keeps
    state per sequence, the pool's ``states`` arenas follow the ``caches``
    (donated: the ones ``extend`` returns take their place) and ``extend`` is told
    each lane's slot in them and where to keep a state for the prefix cache
    (``_SLOT``, ``_SNAP_AT``, ``_SNAP_SLOT``). With ``pages`` (static, as ``tc`` is) the
    ``caches`` are the pool's arenas themselves, not donated, and ``extend`` is told the
    lanes' first ``pages`` block ids (``table=``, the buffer's ``table`` section): a decode
    call of a model that reads a lane's pages where the pool keeps them
    (:func:`reads_pages`)."""
    import jax
    import jax.numpy as jnp

    donated = dict(donate_argnums=tuple(range(3 + caches, 3 + caches + states))) if states else {}

    @functools.partial(accelerator.Programs, static_argnames=("tc", "pages"), **donated)
    def extend_call(params, operands, home, *arrays, tc, pages=0):
        tokens, source = _sections(operands)[0][:, :tc], operands[:, _FROM]
        first = jnp.where(source < 0, tokens[:, 0], home[jnp.maximum(source, 0)])
        where = tuple(
            operands[:, at] for at in (_SLOT, _SNAP_AT, _SNAP_SLOT)) if states else ()
        table = dict(table=_sections(operands)[3][:, :pages]) if pages else {}
        return extend(
            params, tokens.at[:, 0].set(first), operands[:, _LENGTH], *arrays, *where,
            last=operands[:, _LAST], **table)

    return extend_call


def reads_pages(extend) -> bool:
    """Whether a decode call of ``extend`` attends through the block table: what the
    model's ``extend`` offers, a ``table=`` keyword (``models/mimo_v2_flash.py``,
    ``models/qwen3_next.py``, ``models/granitemoehybrid.py``, ``models/kimi_k2.py``;
    not ``models/keye_vl2.py`` and ``models/glm_moe_dsa.py``, whose decode lanes read
    selected rows). Such a call is handed
    the pool's arenas where another is handed padded caches, and runs no gather.
    Nothing else decides it: no key of a configuration, no keyword of the engine."""
    return "table" in inspect.signature(extend).parameters


def _extend_name(b: int, tc: int, cap: int) -> str:
    """The name of the ``extend`` program for ``b`` lanes of ``tc`` tokens over a
    cache of ``cap``: ``extend_decode_8x1x8192``, ``extend_prefill_1x256x8192``.
    From the sizes that shape the program alone (one token a lane is the decode
    program, whoever calls it), so no two programs share a name and none has two."""
    return f"extend_{'decode' if tc == 1 else 'prefill'}_{b}x{tc}x{cap}"


class _SeqState:
    """A sequence as the host keeps it. ``pos``, ``length`` and ``sent`` say
    what has been launched (prompt tokens fed, tokens in the cache, tokens
    asked of the device); ``out`` and ``last_token`` what has landed. Between
    the two the lane is in ``call``, as lane ``lane`` of it."""

    __slots__ = (
        "prompt", "max_new", "eos", "model_id", "adapter", "lease", "blocks",
        "pos", "length", "sent", "call", "lane", "out", "last_token",
        "cached_tokens", "hashes", "ttft_s", "queue_s", "stream_q",
        "cancel_ev", "return_logits", "logits", "slot", "snapshot",
    )

    @property
    def on_host(self) -> bool:
        """The host makes this lane's token from the rows of logits and hidden
        (it returns them, or an adapter changes them): nothing can be fed to
        it before its call has landed."""
        return self.return_logits or self.adapter is not None


class _Call:
    """A device call between its launch and its landing: what it left on the
    device (``home``, for the host and for the next call, and ``picked``: the
    row of logits and of hidden a lane that ``extend`` made; both gone once it
    has landed), its ``lanes`` as ``(sequence,
    state)`` and which of them sample a token (``emits``). And its record:
    its number ``seq`` among the engine's calls, its ``form`` (``prefill`` or
    ``decode``), its padded ``shape`` ``(lanes, tokens, cache)`` and the name of
    the ``program`` that shape runs as (:func:`_extend_name`), the ``step``
    that launched it and whether that step was ``recorded`` by a profiler
    session (known at that step's end), and the instant its launch returned
    (``launched_at``, on the engine's clock: ``LLMEngine._now``)."""

    __slots__ = (
        "home", "picked", "lanes", "emits", "sampled",
        "seq", "form", "shape", "program", "step", "recorded", "launched_at",
    )

    def __init__(
            self, home, picked, lanes, emits, *, seq, form, shape, program, step, launched_at):
        self.home, self.picked, self.lanes, self.emits = home, picked, lanes, emits
        self.sampled: List[tuple] = []
        self.seq, self.form, self.shape, self.step = seq, form, shape, step
        self.program = program
        self.recorded, self.launched_at = False, launched_at


#: the phases of an engine step, as ``phase_s`` / ``phase_n`` key them and as
#: the profiler sees them (``llm.<phase>``). ``step`` holds ``admit``,
#: ``prefill`` and ``decode``; those two hold the four phases of a call's launch
#: (``upload`` to ``kv_scatter``, none of which waits for the device) and, after
#: them, the two of the landing of the call before (``fetch``, which waits, and
#: ``sample``); a step with nothing to launch holds a landing alone.
#: The leaves partition a step: what is in none of them is a missing phase.
LEAF_PHASES = (
    "admit", "upload", "kv_gather", "dispatch", "kv_scatter", "fetch", "sample",
)
PHASES = ("step", "prefill", "decode") + LEAF_PHASES
#: where an engine keeps state per sequence, inside ``admit``: a cached prefix's
#: state copied to the sequence's slot (a call's own states ``extend`` reads and
#: writes where they lie: no phase of the host's)
STATE_PHASES = ("state_restore",)
#: the two forms of device call, as ``stats()["calls"]`` keys them
FORMS = ("prefill", "decode")
#: what one ``_phase`` may cost outside a profiler session, where its span is
#: a no-op (2.7 us on the sandbox's CPU): under 0.3 ms for the <= 30 phases of
#: a step. ``tests/test_llm_spans.py`` holds the engine to it.
PHASE_BUDGET_NS = 10_000.0
#: the units of host work an engine brackets for ``accelerator.HostWatch``: a
#: step, and the time from one step's return to the next step while a sequence
#: is active (the batcher's loop and the wake-up of finished callers; with
#: nothing active it is nobody's)
HOST_UNITS = ("llm.step", "llm.between")
#: what the two units of a step may add to it outside a profiler session: two
#: readings of four clocks, shared between the units, and their arithmetic
#: (``tests/test_llm_spans.py`` holds the engine to it, with the phases' budget)
HOST_BUDGET_NS = 5_000.0


class LLMEngine:
    """The scheduler + paged-attention runtime behind ``LLMServer``.

    ``step(seqs)`` is a continuous-batching step function: each call
    admits new sequences (allocating their KV lease or shedding), launches at
    most one bucketed prefill chunk and one tc=1 decode over every
    decoding lane, and finishes/streams tokens. All shapes reaching the
    jitted extend fn are drawn from the configured buckets.

    The device runs a call behind the host: a call is landed (its ids fetched,
    its tokens emitted) only after its successor has been launched, so one
    call is in flight when ``step`` returns, and the next launch reads a
    lane's token from that call's ids on the device. The host lands every
    call but the newest before it launches, so a lane's last token is either
    on the host or in that one call. What the host must see first, it waits
    for: the call of a lane whose rows it needs (:attr:`_SeqState.on_host`),
    a call whose buffers leave the device no room for the next one's
    (:meth:`_fits`), a call that may free the blocks an allocation lacks, and
    the last call when there is nothing to launch."""

    def __init__(self, cfg=None, params=None, *, deployment: str = "llm",
                 num_blocks: int = 128, block_size: int = 16,
                 prefill_chunk: int = 32, prefill_lanes: int = 4,
                 lane_buckets: Sequence[int] = LANE_BUCKETS,
                 prefill_token_buckets: Sequence[int] = (8, 16, 32),
                 cache_buckets: Sequence[int] = (32, 64, 128),
                 max_adapters: int = 4, adapter_loader=None,
                 prefix_caching: bool = True, default_max_new_tokens: int = 16,
                 step_delay_s: float = 0.0, seed: int = 0,
                 state_slots: Optional[int] = None):
        import jax.numpy as jnp

        from ray_tpu.models import gpt

        self.cfg = cfg or gpt.gpt_nano()
        self._params = params if params is not None else make_params(
            self.cfg, seed)
        self._extend = self.cfg.make_extend_fn()
        _LIVE.add(self)
        #: per-sequence state (a recurrent layer's), where the model has any
        state_arrays = tuple(getattr(self.cfg, "state_arrays", ()))
        self._stateful = bool(state_arrays)
        self._extend_call = _operand_extend(
            self._extend, len(self.cfg.cache_arrays), len(state_arrays))
        #: whether a decode call reads the lanes' pages in the arenas (no gather)
        self._reads_pages = reads_pages(self._extend)
        self.deployment = deployment
        self.block_size = int(block_size)
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_lanes = int(prefill_lanes)
        self.lane_buckets = sorted(lane_buckets)
        self.prefill_token_buckets = sorted(prefill_token_buckets)
        self.cache_buckets = sorted(cache_buckets)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.max_context = min(self.cfg.max_seq_len, self.cache_buckets[-1])
        if any(cap % self.block_size for cap in self.cache_buckets):
            raise ValueError(
                f"cache buckets {self.cache_buckets} must be whole blocks of "
                f"{self.block_size} tokens: a padded cache is a block table")
        if self._stateful:
            # a kept state lies between two sub-chunks of the recurrence and at
            # a block's end, and every chunk starts at one
            chunk = self.cfg.state_chunk
            if self.block_size % chunk or self.prefill_chunk % self.block_size or any(
                    tc % chunk for tc in self.prefill_token_buckets):
                raise ValueError(
                    f"blocks of {self.block_size}, prefill chunks of {self.prefill_chunk} in "
                    f"buckets {self.prefill_token_buckets}: the model keeps a state every "
                    f"{chunk} tokens, and a block and a chunk must end at one")
            if state_slots is None:
                # a slot a sequence the batcher can hold, as many snapshots, nobody's
                state_slots = 4 * self.lane_buckets[-1] + 1
        self.pool = KVBlockPool(
            self.cfg, num_blocks=num_blocks, block_size=block_size,
            state_slots=state_slots or 0, deployment=deployment,
        )
        self._operand_width = _operand_width(
            self.prefill_token_buckets[-1],
            self.cache_buckets[-1] // self.block_size, self._stateful)
        self._warm_paging()
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.pool, deployment) if prefix_caching else None
        )
        loader = adapter_loader or _fetch_lora
        self._mux = _MultiplexWrapper(loader, None, int(max_adapters))
        #: fault injection: stretch every engine step (chaos / cancellation
        #: tests need the decode window to outlive a few control RPCs)
        self.step_delay_s = float(step_delay_s)
        # counters: always on, read as deltas through ``stats()``. Bytes are
        # computed from the sizes of the arrays handed over, not measured.
        self.steps = 0
        self.admitted = 0
        #: ``(tokens, slot)`` of the sequence that finished last: the tokens its
        #: state holds (all it was fed: the prompt and all but the newest of its
        #: own) and the slot that state lies in, as the sequence left it until
        #: the slot is taken again. None before any, and without states
        self.last_finished: Optional[tuple] = None
        self.queue_s = 0.0              # sum of enqueue -> admitted
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_transfers = 0          # host -> device puts
        self.d2h_transfers = 0          # blocking device -> host copies
        self.ids_only_calls = 0         # calls that brought home the ids alone
        self.calls_ahead = 0            # calls launched while another was in flight
        self.tokens_fed_on_device = 0   # lanes whose token the call before left there
        # what ``extend`` counted (one int32 vector behind the caches' new
        # rows), summed over the device calls under the configuration's names
        # for it, and what the configuration counts of a call's gathered
        # caches on the host (``count_gathered``): the engine knows neither
        self._counter_names = tuple(getattr(self.cfg, "counters", ()))
        self.counted: Dict[str, int] = dict.fromkeys(self._counter_names, 0)
        self._count_gathered = getattr(self.cfg, "count_gathered", None)
        if self._count_gathered is not None:
            self.counted.update(dict.fromkeys(self._count_gathered(0, 0), 0))
        # cache slots gathered for layers whose queries see a window only
        # (``cfg.sliding_window``, ``cfg.sliding_layers``; 0 without them), and
        # those of them that hold a token too old for any query of the call to
        # see: what a window-aware allocator would neither keep nor gather.
        # Counted from what a call gathers: the sliding layers among those a
        # token is cached in (``cfg.cached_layers``; every layer without it), so
        # a model that keeps its windows as state (``models/mimo_v2_flash.py``)
        # reads 0 here, with sliding layers and all
        self.window_slots = 0
        self.window_slots_outside = 0
        self._window = getattr(self.cfg, "sliding_window", None)
        sliding = tuple(getattr(self.cfg, "sliding_layers", ()))
        self._window_layers = sum(
            s and c for s, c in zip(
                sliding, getattr(self.cfg, "cached_layers", (True,) * len(sliding))))
        phases = PHASES + (STATE_PHASES if self._stateful else ())
        self.phase_s: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self.phase_n: Dict[str, int] = dict.fromkeys(phases, 0)
        # states the prefix cache gave back to a sequence, and the bytes of
        # state copied by anything but the model itself: from a snapshot's slot
        # to a sequence's, which is all the store copies (0 without states)
        self.state_restores = 0
        self.state_bytes_moved = 0
        #: per form of call, over the device calls: how many; their real lanes
        #: and their lane buckets; the lanes whose row of logits was read
        #: (``heads``); the tokens fed and lanes x token bucket; the
        #: live tokens in the lanes' caches and lanes x cache bucket (gathered
        #: into padded caches, or read through the block table: ``paged``, the
        #: calls that ran no gather; every decode call of a model whose
        #: ``extend`` reads pages, none of any other); and ``busy_s``, a call's
        #: landing less the later of its own launch's
        #: return and the landing before it: what the device spent on it as
        #: the host sees it, exact while the device is never idle between
        #: calls and an upper bound otherwise. The totals over both forms are
        #: the properties below (``lanes_used`` ... ``decode_tokens``).
        self.calls: Dict[str, Dict[str, Any]] = {
            form: dict(
                n=0, lanes_used=0, lane_slots=0, heads=0, tokens=0, token_slots=0,
                cache_tokens=0, cache_slots=0, paged=0, busy_s=0.0)
            for form in FORMS}
        #: the same ``n`` and ``busy_s`` per program, by its name (the module a
        #: profile shows, ``llm.dispatch``'s ``program``): a group for every
        #: program ``warm`` ran, at zero from then on, and for every other from
        #: its first call, which is counted in ``programs_cold``, with the wall
        #: seconds that call's dispatch took (its trace and its compile or cache
        #: load, inside traffic) in ``programs_cold_s``
        self.programs: Dict[str, Dict[str, Any]] = {}
        self.programs_cold = 0
        self.programs_cold_s = 0.0
        self._landed_at = 0.0           # the instant of the last landing, by ``_now``
        #: the seconds the host spent outside ``step`` after the device had
        #: finished the call in flight (a batcher waiting, a profiler starting
        #: or stopping: seconds at a time): none of a call's ``busy_s``
        self._away_s = 0.0
        self._left_at = 0.0             # when ``step`` last returned
        #: the slowest step since ``stats()`` was last read (so two reads
        #: bound a window, as they do for the counters): when it ended, how
        #: long it took, its lanes and its own seconds per phase. Time in
        #: ``fetch`` is the device or the runtime; anywhere else, the host.
        self.slowest_step: Optional[Dict[str, Any]] = None
        #: what the host's own part of a step and of the time between two steps
        #: cost this thread, and the steps that stood still with their cause and
        #: their stack (``accelerator.HostWatch``): ``stats()``'s ``host``, ``held``
        #: and ``held_steps``, the engine's own; ``gc`` is the process's
        self._watch = accelerator.host_watch()
        self._book = accelerator.HostBook(*HOST_UNITS)
        self._unit = None               # the step under way while it is ``open``, else the last
        self._between = None            # from a step's return while a sequence is active, likewise
        self._late_s = 0.0              # of the step's landings, what their programs do not explain
        #: a program's usual call, which explains a landing's wait: ``[calls landed,
        #: the sum of their busy_s]``, a call counted at no more than twice the usual
        #: once four have landed, so that a landing that was held does not explain
        #: the next one
        self._usual: Dict[str, List[float]] = {}
        #: the call launched last, until its successor is (or nothing can be)
        self._flight: Optional[_Call] = None
        #: what the first call of an idle engine is handed for ``home``
        self._no_home = jnp.zeros((self._home_width,), jnp.int32)
        (self._device,) = self._no_home.devices()
        #: whether the runtime counts the device's bytes (the CPU's does not)
        self._counts_bytes = "bytes_limit" in (self._device.memory_stats() or {})
        #: the temporaries of the largest ``extend``, once ``warm`` has asked
        self._temp_bytes = 0
        #: ``_work()`` over the *recorded* steps alone: those at whose first and
        #: last instruction a profiler session recorded (``accelerator.
        #: recording``). Cumulative and never reset, read as a delta like the
        #: rest. What a step's host counts is that step's; what a landing
        #: learns of its call (``extend``'s counters, ``busy_s``) goes to the
        #: step that launched the call, recorded or not, whenever it lands.
        self.traced = self._work()
        #: ``_work()`` as the step under way found it, while a session records
        self._step_began: Optional[Dict[str, Any]] = None

    def _warm_paging(self) -> None:
        """Compile the pool's programs for every shape the buckets allow, so
        that nothing around ``extend`` compiles once requests arrive.
        ``extend`` is only traced here, for the shapes it hands to the
        page-back: the first call of each of its own shapes still compiles."""
        import jax

        #: how wide a call's ``home`` is: the ids, as wide as the widest lane
        #: bucket, then (below) what ``extend`` counted
        self._home_width = self.lane_buckets[-1]

        def extend_outputs(b, tc):
            cap = self.cache_buckets[0]
            return jax.eval_shape(
                functools.partial(
                    self._extend_call, _extend_name(b, tc, cap), **self._extend_statics(tc, cap)),
                *self._extend_args(jax.ShapeDtypeStruct, b, tc, cap))

        outputs = {
            (b, tc): extend_outputs(b, tc)
            for b in self.lane_buckets
            for tc in [1] + self.prefill_token_buckets
        }
        counted = self.pool.split_outputs(outputs[self.lane_buckets[0], 1][2:])[2]
        self._home_width += sum(math.prod(c.shape) for c in counted)
        #: the bytes ``extend`` hands back for (lanes, tokens), for ``_fits``
        #: (the state arenas it returns are the ones it was handed: no more bytes)
        self._output_bytes = {}
        for shape, (logits, hidden, *rest) in outputs.items():
            rows, _, counts = self.pool.split_outputs(rest)
            self._output_bytes[shape] = sum(
                math.prod(o.shape) * o.dtype.itemsize for o in (logits, hidden, *rows, *counts))
        # a call that reads pages gathers nothing: no gather for its lanes alone
        self.pool.warm(outputs, self.cache_buckets, sorted({
            b for b, tc, _ in self.extend_shapes() if not self._paged(tc)}))

    def _paged(self, tc: int) -> bool:
        """Whether a call of ``tc`` tokens a lane reads the pool's pages where they
        lie: a decode call of a model whose ``extend`` offers it."""
        return tc == 1 and self._reads_pages

    def _extend_statics(self, tc: int, cap: int) -> Dict[str, int]:
        """What shapes ``_extend_call``'s program beside its arrays."""
        return dict(tc=tc, pages=cap // self.block_size) if self._paged(tc) else dict(tc=tc)

    def _extend_args(self, make, b: int, tc: int, cap: int, held: bool = False):
        """The arrays ``_extend_call`` takes for ``b`` lanes of ``tc`` tokens over a
        cache of ``cap``, each made by ``make(shape, dtype)``; with ``held`` the
        pool's own state arenas (a call that runs is handed them) and, for a call
        that reads pages, its block arenas in the caches' place."""
        if self._paged(tc):
            caches = self.pool.arenas if held else (make(a.shape, a.dtype) for a in self.pool.arenas)
        else:
            caches = (
                make((self.pool.layers, b, cap // grain) + tuple(each[:2]), self.pool.dtype)
                for each, grain in zip(self.cfg.cache_arrays, self.pool.grains))
        states = self.pool.states if held else (make(s.shape, s.dtype) for s in self.pool.states)
        return (
            self._params, make((b, self._operand_width), np.int32),
            make((self._home_width,), np.int32), *caches, *states)

    def extend_shapes(self) -> List[tuple]:
        """Every (lanes, tokens, cache) a step can ask ``extend`` for, one
        program each: a decode call (one token) in every lane bucket, a
        prefill call in every lane bucket that ``prefill_lanes`` can fill, each
        over every cache bucket."""
        prefill_b = batching.bucket_pad_size(self.prefill_lanes, self.lane_buckets)
        return [
            (b, tc, cap)
            for b in self.lane_buckets
            for tc in [1] + self.prefill_token_buckets
            for cap in self.cache_buckets
            if tc == 1 or b <= prefill_b
        ]

    def warm(self) -> Dict[str, Any]:
        """Run ``extend`` once in every shape of :meth:`extend_shapes`, as a
        step calls it, on zeros made on the device (and the pool's own state
        arenas, of which zeros name slot 0 alone), so that no request meets a
        compile; each program has its group in ``stats()["programs"]`` from
        here on. Returns how many ``shapes``, the seconds it took
        (``warm_s``) and, where the compiler says, the bytes of the largest one
        (``compiled``: by lanes x cache, among the shapes that are handed padded
        caches), whose temporaries ``_fits`` then counts for any call:
        compiling every shape a second time to ask each would double this."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        shapes = self.extend_shapes()
        for b, tc, cap in shapes:
            name = _extend_name(b, tc, cap)
            _, _, *rest = jax.block_until_ready(self._extend_call(
                name, *self._extend_args(jnp.zeros, b, tc, cap, held=True),
                **self._extend_statics(tc, cap)))
            self.pool.states = self.pool.split_outputs(rest)[1]
            if name not in self.programs:
                self._new_program(name)
        warm_s = time.perf_counter() - t0
        # of the shapes that hold padded caches (a call that reads pages holds none,
        # and next to no temporaries: ``_fits`` would count too little for a chunk)
        b, tc, cap = largest = max(
            shapes, key=lambda s: (not self._paged(s[1]), s[0] * s[2], s[1]))
        memory = self._extend_call.lower(
            _extend_name(b, tc, cap), *self._extend_args(jax.ShapeDtypeStruct, b, tc, cap),
            **self._extend_statics(tc, cap)
        ).compile().memory_analysis()
        if memory is not None:
            self._temp_bytes = memory.temp_size_in_bytes
        return {
            "shapes": len(shapes), "warm_s": warm_s,
            "compiled": None if memory is None else {
                "shape": list(largest),
                "argument_bytes": memory.argument_size_in_bytes,
                "temp_bytes": memory.temp_size_in_bytes,
                "output_bytes": memory.output_size_in_bytes,
                "alias_bytes": memory.alias_size_in_bytes,
            },
        }

    def _new_program(self, name: str) -> None:
        """A group at zero for the program ``name``, in ``programs`` and in
        ``traced``'s: two reads bound a window only for a group both hold."""
        for book in (self.programs, self.traced["programs"]):
            book[name] = dict(n=0, busy_s=0.0)

    # -- public stats ------------------------------------------------------

    lanes_used = property(lambda self: self._over_forms("lanes_used"))
    lane_slots = property(lambda self: self._over_forms("lane_slots"))
    cache_tokens = property(lambda self: self._over_forms("cache_tokens"))
    cache_slots = property(lambda self: self._over_forms("cache_slots"))
    prefill_tokens = property(lambda self: self.calls["prefill"]["tokens"])
    decode_tokens = property(lambda self: self.calls["decode"]["tokens"])

    def _over_forms(self, key: str) -> int:
        return sum(self.calls[form][key] for form in FORMS)

    def stats(self) -> Dict[str, Any]:
        # a read takes ``slowest_step`` with it: the next starts from here
        slowest, self.slowest_step = self.slowest_step, None
        return {
            "device": accelerator.device_report(),
            "compile_cache": accelerator.compile_cache_stats(),
            "kv_blocks_in_use": self.pool.in_use(),
            "prefix_hits": self.prefix.hits if self.prefix else 0,
            "prefix_misses": self.prefix.misses if self.prefix else 0,
            "prefix_cached_blocks": len(self.prefix) if self.prefix else 0,
            "adapters_resident": self._mux.loaded_ids(),
            **({
                "state_slots_in_use": self.pool.slots_in_use(),
                "state_snapshots": self.prefix.snapshots() if self.prefix else 0,
            } if self._stateful else {}),
            **self._work(),
            "traced": copy.deepcopy(self.traced),
            "slowest_step": slowest,
            "gc": self._watch.gc_totals(),
            "held_steps": list(self._book.steps),
        }

    @property
    def params(self):
        """The weights being served, as they were handed over."""
        return self._params

    def held_snapshot(self, prompt: Sequence[int]) -> Optional[tuple]:
        """``(tokens, slot)``: how many of ``prompt``'s tokens a request for it
        would find cached with their state, and the slot of that snapshot
        (:meth:`KVBlockPool.read_state` reads it); None where the prefix cache
        holds none for it. Takes nothing and touches no order of eviction: for
        tests and probes."""
        if self.prefix is None or not self._stateful:
            return None
        hashes = chain_hashes(prompt, self.block_size)[:(len(prompt) - 1) // self.block_size]
        for n in range(len(hashes), 0, -1):
            with contextlib.suppress(KeyError):
                return n * self.block_size, self.prefix.snapshot(hashes[n - 1])
        return None

    def _work(self) -> Dict[str, Any]:
        """Every counter that counts work, its groups copied: what ``stats()``
        returns of them, what ``traced`` keeps a second time, and what a
        recorded step is the difference of."""
        return {
            "steps": self.steps,
            "admitted": self.admitted,
            "queue_s": self.queue_s,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "cache_tokens": self.cache_tokens,
            "cache_slots": self.cache_slots,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "h2d_transfers": self.h2d_transfers,
            "d2h_transfers": self.d2h_transfers,
            "ids_only_calls": self.ids_only_calls,
            "lanes_used": self.lanes_used,
            "lane_slots": self.lane_slots,
            "calls_ahead": self.calls_ahead,
            "tokens_fed_on_device": self.tokens_fed_on_device,
            **self.counted,
            **({
                "state_restores": self.state_restores,
                "state_bytes_moved": self.state_bytes_moved,
            } if self._stateful else {}),
            "window_slots": self.window_slots,
            "window_slots_outside": self.window_slots_outside,
            "phase_s": dict(self.phase_s),
            "phase_n": dict(self.phase_n),
            "calls": {form: dict(counts) for form, counts in self.calls.items()},
            "programs": {name: dict(counts) for name, counts in self.programs.items()},
            "programs_cold": self.programs_cold,
            "programs_cold_s": self.programs_cold_s,
            **self._book.totals(),
        }

    @contextlib.contextmanager
    def _phase(self, name: str, **what):
        """One phase of a step: a span ``llm.<name>`` on the profiler's clock,
        recorded while a profiler session runs (``accelerator.span``; ``what``
        is its metadata, and the span is yielded for ``set_metadata``), and
        always its wall time and a count in ``phase_s`` / ``phase_n``."""
        t0 = time.perf_counter()
        try:
            with accelerator.span("llm." + name, **what) as span:
                yield span
        finally:
            self.phase_s[name] += time.perf_counter() - t0
            self.phase_n[name] += 1

    # -- scheduling --------------------------------------------------------

    def step(self, seqs: List[Any]) -> None:
        watch = self._watch
        between = self._between
        # one reading of the clocks ends ``llm.between`` and begins ``llm.step``; right
        # after the last step's own (the batcher's loop takes microseconds), the wall alone
        began = watch.read(between.began if between is not None and between.open else None)
        self._step_began = self._work() if accelerator.recording() else None
        if between is not None and between.open:
            if seqs and seqs[0].state is not None:
                watch.close(between, began, "between")
            else:
                # the sequences it waited with have gone (cancelled; those that
                # stay come first): nobody's time
                watch.drop(between)
        if self._flight is not None and self._flight.home.is_ready():
            self._away_s += began[0] - self._left_at
        before, lanes0, this = dict(self.phase_s), self.lanes_used, self.steps
        self._late_s = 0.0
        unit = self._unit = watch.open("llm.step", self._book, began, again=self._unit)
        try:
            # the phase ``step``: its span, and its seconds and count from the unit's clock
            with accelerator.span("llm.step"):
                if self.step_delay_s:
                    time.sleep(self.step_delay_s)
                if self._admit_phase(seqs):
                    # too few blocks while a call is in flight: a sequence
                    # that ends in it gives its own back. Land it, ask again.
                    self._land()
                    self._admit_phase(seqs)
                flight = self._flight
                self._prefill_step(seqs)
                self._decode_step(seqs)
                newest = self._flight
                if newest is not None and (
                        newest is flight or all(s.done for s, _ in newest.lanes)):
                    # nothing was launched, so there is nothing to wait behind;
                    # or no lane of the call is left to ask for another step
                    # (an ``eos_token`` landed while it was launched)
                    self._land()
                self.steps += 1
        except BaseException:
            # a crashed forward poisons the batch (the batcher fails every
            # caller) — the leases must not ride down with it, nor those of
            # the call in flight, which is dropped: nothing will land it
            self.phase_s["step"] += time.perf_counter() - began[0]
            self.phase_n["step"] += 1
            watch.drop(unit)
            flight, self._flight, self._step_began = self._flight, None, None
            for st in [s.state for s in seqs] + [
                    st for _, st in (flight.lanes if flight else ())]:
                if isinstance(st, _SeqState) and st.lease is not None:
                    st.lease.release()
            raise
        ended = watch.read()
        # the one place a step's wall and its phases' split are computed: the
        # held record's ``where`` and ``slowest_step`` read the same numbers
        held = watch.close(unit, ended, lambda: self._where(before))
        wall_s = unit.wall_s
        self.phase_s["step"] += wall_s
        self.phase_n["step"] += 1
        if self.slowest_step is None or wall_s > self.slowest_step["wall_s"]:
            self.slowest_step = {
                "at": time.time(), "wall_s": wall_s,
                "lanes": self.lanes_used - lanes0,
                "phase_s": {**self._split(before), "step": wall_s},
                # what the step cost this thread and, where the watch found it
                # held, why: the record's fields
                **dict(zip(accelerator.MEASURES, unit.measured)),
                **{k: (held or {}).get(k) for k in ("excess_s", "where", "cause", "stack")},
            }
        began, self._step_began = self._step_began, None
        if began is not None:
            recorded = accelerator.recording()
            if recorded:
                _add_difference(self.traced, self._work(), began)
            if self._flight is not None and self._flight.step == this:
                # every other call this step launched has landed in it
                self._flight.recorded = recorded
        self._left_at = ended[0]
        if self._flight is not None:            # a sequence waits for the next step
            self._between = watch.open("llm.between", self._book, ended, again=between)

    def _split(self, before: Dict[str, float]) -> Dict[str, float]:
        """The step's own seconds a phase: ``phase_s`` now less ``before``."""
        return {k: v - before[k] for k, v in self.phase_s.items() if v > before[k]}

    def _where(self, before: Dict[str, float]) -> str:
        """The leaf phase that holds most of what nothing explains of the step
        under way: a phase's whole time, but of ``fetch`` only what its calls'
        programs do not explain (``_land``)."""
        split = {**self._split(before), "fetch": self._late_s}
        return max(LEAF_PHASES, key=lambda p: split.get(p, 0.0))

    def _now(self) -> float:
        """The clock of a call's launch and landing: the host's, less the
        time it was away from ``step`` with the device done (``_away_s``)."""
        return time.perf_counter() - self._away_s

    def _admit_phase(self, seqs) -> bool:
        with self._phase("admit"):
            short = self._admit(seqs)
            self._sweep_cancelled(seqs)
        return short

    def _admit(self, seqs) -> bool:
        """Give every new sequence its state and its blocks, or fail it. True
        where one found too few blocks while a call is in flight and was left
        as it came: the landing may free some (``step`` asks again)."""
        for s in seqs:
            if s.state is not None or s.done:
                continue
            item = s.item if isinstance(s.item, dict) else {"prompt": s.item}
            st = _SeqState()
            st.prompt = [int(t) for t in item.get("prompt", [])]
            st.max_new = int(
                item.get("max_new_tokens", self.default_max_new_tokens))
            st.eos = item.get("eos_token")
            st.model_id = item.get("model_id")
            st.adapter = None
            st.stream_q = item.get(_STREAM_KEY)
            st.cancel_ev = item.get(_CANCEL_KEY)
            st.return_logits = bool(item.get("return_logits"))
            st.logits = [] if st.return_logits else None
            st.out = []
            st.last_token = None
            st.sent = 0
            st.call = st.lane = None
            st.ttft_s = None
            st.queue_s = None
            if not st.prompt or st.max_new < 1:
                s.fail(ValueError("payload needs a non-empty 'prompt'"))
                continue
            total = len(st.prompt) + st.max_new
            if total > self.max_context:
                s.fail(ValueError(
                    f"prompt+max_new_tokens = {total} exceeds the engine "
                    f"context of {self.max_context}"
                ))
                continue
            lease = KVLease(self.pool)
            st.lease = lease
            st.blocks = lease.blocks
            bs = self.block_size
            st.hashes = (
                chain_hashes(st.prompt, bs) if self.prefix is not None else []
            )
            # never reuse the whole prompt: the last prompt token must be
            # fed through prefill to produce the first sampled token
            reuse_cap = (len(st.prompt) - 1) // bs
            st.slot = st.snapshot = None
            try:
                if self._stateful:
                    # before the match: taking a slot may cost a snapshot
                    st.slot = lease.add_slot()
                    # all of a cached chain or nothing: its state is the one at
                    # its end, which is where this prompt's would be kept too
                    st.hashes = st.hashes[:reuse_cap]
                cached = (
                    self.prefix.match(st.hashes[:reuse_cap])
                    if self.prefix is not None else []
                )
                lease.add(cached)
                if cached and self._stateful:
                    with self._phase("state_restore", tokens=len(cached) * bs):
                        self.pool.copy_state(
                            self.prefix.snapshot(st.hashes[len(cached) - 1]), st.slot)
                        self.state_restores += 1
                        self.state_bytes_moved += self.pool.state_bytes
                need = math.ceil(len(st.prompt) / bs) - len(cached)
                lease.add(self.pool.allocate(need))
            except NoKVBlocksError as e:
                lease.release()
                if self._flight is not None:
                    return True
                s.fail(BackPressureError(str(e), retry_after_s=0.05))
                continue
            s.on_release = lease.release
            st.pos = len(cached) * bs       # prompt tokens already cached
            st.length = st.pos              # tokens resident in the cache
            st.cached_tokens = st.pos
            if st.model_id:
                try:
                    st.adapter = self._mux.load(st.model_id)
                except Exception as e:  # noqa: BLE001 — unknown model id
                    lease.release()
                    s.fail(e if isinstance(e, KeyError) else RuntimeError(
                        f"loading adapter {st.model_id!r} failed: {e!r}"))
                    continue
            s.state = st
            st.queue_s = time.monotonic() - s.enqueued_at
            self.admitted += 1
            self.queue_s += st.queue_s
        return False

    def _sweep_cancelled(self, seqs) -> None:
        for s in seqs:
            st = s.state
            if (isinstance(st, _SeqState) and not s.done
                    and st.cancel_ev is not None and st.cancel_ev.is_set()):
                from ray_tpu._private.core_worker import TaskCancelledError

                st.lease.release()
                s.fail(TaskCancelledError(f"llm:{self.deployment}"))

    def _live(self, seqs) -> List[Any]:
        return [
            s for s in seqs
            if isinstance(s.state, _SeqState) and not s.done
        ]

    def _prefill_step(self, seqs) -> None:
        pending = [
            s for s in self._live(seqs) if s.state.pos < len(s.state.prompt)
        ]
        if not pending:
            return
        with self._phase("prefill"):
            lanes = [(s, s.state) for s in pending[:self.prefill_lanes]]
            chunks = [
                st.prompt[st.pos:st.pos + self.prefill_chunk] for _, st in lanes
            ]
            tc = batching.bucket_pad_size(
                max(map(len, chunks)), self.prefill_token_buckets)
            # a lane whose prompt ends in this chunk samples its first token
            self._launch(lanes, chunks, tc, [
                st.pos + len(ch) >= len(st.prompt)
                for (_, st), ch in zip(lanes, chunks)])

    def _decode_step(self, seqs) -> None:
        # a lane is not launched past ``max_new``, which the host knows by
        # count; past an ``eos_token`` it may be, once: the id is in flight
        decoding = [
            s for s in self._live(seqs)
            if s.state.pos >= len(s.state.prompt)
            and s.state.sent < s.state.max_new
        ]
        max_lanes = self.lane_buckets[-1]
        while decoding:
            group, decoding = decoding[:max_lanes], decoding[max_lanes:]
            with self._phase("decode"):
                self._decode_lanes(group)

    def _decode_lanes(self, group) -> None:
        lanes = []
        for s in group:
            st = s.state
            try:
                try:
                    self._grow(st)
                except NoKVBlocksError:
                    if self._flight is None:
                        raise
                    # a sequence that ends in the call in flight gives its
                    # blocks back: land it, ask again
                    self._land()
                    if not s.done:
                        self._grow(st)
            except NoKVBlocksError as e:
                st.lease.release()
                s.fail(BackPressureError(str(e), retry_after_s=0.05))
                continue
            lanes.append((s, st))
        # such a landing may have ended a lane (its ``eos_token``)
        lanes = [(s, st) for s, st in lanes if not s.done]
        if lanes:
            self._launch(lanes, [None] * len(lanes), 1, [True] * len(lanes))

    def _grow(self, st: _SeqState) -> None:
        """Room in ``st``'s cache for the token about to be written: a block
        more where its last is full, and that block its own (copy-on-write)."""
        at = st.length // self.block_size
        if at >= len(st.blocks):
            st.lease.add(self.pool.allocate(at + 1 - len(st.blocks)))
        self.pool.ensure_private(st.blocks, at)

    # -- device call + paging: the launch, then (a call later) the landing --

    def _launch(self, lanes, chunks, tc: int, emits) -> Optional[_Call]:
        """Launch one device call and land the one before it. Each lane
        ``(sequence, state)`` is fed its chunk over its paged cache (``None``:
        its last token, wherever that is), the new K/V is paged back and the
        last valid row of each lane that ``emits`` made into logits and
        sampled (``heads`` of the call's record: how many such lanes), all on
        the device: one buffer goes up and nothing here waits for the device.
        The call is what is in flight from here on; its lanes' ``pos``,
        ``length`` and ``sent`` and the engine's token counts count it at once.
        None where the call before had to land first and ended every lane."""
        import jax

        bs, flight = self.block_size, self._flight

        def shape():
            t_max = max(
                st.length + (1 if ch is None else len(ch))
                for (_, st), ch in zip(lanes, chunks))
            return (
                batching.bucket_pad_size(len(lanes), self.lane_buckets),
                batching.bucket_pad_size(t_max, self.cache_buckets))

        b, t_cap = shape()
        if flight is not None and (
                any(st.on_host and st.call is flight for _, st in lanes)
                or not self._fits(b, tc, t_cap)):
            # the old order, for this call: the host makes these lanes' token,
            # or the device has no room for two calls' buffers
            self._land()
            flight = None
            # that landing may have ended a lane (its ``eos_token``): its lease
            # is back with the pool, and nothing more is written through it
            kept = [
                (lane, ch, emit) for lane, ch, emit in zip(lanes, chunks, emits)
                if not lane[0].done]
            if not kept:
                return None
            if len(kept) < len(lanes):
                lanes, chunks, emits = zip(*kept)
                b, t_cap = shape()
        states, decode = [st for _, st in lanes], chunks[0] is None
        form = "decode" if decode else "prefill"
        seq = self._over_forms("n") + 1
        program = _extend_name(b, tc, t_cap)
        with self._phase("upload"):
            cached = sum(st.length for st in states)    # live tokens in the lanes' caches
            # a lane's last token is on the host if its call has landed, else
            # in the one call in flight: the next program reads it there
            sources = [
                st.lane if ch is None and st.call is not None else -1
                for st, ch in zip(states, chunks)
            ]
            chunks = [
                ch if ch is not None else [0 if lane >= 0 else st.last_token]
                for st, ch, lane in zip(states, chunks, sources)
            ]
            # zeros are padding: block 0, whatever it holds, lies past a
            # frontier, and nothing is paged back past the count
            operands = np.zeros((b, self._operand_width), np.int32)
            tokens, rows, slots, table = _sections(operands)
            # a negative id is padding: a model may skip it (an expert
            # layer does), and none may let it change a real token
            tokens[:, :tc] = -1
            operands[:, _FROM] = operands[:, _LAST] = -1
            operands[:len(states), _FROM] = sources
            self.tokens_fed_on_device += sum(lane >= 0 for lane in sources)
            # page-back: token rows[i] of the call goes to arena slot slots[i]
            to_rows = np.zeros((b * tc,), np.int32)
            to_slots = np.zeros((b * tc,), np.int32)
            fed = 0
            for i, (st, ch) in enumerate(zip(states, chunks)):
                n = len(ch)
                tokens[i, :n] = ch
                operands[i, _LENGTH] = st.length
                if emits[i]:
                    operands[i, _LAST] = n - 1
                blocks = np.asarray(
                    st.blocks[:math.ceil((st.length + n) / bs)], np.int32)
                table[i, :len(blocks)] = blocks
                pos = st.length + np.arange(n)
                to_rows[fed:fed + n] = i * tc + np.arange(n)
                to_slots[fed:fed + n] = blocks[pos // bs] * bs + pos % bs
                fed += n
            rows[:, :tc] = to_rows.reshape(b, tc)
            slots[:, :tc] = to_slots.reshape(b, tc)
            operands[0, _COUNT] = fed
            if self._stateful:
                self._state_operands(operands, states, chunks, decode)
            self.h2d_bytes += operands.nbytes
            self.h2d_transfers += 1
            operands = jax.device_put(operands)
        paged = self._paged(tc)
        if paged:
            # the call reads its lanes' pages where the pool keeps them: it is handed
            # the arenas and its table, and there is no gather and no phase of one
            caches = self.pool.arenas
        else:
            with self._phase("kv_gather"):
                # slots past a lane's frontier hold what the pool holds there:
                # zeros or finite model output, which extend's mask weighs 0
                caches = self.pool.gather(operands, t_cap // bs)
                if self._count_gathered is not None:
                    for name, n in self._count_gathered(b, t_cap).items():
                        self.counted[name] += n
                if self._window_layers:
                    # live tokens older than the oldest position the lane's first
                    # query sees, length - window + 1
                    self.window_slots += self._window_layers * b * t_cap
                    self.window_slots_outside += self._window_layers * sum(
                        max(0, st.length - self._window + 1) for st in states)
        # what the call is: on its span, and summed under its form
        what = dict(
            lanes=len(states), lane_slots=b, heads=sum(emits), tokens=fed,
            token_slots=b * tc, cache_tokens=cached, cache_slots=b * t_cap, paged=int(paged))
        # a program nothing has run yet (a shape the warm-up left out, an engine
        # that was never warmed): this call traces and compiles it, and says so
        cold = program not in self.programs
        with self._phase(
                "dispatch", call=seq, form=form, program=program,
                ahead=int(flight is not None), **what, **({"cold": 1} if cold else {})):
            t0 = time.perf_counter()
            # the state arenas go in donated, and the ones that come back (each
            # lane's slot advanced, a kept state in its own) take their place
            logits, hidden, *rest = self._extend_call(
                program, self._params, operands,
                self._no_home if flight is None else flight.home, *caches,
                *self.pool.states, **self._extend_statics(tc, t_cap))
            news, self.pool.states, counted = self.pool.split_outputs(rest)
            del caches, rest        # the caches are freed when extend has run
            if cold:
                self._new_program(program)
                self.programs_cold += 1
                self.programs_cold_s += time.perf_counter() - t0
            self.programs[program]["n"] += 1
            counts = self.calls[form]
            counts["n"] += 1
            counts["lanes_used"] += what.pop("lanes")
            for key, n in what.items():
                counts[key] += n
            self.calls_ahead += flight is not None
        with self._phase("kv_scatter"):
            home = self.pool.page_back(
                news, operands, logits, counted, self.lane_buckets[-1])
            del news, operands
            call = _Call(
                home, (logits, hidden), lanes, emits, seq=seq, form=form, shape=(b, tc, t_cap),
                program=program, step=self.steps, launched_at=self._now())
            for i, (st, ch, emit) in enumerate(zip(states, chunks, emits)):
                st.call, st.lane = call, i
                st.length += len(ch)
                st.sent += emit
                if not decode:
                    st.pos += len(ch)
            if not decode:
                internal_metrics.inc(
                    "ray_tpu_llm_prefill_tokens_total", fed,
                    {"deployment": self.deployment},
                )
        if flight is not None:
            self._land()
        self._flight = call
        return call

    def _state_operands(self, operands, states, chunks, decode: bool) -> None:
        """The state columns of a call's operand buffer: each lane's slot and,
        for the chunk of a prompt that crosses the end of the blocks a later
        request may reuse, how far in that end lies and the slot its state goes
        to: one more of the sequence's lease, until the prefix cache takes it.
        Without a free slot the prompt is not cached."""
        for i, (st, ch) in enumerate(zip(states, chunks)):
            operands[i, _SLOT] = st.slot
            at = len(st.hashes) * self.block_size - st.length
            if decode or self.prefix is None or not 0 < at <= len(ch):
                continue
            try:
                st.snapshot = st.lease.add_slot()
            except NoKVBlocksError:
                continue
            operands[i, _SNAP_AT], operands[i, _SNAP_SLOT] = at, st.snapshot

    def _fits(self, b: int, tc: int, t_cap: int) -> bool:
        """Whether the device has room for what a call of this shape takes at
        its launch, beside what the call in flight still holds: the padded
        caches, ``extend``'s outputs and its temporaries (those of the largest
        shape, where ``warm`` has asked the compiler). Outputs are allocated
        when a program is launched, not when it runs. By the runtime's own
        count of free bytes, and of the largest free block where it keeps one
        (free bytes in pieces hold no cache); a backend that counts none (the
        CPU's) always has room."""
        if not self._counts_bytes:
            return True
        memory = self._device.memory_stats()
        caches = 0 if self._paged(tc) else self.pool.cache_bytes(b * t_cap)
        need = caches + self._output_bytes[b, tc] + self._temp_bytes
        free = memory["bytes_limit"] - memory["bytes_in_use"]
        return need <= min(free, memory.get("largest_free_block_bytes", free))

    def _land(self) -> _Call:
        """Land the call in flight: wait for the device, bring home one int32
        array (the greedy ids and, behind them, what an expert layer counted)
        and emit each lane's token. ``sampled`` holds a lane's ``(id, logits
        row, hidden row)``; the rows ``[vocab]`` and ``[embed]`` are None unless
        a lane of the call needs them on the host: one that returns its logits,
        or one whose adapter changes them (the hidden rows come home for an
        adapter alone). A forward that raised on the device raises here."""
        call, self._flight = self._flight, None
        states = [st for _, st in call.lanes]
        with self._phase("fetch", call=call.seq) as span:
            # what explains the wait: the program's usual call (one program's calls
            # spread under 1 %), counted from where this call could start. The unit
            # is told before the wait, so that the watcher takes no usual wait for a hold
            unit, known = self._unit, self._usual.get(call.program)
            started = max(call.launched_at, self._landed_at)
            asked = self._now()
            usual_s = known[1] / known[0] if known else None
            expected_s = 0.0 if usual_s is None else max(0.0, started + usual_s - asked)
            if unit is not None:
                unit.explained_s += expected_s
            # waits for the device; then the ids are home
            home = np.asarray(call.home)
            fetched = [home]
            now = self._now()
            busy_s = now - started
            self._landed_at = now
            if known is None:
                self._usual[call.program] = [1, busy_s]
            else:
                known[0] += 1
                known[1] += busy_s if known[0] <= 4 else min(busy_s, 2 * usual_s)
            if unit is not None:
                # a landing later than its program explains is held in ``fetch``:
                # of the wait, what the call took over its usual time
                waited_s = now - asked
                late_s = 0.0 if usual_s is None else min(waited_s, max(0.0, busy_s - usual_s))
                unit.explained_s += waited_s - late_s - expected_s
                self._late_s += late_s
            # behind the ids: what ``extend`` counted, in its names' order
            counted = {
                name: int(n)
                for name, n in zip(self._counter_names, home[self.lane_buckets[-1]:])}
            if counted:
                span.set_metadata(**counted)
            # what a landing learns of its call is the launching step's: this
            # one's own record takes it as it takes everything (``step``); an
            # earlier step's call is kept out of this step's record, and goes
            # to ``traced`` where that step was recorded
            books = [(self.counted, self.calls, self.programs)]
            if call.step != self.steps:
                for work in (self._step_began, self.traced if call.recorded else None):
                    if work is not None:
                        books.append((work, work["calls"], work["programs"]))
            for flat, calls, programs in books:
                for name, n in counted.items():
                    flat[name] += n
                calls[call.form]["busy_s"] += busy_s
                programs[call.program]["busy_s"] += busy_s
            adapted = any(st.adapter is not None for st in states)
            logits = hidden = None
            if adapted or any(st.return_logits for st in states):
                logits = np.asarray(call.picked[0])
                fetched.append(logits)
                if adapted:
                    hidden = np.asarray(call.picked[1])
                    fetched.append(hidden)
            else:
                self.ids_only_calls += 1
            call.home = call.picked = None      # nothing reads them on the device now
            self.d2h_transfers += len(fetched)
            self.d2h_bytes += sum(a.nbytes for a in fetched)
            call.sampled = [
                (int(home[i]), None if logits is None else logits[i],
                 None if hidden is None else hidden[i])
                for i in range(len(states))
            ]
        with self._phase("sample"):
            for (s, st), emits, sampled in zip(call.lanes, call.emits, call.sampled):
                if st.call is call:
                    st.call = None
                # a lane that ended while the call was in flight (its
                # ``eos_token`` landed, it was cancelled or shed) drops the id.
                # Its blocks went back then, with this call's writes to them
                # still to come: device programs run in launch order, so the
                # next owner's own writes come after these, and until then it
                # masks every slot past its frontier.
                if not emits or s.done or st.lease.released:
                    continue
                if not st.out and self.prefix is not None:
                    # its first token: the prompt is whole in the cache. Cache
                    # every full prompt block (first writer wins); with the
                    # state at their end, where the model keeps one
                    kept = st.snapshot if (
                        st.snapshot is not None and st.lease.give_slot(st.snapshot)) else None
                    if not self.prefix.insert(
                            st.hashes, st.blocks[:len(st.hashes)], kept) and kept is not None:
                        self.pool.free_slot(kept)
                self._emit(s, st, *sampled)
        return call

    # -- sampling / completion --------------------------------------------

    def _emit(self, s, st: _SeqState, tok: int, logits_row, hidden_row) -> None:
        """Lane ``st`` sampled ``tok`` on the device. The rows are there where
        the call brought them home (``_land``)."""
        if st.adapter is not None:
            a, bmat, scale = st.adapter
            logits_row = logits_row + scale * (hidden_row @ a) @ bmat
            tok = int(np.argmax(logits_row))    # the delta may move the maximum
        st.out.append(tok)
        st.last_token = tok
        if st.logits is not None:
            st.logits.append(np.asarray(logits_row, np.float32).copy())
        if st.ttft_s is None:
            st.ttft_s = time.monotonic() - s.enqueued_at
            internal_metrics.observe(
                "ray_tpu_llm_ttft_seconds", st.ttft_s,
                {"deployment": self.deployment},
            )
            internal_metrics.observe(
                "ray_tpu_llm_queue_seconds", st.queue_s,
                {"deployment": self.deployment},
            )
        if st.stream_q is not None:
            st.stream_q.put(("tok", tok))
        if len(st.out) >= st.max_new or (st.eos is not None
                                         and tok == st.eos):
            self._finish(s, st)

    def _finish(self, s, st: _SeqState) -> None:
        if st.slot is not None:
            self.last_finished = (len(st.prompt) + len(st.out) - 1, st.slot)
        st.lease.release()
        result: Dict[str, Any] = {
            "tokens": st.out,
            "ttft_s": st.ttft_s,
            "queue_s": st.queue_s,
            "prefix_cached_tokens": st.cached_tokens,
            "prefill_tokens": len(st.prompt) - st.cached_tokens,
            "model_id": st.model_id,
        }
        if st.logits is not None:
            result["logits"] = np.stack(st.logits)
        s.finish(result)
        if st.stream_q is not None:
            st.stream_q.put(("end", result))


def _add_difference(into: Dict[str, Any], after: Dict[str, Any], before: Dict[str, Any]) -> None:
    """``into += after - before``, number by number, through groups of numbers;
    a group that ``before`` lacks (a program first called since) counts from zero."""
    for key, value in after.items():
        if isinstance(value, dict):
            _add_difference(into[key], value, before.get(key, {}))
        else:
            into[key] += value - before.get(key, 0)


# ---------------------------------------------------------------------------
# deployment-facing server
# ---------------------------------------------------------------------------


class LLMServer:
    """Deployment callable: ``__call__(payload) -> result`` (blocking) and
    ``stream(payload)`` (token generator). Payloads:

    ``{"prompt": [token ids], "max_new_tokens": n, "model_id": "lora:x",
    "eos_token": id, "return_logits": bool}``

    Results carry ``tokens``, ``ttft_s`` (replica enqueue -> first token),
    ``queue_s`` (enqueue -> admitted with its KV blocks, so ``ttft_s -
    queue_s`` is the prefill the request itself needed),
    ``prefix_cached_tokens`` and ``prefill_tokens``. Deploy with
    ``slo_ttft_p99_s=...`` to get the auto-registered
    ``serve-<name>-ttft-p99`` SLO rule."""

    def __init__(self, cfg=None, **engine_kwargs):
        self._engine = LLMEngine(cfg, **engine_kwargs)

    @staticmethod
    def concurrent_queries(*_, lane_buckets: Sequence[int] = LANE_BUCKETS, **__) -> int:
        """The requests a replica bound with these engine sizes runs at once
        (``serve.deployment`` reads it where it names no ``max_concurrent_queries``,
        and never goes under its own default for it): a request a decode lane, so
        the engine's largest lane bucket, as far as ``generate`` batches."""
        return min(max(lane_buckets), MAX_LANES)

    @batching.continuous_batch(max_batch_size=MAX_LANES, batch_wait_timeout_s=0.001)
    def generate(self, seqs):
        self._engine.step(seqs)

    def __call__(self, payload):
        return self.generate(payload)

    def stream(self, payload):
        """Yield tokens as they decode. Closing the generator (client EOF)
        cancels the sequence and releases its KV blocks."""
        out: "queue_mod.Queue" = queue_mod.Queue()
        cancel = threading.Event()
        payload = dict(payload)
        payload[_STREAM_KEY] = out
        payload[_CANCEL_KEY] = cancel
        err: List[BaseException] = []

        def run():
            try:
                self.generate(payload)
            except BaseException as e:  # noqa: BLE001
                err.append(e)
                out.put(("error", e))

        threading.Thread(target=run, daemon=True).start()
        try:
            while True:
                kind, val = out.get(timeout=120.0)
                if kind == "tok":
                    yield val
                elif kind == "end":
                    return
                else:
                    raise val
        finally:
            cancel.set()

    def kv_stats(self) -> Dict[str, Any]:
        return self._engine.stats()
