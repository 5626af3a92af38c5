"""LLM inference engine (serve.llm): paged KV-cache accounting, prefix
caching correctness (including bitwise cached-vs-uncached decode), the
prefill/decode split, LoRA multiplexing, and the KV leak surface under
cancel / shed / chaos-kill."""

import json
import socket
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import (
    cohere2_moe, glm_moe_dsa, gpt, granitemoehybrid, keye_vl2, kimi_k2, longcat_flash,
    mimo_v2_flash, qwen3_next)
from ray_tpu.serve import batching, llm
from ray_tpu.serve.llm import (
    LANE_BUCKETS,
    MAX_LANES,
    KVBlockPool,
    KVLease,
    LLMEngine,
    LLMServer,
    NoKVBlocksError,
    PrefixCache,
    _extend_name,
    chain_hashes,
    random_lora,
)

CFG = gpt.gpt_nano()
#: the pool's own tests run over both kinds of per-token state: K and V (two
#: arenas), and K, V and an indexer's key (three, of two shapes); and over both
#: ways the paging programs move an arena: a row that is no whole number of the
#: chip's 128 lanes (every arena of the two nano presets) goes a block's tokens
#: last, a whole one (K and V at a head of 128, as on the chip) as it is shaped
both_kinds_of_state = pytest.mark.parametrize(
    "cfg", [
        CFG, keye_vl2.keye_vl2_nano(),
        keye_vl2.keye_vl2_nano(num_heads=4, kv_heads=2, head_dim=128)],
    ids=["two-arenas", "three-arenas", "three-arenas-whole-lanes"])


def test_the_pools_presets_cover_both_ways_an_arena_is_moved():
    """``both_kinds_of_state``'s presets against what the paging programs ask of an
    arena (its last size, a whole number of 128 lanes or not): the two nano
    presets' arenas all go tokens last, the third's K and V as they are shaped
    beside an indexer's key that goes tokens last, which is the chip's Keye."""
    whole = [
        [not dim % 128 for _, dim in cfg.cache_arrays]
        for cfg in both_kinds_of_state.args[1]]
    assert whole == [[False, False], [False, False, False], [True, True, False]]


def _prompt(seed: int, n: int):
    return [
        int(t)
        for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, n)
    ]


@pytest.fixture(scope="module")
def llm_server():
    """One in-process LLMServer shared by the numerics tests (amortizes
    the jit compiles of the bucketed prefill/decode shapes)."""
    srv = LLMServer(
        CFG, num_blocks=64, block_size=16, prefill_lanes=2,
        lane_buckets=(1, 2, 4), prefill_token_buckets=(16, 32),
        cache_buckets=(64, 128), prefix_caching=True,
        adapter_loader=lambda mid: _ADAPTERS[mid],
    )
    yield srv
    batching.shutdown_batchers(srv)


_AD = random_lora(CFG, rank=4, seed=3, scale=4.0)
_ADAPTERS = {"lora:a": (_AD["A"], _AD["B"], _AD["scale"])}


@pytest.fixture
def serve_session(ray_start_regular):
    yield
    serve.shutdown()


def _await(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# KV block pool: refcounts, exactly-once leases, copy-on-write
# ---------------------------------------------------------------------------


@both_kinds_of_state
def test_kv_pool_allocate_free_refcounts(cfg):
    pool = KVBlockPool(cfg, num_blocks=8, block_size=4)
    a = pool.allocate(3)
    assert pool.in_use() == 3
    pool.incref(a[:1])
    pool.free(a)                      # drops one ref on each
    assert pool.in_use() == 1         # a[0] still held by the incref
    pool.free(a[:1])
    assert pool.in_use() == 0
    with pytest.raises(NoKVBlocksError):
        pool.allocate(9)
    assert pool.in_use() == 0         # failed allocation takes nothing


@both_kinds_of_state
def test_kv_lease_releases_exactly_once(cfg):
    pool = KVBlockPool(cfg, num_blocks=8, block_size=4)
    lease = KVLease(pool)
    lease.add(pool.allocate(4))
    before = pool.freed_total
    for _ in range(5):                # finish + cancel + poison + ... races
        lease.release()
    assert pool.in_use() == 0
    assert pool.freed_total == before + 4
    # a straggler add after release must not leak either
    lease.add(pool.allocate(1))
    assert pool.in_use() == 0


def _fill_block(pool, block, value):
    """Write ``value``, ``-value``, ``value + 1``, ... into one block of the
    device arenas, an arena each."""
    pool.arenas = tuple(
        a.at[:, block].set(v) for a, v in zip(pool.arenas, _fills(pool, value)))


def _fills(pool, value):
    return [(value + i // 2) * (-1) ** i for i in range(len(pool.arenas))]


@both_kinds_of_state
def test_kv_pool_copy_on_write(cfg):
    import jax

    pool = KVBlockPool(cfg, num_blocks=8, block_size=4)
    assert len(pool.arenas) == len(cfg.cache_arrays)
    assert all(isinstance(a, jax.Array) for a in pool.arenas)
    assert pool.k_data is pool.arenas[0] and pool.v_data is pool.arenas[1]
    (shared,) = pool.allocate(1)
    _fill_block(pool, shared, 7.0)
    pool.incref([shared])             # second holder (e.g. prefix cache)
    blocks = [shared]
    new = pool.ensure_private(blocks, 0)
    assert new != shared and blocks[0] == new
    held = pool.read_block(new)
    assert [a.shape for a in held] == [
        (cfg.num_layers, 4) + tuple(each) for each in cfg.cache_arrays]
    assert all(np.all(a == v) for a, v in zip(held, _fills(pool, 7.0)))   # contents cloned
    assert pool.refcount(shared) == 1            # our ref moved off it
    _fill_block(pool, new, 9.0)
    assert all(                                  # original untouched
        np.all(a == v) for a, v in zip(pool.read_block(shared), _fills(pool, 7.0)))
    # unshared block: no clone
    assert pool.ensure_private(blocks, 0) == new


@both_kinds_of_state
def test_clone_block_copies_one_block_on_the_device(cfg):
    """The clone moves exactly one block of every arena and nothing else,
    without the arenas leaving the device."""
    import jax
    import jax.numpy as jnp

    pool = KVBlockPool(cfg, num_blocks=6, block_size=4)
    rng = np.random.RandomState(0)
    was = [rng.standard_normal(a.shape).astype(np.float32) for a in pool.arenas]
    pool.arenas = tuple(jnp.asarray(a) for a in was)
    pool.clone_block(4, 1)
    assert all(isinstance(a, jax.Array) for a in pool.arenas)
    for a in was:
        a[:, 1] = a[:, 4]
    assert all(np.array_equal(np.asarray(a), w) for a, w in zip(pool.arenas, was))
    assert all(np.array_equal(a, w[:, 4]) for a, w in zip(pool.read_block(1), was))


@both_kinds_of_state
def test_pool_that_does_not_fit_fails_at_construction_with_its_sizes(monkeypatch, cfg):
    import jax.numpy as jnp

    def refuses(shape, dtype):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(jnp, "zeros", refuses)
    with pytest.raises(MemoryError) as ei:
        KVBlockPool(cfg, num_blocks=8, block_size=4)
    held = cfg.num_layers * 8 * 4 * sum(heads * dim for heads, dim in cfg.cache_arrays) * 4
    assert f"are {held} bytes" in str(ei.value) and "8 blocks" in str(ei.value)
    assert str(cfg.cache_arrays) in str(ei.value)


# ---------------------------------------------------------------------------
# prefix cache: chained hashes, LRU eviction under pool pressure
# ---------------------------------------------------------------------------


def test_chain_hashes_commit_to_prefix():
    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)   # 2 full blocks
    b = chain_hashes([1, 2, 3, 4, 5, 6, 7, 99], 4)
    assert len(a) == 2 and len(b) == 2
    assert a[0] == b[0]               # shared first block
    assert a[1] != b[1]               # divergent token invalidates block 2
    # a divergent EARLY token invalidates every later block (chained)
    c = chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert c[0] != a[0] and c[1] != a[1]


@both_kinds_of_state
def test_prefix_cache_match_insert_evict(cfg):
    pool = KVBlockPool(cfg, num_blocks=4, block_size=4)
    cache = PrefixCache(pool)
    hashes = chain_hashes(list(range(8)), 4)
    blocks = pool.allocate(2)
    cache.insert(hashes, blocks)
    assert pool.refcount(blocks[0]) == 2
    got = cache.match(hashes)
    assert got == blocks and cache.hits == 2
    pool.free(got)                    # matched refs back
    pool.free(blocks)                 # original owner done: cache-only now
    assert pool.in_use() == 2         # cache keeps them resident
    # pool pressure evicts idle cached blocks LRU-first
    more = pool.allocate(4)
    assert len(more) == 4 and len(cache) == 0 and cache.evictions == 2


# ---------------------------------------------------------------------------
# engine numerics: real gpt decode, prefix reuse bitwise-equal
# ---------------------------------------------------------------------------


def test_first_token_matches_full_forward(llm_server):
    """The engine's first sampled token equals greedy argmax of the full
    (non-cached) training forward at the last prompt position."""
    import jax.numpy as jnp

    prompt = _prompt(0, 24)
    r = llm_server({"prompt": prompt, "max_new_tokens": 1})
    model = gpt.GPT(CFG)
    variables = {"params": llm_server._engine._params}
    ref = model.apply(variables, jnp.asarray([prompt], jnp.int32))
    assert r["tokens"][0] == int(np.argmax(np.asarray(ref)[0, -1]))


def test_prefix_cache_hits_skip_prefill_and_decode_bitwise(llm_server):
    srv = llm_server
    prompt = _prompt(1, 40)
    s0 = srv.kv_stats()
    r1 = srv({"prompt": prompt, "max_new_tokens": 6, "return_logits": True})
    assert r1["prefix_cached_tokens"] == 0 and r1["prefill_tokens"] == 40
    reqs = [
        srv({"prompt": prompt, "max_new_tokens": 6, "return_logits": True})
        for _ in range(3)
    ]
    s1 = srv.kv_stats()
    assert s1["prefix_hits"] > s0["prefix_hits"]       # counter increments
    for r in reqs:
        assert r["prefix_cached_tokens"] == 32         # 2 of 3 blocks reused
        assert r["prefill_tokens"] == 8                # prefill FLOPs skipped
        assert r["tokens"] == r1["tokens"]
        # cached-KV decode is BITWISE identical to the uncached decode
        assert np.array_equal(r["logits"], r1["logits"])


def test_prefix_cached_decode_matches_cacheless_engine(llm_server):
    """Cross-engine: logits from the prefix-cached request equal those of
    a fresh engine with prefix caching disabled, bit for bit."""
    prompt = _prompt(2, 33)
    warm = llm_server(
        {"prompt": prompt, "max_new_tokens": 4, "return_logits": True})
    hit = llm_server(
        {"prompt": prompt, "max_new_tokens": 4, "return_logits": True})
    assert hit["prefix_cached_tokens"] > 0
    plain = LLMServer(
        CFG, num_blocks=64, block_size=16, prefill_lanes=2,
        lane_buckets=(1, 2, 4), prefill_token_buckets=(16, 32),
        cache_buckets=(64, 128), prefix_caching=False,
    )
    try:
        ref = plain(
            {"prompt": prompt, "max_new_tokens": 4, "return_logits": True})
        assert ref["prefix_cached_tokens"] == 0
        assert np.array_equal(hit["logits"], ref["logits"])
        assert hit["tokens"] == ref["tokens"] == warm["tokens"]
    finally:
        batching.shutdown_batchers(plain)


def test_divergent_suffix_invalidates_correctly(llm_server):
    """Two prompts sharing a system prefix but diverging afterwards reuse
    only the shared blocks and produce independent (correct) outputs."""
    system = _prompt(3, 32)
    pa = system + _prompt(4, 8)
    pb = system + _prompt(5, 8)
    ra1 = llm_server({"prompt": pa, "max_new_tokens": 5})
    rb1 = llm_server({"prompt": pb, "max_new_tokens": 5})
    ra2 = llm_server({"prompt": pa, "max_new_tokens": 5})
    rb2 = llm_server({"prompt": pb, "max_new_tokens": 5})
    assert ra2["prefix_cached_tokens"] >= 32
    assert rb2["prefix_cached_tokens"] >= 32
    assert ra1["tokens"] != rb1["tokens"]      # suffix actually matters
    assert ra1["tokens"] == ra2["tokens"]
    assert rb1["tokens"] == rb2["tokens"]


def test_lora_adapter_changes_logits(llm_server):
    prompt = _prompt(6, 24)
    base = llm_server({"prompt": prompt, "max_new_tokens": 6})
    lora = llm_server(
        {"prompt": prompt, "max_new_tokens": 6, "model_id": "lora:a"})
    assert lora["tokens"] != base["tokens"]
    assert "lora:a" in llm_server.kv_stats()["adapters_resident"]


def test_ttft_reported_and_concurrent_batching(llm_server):
    out = []

    def call(i):
        out.append(llm_server(
            {"prompt": _prompt(50 + i, 20), "max_new_tokens": 8}))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(out) == 4
    for r in out:
        assert r["ttft_s"] is not None and 0 < r["ttft_s"] < 60
        assert len(r["tokens"]) == 8


# ---------------------------------------------------------------------------
# the pool on the device: paging is bitwise a host-built pair, clones before
# a shared write, and compiles nothing once the engine is built
# ---------------------------------------------------------------------------

_PAGING = dict(
    num_blocks=48, block_size=16, prefill_chunk=32,
    lane_buckets=(1, 2, 4), prefill_token_buckets=(16, 32),
    cache_buckets=(64, 128),
)


def _sequences(lengths, new, **asks):
    return [
        batching._Sequence({"prompt": _prompt(70 + i, n), "max_new_tokens": new, **asks})
        for i, n in enumerate(lengths)
    ]


def _drive(eng, seqs):
    steps = 0
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 200
    for s in seqs:
        assert s._error is None, s._error


def _paged(pool, blocks, n):
    """The first ``n`` tokens of the caches that ``blocks`` page, on the host:
    one array per arena."""
    return [
        np.concatenate(held, axis=1)[:, :n]
        for held in zip(*(pool.read_block(b) for b in blocks))]


@both_kinds_of_state
def test_paged_device_calls_are_bitwise_a_zero_padded_host_pair(cfg):
    """Every device call of a mixed workload (1 to 4 lanes, lengths that end
    inside a block, a pool that starts full of finite garbage) against the
    same ``extend`` fed a zero-padded pair built on the host from a mirror of
    each lane's cache: the sampled rows (which an adapter that adds nothing
    brings home), the ids sampled from them on the device and the pool's live
    contents are the same bits."""
    import jax.numpy as jnp

    nothing = (
        np.zeros((cfg.embed_dim, 1), np.float32), np.zeros((1, cfg.vocab_size), np.float32), 0.0)
    eng = LLMEngine(
        cfg, prefix_caching=False, prefill_lanes=2, adapter_loader=lambda mid: nothing, **_PAGING)
    pool, rng = eng.pool, np.random.RandomState(7)
    pool.arenas = tuple(
        jnp.asarray(rng.standard_normal(a.shape), pool.dtype) for a in pool.arenas)
    held = len(pool.arenas)

    def empty():
        return [np.zeros((cfg.num_layers, 0) + tuple(each)) for each in cfg.cache_arrays]

    mirror, calls, wanted, launch, land = {}, [], {}, eng._launch, eng._land

    def launched(lanes, chunks, tc, emits):
        # the host makes an adapter lane's token: its call lands before the
        # lane is fed again (the engine would see to it; the mirror needs it now)
        states = [st for _, st in lanes]
        if any(st.call is not None for st in states):
            assert all(st.call in (None, eng._flight) for st in states)
            eng._land()
        chunks = [ch if ch is not None else [st.last_token] for st, ch in zip(states, chunks)]
        b = batching.bucket_pad_size(len(states), eng.lane_buckets)
        cap = batching.bucket_pad_size(
            max(st.length + len(ch) for st, ch in zip(states, chunks)), eng.cache_buckets)
        caches = [
            np.zeros((cfg.num_layers, b, cap) + tuple(each), pool.dtype)
            for each in cfg.cache_arrays]
        # a negative id is padding, as the engine marks it
        tokens, lengths = np.full((b, tc), -1, np.int32), np.zeros((b,), np.int32)
        for i, (st, ch) in enumerate(zip(states, chunks)):
            _, mine = mirror.setdefault(id(st), (st, empty()))
            assert mine[0].shape[1] == st.length
            for cache, m in zip(caches, mine):
                cache[:, i, :st.length] = m
            tokens[i, :len(ch)], lengths[i] = ch, st.length
        # the row the engine says each lane reads: its last, where it emits
        last = np.full((b,), -1, np.int32)
        last[:len(states)] = [len(ch) - 1 if emit else -1 for ch, emit in zip(chunks, emits)]
        want = [
            np.asarray(o)
            for o in eng._extend(eng._params, tokens, lengths, *caches, last=last)]
        call = launch(lanes, chunks, tc, emits)
        wanted[id(call)] = (call, states, chunks, want)
        calls.append((len(states), tc, cap, [mirror[id(st)][1][0].shape[1] % eng.block_size for st in states]))
        return call

    def landed():
        call = land()
        _, states, chunks, (logits, hidden, *rest) = wanted.pop(id(call))
        news = rest[:held]
        assert len(call.sampled) == len(states)
        for i, (st, ch) in enumerate(zip(states, chunks)):
            n = len(ch)
            tok, logits_row, hidden_row = call.sampled[i]
            assert np.array_equal(logits_row, logits[i])
            assert np.array_equal(hidden_row, hidden[i])
            assert tok == np.argmax(logits[i])
            mine = [
                np.concatenate([m, new[:, i, :n]], axis=1)
                for m, new in zip(mirror[id(st)][1], news)]
            mirror[id(st)] = (st, mine)
            # nothing was fed to the lane since (its call had to land first)
            assert st.length == mine[0].shape[1]
            paged = _paged(pool, st.blocks, st.length)
            assert len(paged) == held and all(
                np.array_equal(p, m) for p, m in zip(paged, mine))
        return call

    eng._launch, eng._land = launched, landed
    _drive(eng, _sequences((20, 40, 9, 33, 70), 5, model_id="lora:nothing"))
    assert not wanted and eng._flight is None       # every call was landed
    assert {c[0] for c in calls} >= {1, 2, 3, 4}
    assert {c[1] for c in calls} == {1, 16, 32} and {c[2] for c in calls} == {64, 128}
    assert any(r for c in calls for r in c[3])      # frontiers inside a block


@both_kinds_of_state
def test_engine_clones_a_shared_tail_block_on_the_device_before_writing_it(cfg):
    eng = LLMEngine(cfg, prefix_caching=False, **_PAGING)
    (seq,) = _sequences((20,), 6)
    eng.step([seq])                             # prefill and the first decode
    st, bs = seq.state, eng.block_size
    assert st.length == 21 and not seq.done
    tail = st.blocks[st.length // bs]
    eng.pool.incref([tail])                     # a second holder appears
    was = eng.pool.read_block(tail)
    assert all(a[:, :5].any() and not a[:, 5:].any() for a in was)
    eng.step([seq])                             # writes token 21: must clone
    clone = st.blocks[1]
    assert clone != tail and eng.pool.refcount(tail) == 1
    assert all(                                                 # original intact
        np.array_equal(a, b) for a, b in zip(was, eng.pool.read_block(tail)))
    for a, c in zip(was, eng.pool.read_block(clone)):
        assert np.array_equal(c[:, :5], a[:, :5])
        assert c[:, 5].any() and not c[:, 6:].any()
    _drive(eng, [seq])
    eng.pool.free([tail])
    assert eng.pool.in_use() == 0
    undisturbed = _sequences((20,), 6)
    _drive(eng, undisturbed)
    assert seq._result["tokens"] == undisturbed[0]._result["tokens"]


def test_nothing_compiles_once_the_engine_is_built_and_extend_is_warm():
    """Every (lanes, tokens, cache) bucket combination, with and without the
    rows coming home, a clone and a whole request with a prefix hit, after
    construction and the engine's warm-up of ``extend``: not one compile
    request reaches the backend."""
    import itertools
    import math

    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    from ray_tpu.serve.llm import _SeqState

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == BACKEND_COMPILE_EVENT else None)
    eng = LLMEngine(CFG, prefix_caching=True, prefill_lanes=4, **_PAGING)
    combos = list(itertools.product(
        eng.lane_buckets, [1] + eng.prefill_token_buckets, eng.cache_buckets))
    assert eng.extend_shapes() == combos
    # one compiled program a shape, each a member of the family under its own name
    assert eng.warm()["shapes"] == eng._extend_call._cache_size() == len(combos)
    assert set(eng.stats()["programs"]) == {_extend_name(*shape) for shape in combos} <= set(
        eng._extend_call.names())
    assert compiles                             # the listener hears a compile
    del compiles[:]

    seen, asked, landed, land = set(), {}, [], eng._land
    eng._land = lambda: landed.append(land()) or landed[-1]
    bs = eng.block_size
    for b, tc, cap in combos:
        lanes = b if b <= 2 else b - 1          # a padded lane where there can be one
        chunk = 1 if tc == 1 else tc - 1
        states = []
        for i in range(lanes):
            st = _SeqState()
            st.length, st.pos, st.sent, st.call = cap - tc - 3, 0, 0, None  # ends inside a block
            st.blocks = eng.pool.allocate(math.ceil((st.length + chunk) / bs))
            st.adapter, st.return_logits = None, i == 0 and cap == eng.cache_buckets[0]
            states.append(st)
        lanes0, slots0 = eng.lane_slots, eng.cache_slots
        # launched behind the call before it, which lands then; no lane emits
        call = eng._launch(
            [(None, st) for st in states], [[1] * chunk] * lanes, tc, [False] * lanes)
        seen.add((eng.lane_slots - lanes0, tc, eng.cache_slots - slots0))
        asked[id(call)] = (call, lanes, states[0].return_logits)
        assert eng._flight is call and len(landed) == len(asked) - 1
        for st in states:
            eng.pool.free(st.blocks)
    eng._land()                                 # the last, with nothing behind it
    assert [id(c) for c in landed] == list(asked) and eng._flight is None
    for call, lanes, rows in asked.values():
        assert len(call.sampled) == lanes
        assert all(0 <= tok < CFG.vocab_size for tok, _, _ in call.sampled)
        assert all((row is not None) == rows for _, row, _ in call.sampled)
    assert seen == {(b, tc, b * cap) for b, tc, cap in combos}
    blocks = eng.pool.allocate(1)
    eng.pool.incref(blocks)
    assert eng.pool.ensure_private(blocks, 0) != eng.pool.free(blocks)     # the clone ran
    eng.pool.free(blocks)
    first, again = (_sequences((40,), 4) for _ in range(2))
    _drive(eng, first)
    _drive(eng, again)
    assert again[0]._result["prefix_cached_tokens"] == 32
    assert again[0]._result["tokens"] == first[0]._result["tokens"]
    assert compiles == []


# ---------------------------------------------------------------------------
# leak surface: shed, stream-cancel, batcher cancellation hooks
# ---------------------------------------------------------------------------


def _leaked(stats):
    return stats["kv_blocks_in_use"] - stats["prefix_cached_blocks"]


@both_kinds_of_state
def test_kv_exhaustion_sheds_without_leak(cfg):
    srv = LLMServer(cfg, num_blocks=2, block_size=16, prefix_caching=False,
                    cache_buckets=(64,))
    try:
        with pytest.raises(serve.BackPressureError) as ei:
            srv({"prompt": _prompt(7, 40), "max_new_tokens": 4})
        assert ei.value.retry_after_s > 0
        assert srv.kv_stats()["kv_blocks_in_use"] == 0
        # pool drained by an admitted sequence -> later request sheds, then
        # succeeds after the first finishes
        r = srv({"prompt": _prompt(8, 20), "max_new_tokens": 4})
        assert len(r["tokens"]) == 4
        assert srv.kv_stats()["kv_blocks_in_use"] == 0
    finally:
        batching.shutdown_batchers(srv)


def test_stream_cancel_releases_kv_exactly_once(llm_server):
    srv = llm_server
    before = srv.kv_stats()
    gen = srv.stream({"prompt": _prompt(9, 30), "max_new_tokens": 80})
    first = next(gen)
    assert isinstance(first, int)
    mid = srv.kv_stats()
    assert mid["kv_blocks_in_use"] > before["kv_blocks_in_use"]
    gen.close()                        # client walks away mid-decode
    _await(
        lambda: _leaked(srv.kv_stats()) == 0,
        10, "KV blocks released after stream cancel",
    )
    # freed exactly once: pool accounting is exact, not merely <= capacity
    after = srv.kv_stats()
    assert after["kv_blocks_in_use"] == after["prefix_cached_blocks"]


def test_batcher_release_hook_fires_exactly_once_on_cancel():
    released = []
    seen = {}

    def step(seqs):
        for s in seqs:
            if s.state is None:
                s.state = 0
                s.on_release = lambda s=s: released.append(s)
                seen[id(s)] = s
            # never finishes: only cancellation can end it

    b = batching._ContinuousBatcher(step, 4, 0.001, None, name="t")
    try:
        result = {}
        t = threading.Thread(
            target=lambda: result.update(r=b.submit("x")), daemon=True)
        t.start()
        _await(lambda: seen, 5, "sequence admitted")
        seq = next(iter(seen.values()))
        seq.cancelled = True           # what submit does when its caller
        with b.cv:                     # is cancelled / force-interrupted
            b.cv.notify_all()
        _await(lambda: len(released) == 1, 5, "release hook")
        time.sleep(0.1)                # more steps run: hook must not refire
        assert len(released) == 1
        assert seq._event.is_set()
    finally:
        b.shutdown(drain=False)


def test_batcher_poisoned_step_runs_release_hooks():
    released = []

    def step(seqs):
        for s in seqs:
            s.on_release = lambda: released.append(1)
        raise RuntimeError("forward crashed")

    b = batching._ContinuousBatcher(step, 4, 0.001, None, name="t")
    try:
        with pytest.raises(RuntimeError, match="forward crashed"):
            b.submit("x")
        assert released == [1]
    finally:
        b.shutdown(drain=False)


# ---------------------------------------------------------------------------
# serve-level: client EOF via the async proxy, chaos-kill mid-decode
# ---------------------------------------------------------------------------

_ENGINE_KW = dict(
    num_blocks=32, block_size=16, prefill_lanes=2, lane_buckets=(1, 2),
    prefill_token_buckets=(16, 32), cache_buckets=(128,),
    prefix_caching=False,
    # stretch each engine step so the decode outlives the kv_stats polls
    # (a 90-token gpt_nano decode completes in well under a second raw)
    step_delay_s=0.05,
)


def test_client_eof_releases_kv_blocks(serve_session):
    """A client that hangs up mid-decode must release the sequence's KV
    blocks: the proxy cancels the in-flight call cooperatively and the
    batcher-blocked replica thread notices (the PR 9 slot discipline,
    extended to the KV lease)."""
    dep = serve.deployment(
        LLMServer, name="llmcancel", max_concurrent_queries=4,
    ).bind(None, **_ENGINE_KW)
    serve.run(dep)
    h = serve.get_deployment_handle("llmcancel")
    proxy = serve.start_http_proxy()
    try:
        # warm: compile prefill+decode buckets so the cancel phase is fast
        warm = h.remote(
            {"prompt": _prompt(10, 30), "max_new_tokens": 2}).result(
                timeout=120)
        assert len(warm["tokens"]) == 2
        assert h.kv_stats.remote().result(timeout=30)[
            "kv_blocks_in_use"] == 0

        payload = json.dumps(
            {"prompt": _prompt(11, 30), "max_new_tokens": 90}).encode()
        request = (
            f"POST /llmcancel HTTP/1.1\r\nHost: {proxy.host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n"
        ).encode() + payload
        conn = socket.create_connection((proxy.host, proxy.port))
        conn.sendall(request)
        _await(
            lambda: h.kv_stats.remote().result(timeout=30)[
                "kv_blocks_in_use"] > 0,
            30, "decode in flight",
        )
        conn.close()                   # client EOF mid-decode
        _await(
            lambda: h.kv_stats.remote().result(timeout=30)[
                "kv_blocks_in_use"] == 0,
            30, "KV blocks released after client EOF",
        )
    finally:
        proxy.stop()


@pytest.mark.slow
def test_chaos_kill_replica_mid_decode_fresh_pool(serve_session):
    """Kill the replica mid-decode: the replacement replica's pool starts
    empty (no phantom leases) and serves fresh traffic."""
    dep = serve.deployment(
        LLMServer, name="llmchaos", max_concurrent_queries=4,
    ).bind(None, **_ENGINE_KW)
    h = serve.run(dep)
    warm = h.remote(
        {"prompt": _prompt(12, 30), "max_new_tokens": 2}).result(timeout=120)
    assert len(warm["tokens"]) == 2
    h._refresh(force=True)
    victim = h._replicas[0]

    def long_call():
        try:
            h.remote(
                {"prompt": _prompt(13, 30), "max_new_tokens": 90}
            ).result(timeout=60)
        except Exception:
            pass                       # killed mid-flight: expected

    t = threading.Thread(target=long_call, daemon=True)
    t.start()
    _await(
        lambda: h.kv_stats.remote().result(timeout=30)[
            "kv_blocks_in_use"] > 0,
        30, "decode in flight",
    )
    ray_tpu.kill(victim)
    t.join(timeout=90)
    # the controller restarts the replica; its pool must start at zero
    _await(
        lambda: _fresh_pool_ok(h), 60, "replacement replica with empty pool")
    r = h.remote(
        {"prompt": _prompt(14, 20), "max_new_tokens": 3}).result(timeout=120)
    assert len(r["tokens"]) == 3


def _fresh_pool_ok(h):
    try:
        return h.kv_stats.remote().result(
            timeout=15)["kv_blocks_in_use"] == 0
    except Exception:
        return False


# ---------------------------------------------------------------------------
# TTFT SLO auto-rule + loadgen TTFT reporting
# ---------------------------------------------------------------------------


def test_ttft_slo_rule_autoregistered(serve_session):
    from ray_tpu import slo

    dep = serve.deployment(
        LLMServer, name="llmslo", max_concurrent_queries=4,
        slo_ttft_p99_s=0.5,
    ).bind(None, **_ENGINE_KW)
    serve.run(dep)
    rules = {r["name"]: r for r in slo.list()}
    assert "serve-llmslo-ttft-p99" in rules, sorted(rules)
    rule = rules["serve-llmslo-ttft-p99"]
    assert "ray_tpu_llm_ttft_seconds" in rule["expr"]
    assert rule["target"] == 0.5
    # the TTFT rule is opt-in: only the deployment that set slo_ttft_p99_s
    # has one (the default p99/availability rules exist regardless)
    assert [n for n in rules if n.endswith("-ttft-p99")] == [
        "serve-llmslo-ttft-p99"
    ]


def test_loadgen_reports_ttft_percentiles(serve_session):
    from ray_tpu.serve import loadgen

    # sized so that the toy device's time is what is compared: 16 steps of 5
    # ms batched against 8 x 16 of them one by one. At 4 tokens of 2 ms (8 ms
    # against 64) both sides measured how long eight threads took to get their
    # requests through the handle on a busy CPU, and the ratio fell under 1.
    res = loadgen.measure_continuous_batching(
        concurrency=8, tokens=16, step_ms=5.0)
    assert res["speedup_x"] > 1.0
    for key in ("ttft_p50_s", "ttft_p99_s", "latency_p50_s", "latency_p99_s"):
        assert res[key] == res[key] and res[key] > 0, (key, res)
    # TTFT is streaming-aware: first token lands well before completion
    assert res["ttft_p50_s"] <= res["latency_p99_s"]


# ---------------------------------------------------------------------------
# a state made of cached rows: the window store (models/mimo_v2_flash.py)
# ---------------------------------------------------------------------------

_WINDOWED = dict(
    num_blocks=32, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2),
    prefill_token_buckets=(16,), cache_buckets=(32, 64), prefix_caching=False)


@pytest.mark.parametrize(
    "cfg,gathered", [
        (cohere2_moe.cohere2_moe_nano(), 4), (mimo_v2_flash.mimo_v2_flash_nano(), 0),
        (CFG, 0)],
    ids=["rows-under-a-mask", "rows-as-state", "no-window"])
def test_window_slots_are_counted_from_what_a_call_gathers(cfg, gathered):
    """``window_slots`` counts the gathered slots of layers that see a window only: all
    four sliding layers of a model that caches every token in every layer, none of a
    model that names its sliding layers and keeps their windows as state (nothing is
    gathered for them), none of a model without windows."""
    eng = LLMEngine(cfg, **_WINDOWED)
    seq = batching._Sequence({"prompt": _prompt(3, 40), "max_new_tokens": 4})
    while not seq.done:
        eng.step([seq])
    assert seq._error is None, seq._error
    stats = eng.stats()
    assert eng._window_layers == gathered
    gathered_slots = stats["cache_slots"]                   # lanes x cache bucket, every call
    assert stats["window_slots"] == gathered * gathered_slots
    assert (stats["window_slots_outside"] > 0) == bool(gathered)
    assert eng.pool.layers == getattr(cfg, "cache_layers", cfg.num_layers)


def test_a_window_slot_is_freed_exactly_once_with_its_lease():
    """The window store's slots ride the lease that the blocks ride: one a sequence at
    admission, back once on finish, on a second release nothing more; a cancelled
    sequence's goes back too, and nothing of a sliding layer is in the block arenas."""
    cfg = mimo_v2_flash.mimo_v2_flash_nano()
    eng = LLMEngine(cfg, **{**_WINDOWED, "state_slots": 4})
    pool = eng.pool
    assert [a.shape[0] for a in pool.arenas] == [cfg.cache_layers] * 2 == [3, 3]
    assert [s.shape[:2] for s in pool.states] == [(4, 4), (4, 4)]
    lease = KVLease(pool)
    slot = lease.add_slot()
    lease.add(pool.allocate(2))
    assert slot != 0 and pool.slots_in_use() == 1 and pool.in_use() == 2
    lease.release()
    lease.release()
    assert pool.slots_in_use() == 0 and pool.in_use() == 0
    assert sorted(pool._free_slots) == [1, 2, 3]            # once: no slot twice in the list
    cancel = threading.Event()
    seqs = [
        batching._Sequence({"prompt": _prompt(5, 30), "max_new_tokens": 6}),
        batching._Sequence({"prompt": _prompt(6, 20), "max_new_tokens": 40, "_cancel": cancel})]
    steps = 0
    while not all(s.done for s in seqs):
        if steps == 6:
            cancel.set()
        eng.step([s for s in seqs if not s.done])
        steps += 1
    assert seqs[0]._error is None and seqs[1]._error is not None
    assert pool.slots_in_use() == 0 and pool.in_use() == 0
    assert sorted(pool._free_slots) == [1, 2, 3]
    # a fourth sequence finds no slot and is shed before anything is written
    waiting = [batching._Sequence({"prompt": _prompt(i, 12), "max_new_tokens": 30}) for i in range(4)]
    eng.step(waiting)
    shed = [s for s in waiting if s.done]
    assert len(shed) == 1 and "state slot" in str(shed[0]._error)


# ---------------------------------------------------------------------------
# a state that a delta rule writes, and sixteen lanes (models/qwen3_next.py)
# ---------------------------------------------------------------------------

_DELTA = dict(
    num_blocks=160, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 16),
    prefill_token_buckets=(16,), cache_buckets=(64,), prefix_caching=False)


def test_a_delta_state_slot_is_freed_exactly_once_with_its_lease():
    """The delta layers' state and convolution tail ride the lease that the blocks
    ride: one slot a sequence at admission, back once on finish, on a second release
    nothing more; a cancelled sequence's goes back too, and a delta layer has nothing in
    the block arenas."""
    cfg = qwen3_next.qwen3_next_nano()
    eng = LLMEngine(cfg, **{**_DELTA, "lane_buckets": (1, 2), "state_slots": 4})
    pool = eng.pool
    assert [a.shape[0] for a in pool.arenas] == [cfg.cache_layers] * 2 == [2, 2]
    assert [s.shape[:2] for s in pool.states] == [(6, 4), (6, 4)]
    assert pool.state_bytes == 6 * (8 * 16 * 16 + 3 * 256) * 4
    lease = KVLease(pool)
    slot = lease.add_slot()
    lease.add(pool.allocate(2))
    assert slot != 0 and pool.slots_in_use() == 1 and pool.in_use() == 2
    lease.release()
    lease.release()
    assert pool.slots_in_use() == 0 and pool.in_use() == 0
    assert sorted(pool._free_slots) == [1, 2, 3]            # once: no slot twice in the list
    cancel = threading.Event()
    seqs = [
        batching._Sequence({"prompt": _prompt(5, 30), "max_new_tokens": 6}),
        batching._Sequence({"prompt": _prompt(6, 20), "max_new_tokens": 40, "_cancel": cancel})]
    steps = 0
    while not all(s.done for s in seqs):
        if steps == 6:
            cancel.set()
        eng.step([s for s in seqs if not s.done])
        steps += 1
    assert seqs[0]._error is None and seqs[1]._error is not None
    assert pool.slots_in_use() == 0 and pool.in_use() == 0
    assert sorted(pool._free_slots) == [1, 2, 3]


def test_sixteen_lanes_decode_in_one_call_and_each_gets_what_it_gets_alone():
    """Sixteen sequences of unlike lengths through the engine's own default of lane
    buckets up to 16: once all have their prompt in, a decode call carries all sixteen
    (``extend_decode_16x1x64``), a gather moves rows for the two full layers alone, and
    every sequence's tokens are those it gets with the engine to itself."""
    cfg = qwen3_next.qwen3_next_nano()
    eng = LLMEngine(cfg, **_DELTA)
    asks = [
        {"prompt": _prompt(40 + i, 6 + 2 * i), "max_new_tokens": 20, "return_logits": True}
        for i in range(16)]
    alone = []
    for ask in asks[::5]:
        seq = batching._Sequence(dict(ask))
        while not seq.done:
            eng.step([seq])
        alone.append(seq._result)
    before = eng.stats()
    seqs = [batching._Sequence(dict(ask)) for ask in asks]
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
    assert all(s._error is None for s in seqs)
    after = eng.stats()
    name = _extend_name(16, 1, 64)
    assert name == "extend_decode_16x1x64" and name not in before["programs"]
    assert after["programs"][name]["n"] > 0
    decode = {
        k: after["calls"]["decode"][k] - before["calls"]["decode"][k]
        for k in ("n", "lanes_used", "lane_slots")}
    assert decode["lanes_used"] == 16 * 19 and decode["lanes_used"] / decode["n"] > 6
    assert eng.pool.layers == cfg.cache_layers == 2         # what a gather moves rows for
    for got, want in zip(seqs[::5], alone):
        assert got._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(got._result["logits"], want["logits"], rtol=2e-4, atol=2e-5)
    assert eng.pool.slots_in_use() == 0 and eng.pool.in_use() == 0


# ---------------------------------------------------------------------------
# a decode call that attends through the block table (no gather)
# ---------------------------------------------------------------------------

_READ_PAGES = {
    "mimo-v2-flash": mimo_v2_flash.mimo_v2_flash_nano,
    "qwen3-next": qwen3_next.qwen3_next_nano,
    "granite-4h": granitemoehybrid.granite_hybrid_nano,
    "granite-4h-experts": lambda: granitemoehybrid.granite_hybrid_nano(router_experts=8),
    # no state: one arena of latent rows, a row the key and in its first features the value
    "kimi-k2": kimi_k2.kimi_k2_nano,
    # two such rows a layer: an arena of more slabs than the model has layers
    "longcat-flash": longcat_flash.longcat_flash_nano,
}
_PAGED = dict(
    num_blocks=64, block_size=8, prefill_chunk=16, prefill_lanes=1, lane_buckets=(1, 2, 4),
    prefill_token_buckets=(16,), cache_buckets=(32, 64), state_slots=12)


@pytest.mark.parametrize("name", list(_READ_PAGES))
def test_a_decode_call_through_the_block_table_decodes_what_the_gather_decodes(name, monkeypatch):
    """A model whose ``extend`` offers ``table=`` against itself through the gather (the
    parent's path: the same engine told that nothing reads pages, which hands every
    call padded caches): three sequences of unlike lengths decode together across a
    change of cache bucket (32 -> 64), one of them returning its logits (the host makes
    its token: its call lands before the next is launched) while the others run a call
    ahead, then a fourth hits the third's cached prefix. Token for token and, for the
    logits, bit for bit the same; every decode call of the one ran no gather
    (``paged`` = ``n``) and only a chunk did, none of the other's."""
    cfg = _READ_PAGES[name]()
    through_table = LLMEngine(cfg, **_PAGED)
    monkeypatch.setattr(llm, "reads_pages", lambda extend: False)
    through_gather = LLMEngine(cfg, **_PAGED)
    monkeypatch.undo()
    assert llm.reads_pages(through_table._extend)
    assert through_table._reads_pages and not through_gather._reads_pages
    # the temporaries ``_fits`` counts for any call are those of the largest shape that is
    # handed padded caches: a chunk's, where the decode calls hold none
    assert through_table.warm()["compiled"]["shape"] == [1, 16, 64]
    assert through_gather.warm()["compiled"]["shape"] == [4, 1, 64]
    asks = [
        {"prompt": [int(t) for t in np.random.RandomState(40 + i).randint(0, cfg.vocab_size, 9 + 7 * i)],
         "max_new_tokens": 24, "return_logits": i == 1}
        for i in range(3)]
    late = {"prompt": asks[2]["prompt"][:16] + [3, 1, 4, 1, 5], "max_new_tokens": 24}

    def decoded(eng):
        gathers, gather = [], eng.pool.gather
        monkeypatch.setattr(eng.pool, "gather", lambda *a: gathers.append(1) or gather(*a))
        seqs = [batching._Sequence(dict(ask)) for ask in asks]
        _drive(eng, seqs)
        seqs.append(batching._Sequence(dict(late)))
        _drive(eng, seqs[-1:])
        stats = eng.stats()
        # every sequence's slot is back; the prefix cache keeps its snapshots' (a model
        # without a state has neither)
        assert stats.get("state_slots_in_use") == stats.get("state_snapshots")
        return [s._result for s in seqs], stats, len(gathers)

    got, stats, gathers = decoded(through_table)
    want, plain, plain_gathers = decoded(through_gather)
    assert [r["tokens"] for r in got] == [r["tokens"] for r in want]
    assert np.array_equal(got[1]["logits"], want[1]["logits"])
    for s in (stats, plain):
        assert s["prefix_hits"] >= 2 and s["calls_ahead"] > 0       # the hit's two blocks
        assert {"extend_decode_4x1x32", "extend_decode_4x1x64"} <= {
            name for name, program in s["programs"].items() if program["n"]}
    calls = stats["calls"]
    assert calls["decode"]["paged"] == calls["decode"]["n"] > 40 and calls["prefill"]["paged"] == 0
    assert gathers == calls["prefill"]["n"] == stats["phase_n"]["kv_gather"]
    assert plain["calls"]["decode"]["paged"] == 0
    assert plain_gathers == plain["calls"]["decode"]["n"] + plain["calls"]["prefill"]["n"]
    # what the calls carried is counted alike, gathered or read in place
    for key in ("n", "lanes_used", "lane_slots", "cache_tokens", "cache_slots"):
        assert calls["decode"][key] == plain["calls"]["decode"][key], key
    assert {k: stats[k] for k in cfg.counters} == {k: plain[k] for k in cfg.counters}


def test_sixteen_lanes_decode_through_the_table_over_latent_pages():
    """Sixteen sequences of unlike lengths over one arena of latent rows (LongCat-Flash's:
    two slabs a layer): once all have their prompt in, a decode call carries all sixteen
    through ``table=`` (``extend_decode_16x1x64``, no gather: sixteen lanes' tables of up to
    eight pages each), and every sequence gets the tokens it gets with the engine to
    itself."""
    cfg = longcat_flash.longcat_flash_nano()
    eng = LLMEngine(cfg, **{**_PAGED, "num_blocks": 160, "lane_buckets": (1, 2, 4, 8, 16)})
    assert eng._reads_pages and eng.pool.layers == cfg.cache_layers == 6
    asks = [
        {"prompt": _prompt(40 + i, 6 + 2 * i), "max_new_tokens": 20, "return_logits": True}
        for i in range(16)]
    alone = []
    for ask in asks[::5]:
        seq = batching._Sequence(dict(ask))
        _drive(eng, [seq])
        alone.append(seq._result)
    before = eng.stats()
    seqs = [batching._Sequence(dict(ask)) for ask in asks]
    _drive(eng, seqs)
    after = eng.stats()
    name = _extend_name(16, 1, 64)
    assert name == "extend_decode_16x1x64" and name not in before["programs"]
    assert after["programs"][name]["n"] > 0
    decode = {
        k: after["calls"]["decode"][k] - before["calls"]["decode"][k]
        for k in ("n", "paged", "lanes_used", "lane_slots")}
    assert decode["paged"] == decode["n"] and decode["lanes_used"] == 16 * 19
    assert decode["lanes_used"] / decode["n"] > 6
    for got, want in zip(seqs[::5], alone):
        assert got._result["tokens"] == want["tokens"]
        np.testing.assert_allclose(got._result["logits"], want["logits"], rtol=2e-4, atol=2e-5)
    assert eng.pool.in_use() == eng.stats()["prefix_cached_blocks"]


def test_a_deployment_takes_its_executing_slots_from_what_its_callable_runs_at_once():
    """``serve.deployment`` without ``max_concurrent_queries``: the callable's own
    answer for the arguments it is bound with where that is past the default's 8
    (``LLMServer``: its engine's largest lane bucket, the engine's own default buckets
    where it is given none, never past what ``generate`` batches), 8 for a callable that
    has none, and the deployment's own number where it names one."""
    assert LLMServer.concurrent_queries() == max(LANE_BUCKETS) == MAX_LANES == 16
    assert LLMServer.concurrent_queries(None, lane_buckets=(1, 2, 4)) == 4
    assert LLMServer.concurrent_queries("a", "b", {}, seed=3, lane_buckets=[1, 2, 4, 8, 16]) == 16
    assert LLMServer.concurrent_queries(lane_buckets=(1, 32)) == 16

    class Wide(LLMServer):
        pass

    app = serve.deployment(Wide, name="wide").bind(None, lane_buckets=(1, 16), num_blocks=8)
    assert serve._executing_slots(app.deployment, app.init_args, app.init_kwargs) == 16
    narrow = serve.deployment(Wide, name="narrow").bind(None, lane_buckets=(1, 4))
    assert serve._executing_slots(narrow.deployment, narrow.init_args, narrow.init_kwargs) == 8
    named = serve.deployment(Wide, name="named", max_concurrent_queries=3).bind(
        None, lane_buckets=(1, 16))
    assert serve._executing_slots(named.deployment, named.init_args, named.init_kwargs) == 3
    plain = serve.deployment(lambda x: x, name="plain").bind()
    assert serve._executing_slots(plain.deployment, plain.init_args, plain.init_kwargs) == 8


# ---------------------------------------------------------------------------
# latent rows under a learned selection: two arenas, two forms of attend
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def glm_engine():
    """One engine over the GLM-5 nano (``topk`` 16 rows a query, a latent row of 128 and an
    indexer key of 16 a token and layer) shared by the tests below; the projections scaled
    up so that the logits are of order 1 and the selection matters."""
    import jax

    cfg = glm_moe_dsa.glm_moe_dsa_nano()
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, cfg.init_params(7))
    return cfg, params, LLMEngine(
        cfg, params, num_blocks=64, block_size=16, prefill_chunk=32, prefill_lanes=1,
        lane_buckets=(1, 2, 4), prefill_token_buckets=(32,), cache_buckets=(64, 128))


def _glm_file(cfg):
    """The keys the benchmark's plain reference reads, for the nano's sizes."""
    import json
    import os

    with open(os.path.join(
            os.path.dirname(__file__), "benchmark", "tiny", "glm_moe_dsa.json")) as f:
        model = json.load(f)["model"]
    assert (model["index_topk"], model["num_hidden_layers"]) == (cfg.topk, cfg.num_layers)
    return model


def test_lanes_under_and_past_index_topk_decode_what_the_reference_computes(glm_engine):
    """Two sequences at once, one whose whole context stays under ``index_topk`` (10 + 5
    tokens: causal attention, the selection decides nothing) and one far past it (75 + 5:
    three chunks under the selection mask, then decode lanes over 16 gathered rows, across
    the 64-slot bucket into the 128-slot one), in decode calls of two lanes of unlike
    lengths: each one's logits are the plain reference's full forward over its own tokens
    (float32 on both sides: 2e-4 of logits of order 1, the roundings of sums in another
    order), and every query attended ``min(topk, what it sees)`` rows."""
    from benchmark.reference import glm_moe_dsa_reference as ref

    cfg, params, eng = glm_engine
    asks = [
        {"prompt": _prompt(90 + i, n), "max_new_tokens": 5, "return_logits": True}
        for i, n in enumerate((10, 75))]
    before = eng.stats()
    seqs = [batching._Sequence(dict(ask)) for ask in asks]
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
    assert all(s._error is None for s in seqs)
    after = eng.stats()
    decode = {k: after["calls"]["decode"][k] - before["calls"]["decode"][k] for k in ("n", "lanes_used")}
    assert decode["lanes_used"] > decode["n"]                   # some calls carried both lanes
    model = _glm_file(cfg)
    for ask, seq in zip(asks, seqs):
        out = seq._result
        fed = ask["prompt"] + out["tokens"][:-1]
        want = np.asarray(ref.program_logits(params, fed, model, 5))
        assert float(np.abs(want).max()) > 0.3
        np.testing.assert_allclose(out["logits"], want, atol=2e-4, rtol=2e-4)
        assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    d = {k: after[k] - before[k] for k in after if k.startswith(("sparse_", "mla_"))}
    seen = [t + 1 for n in (10, 75) for t in range(n + 4)]
    assert d["sparse_keys_scored"] == cfg.num_layers * sum(seen)
    assert d["sparse_keys_attended"] == cfg.num_layers * sum(min(s, cfg.topk) for s in seen)
    assert d["mla_pairs_absorbed"] == d["sparse_keys_attended"] and d["mla_pairs_expanded"] == 0
    assert 0 < d["sparse_slots_read"] < d["sparse_slots_gathered"]
    assert eng.pool.in_use() == 0 or eng.prefix is not None


def test_a_prefix_hit_restores_both_arenas_and_decodes_the_same_bits(glm_engine):
    """The same 75-token prompt twice: the second time four blocks of 16 come from the
    prefix cache, the latent rows and the indexer's keys with them (the repeat's first
    chunk scores its queries against keys it never made), and tokens and logits are the
    first run's to the bit."""
    cfg, _, eng = glm_engine
    assert [a.shape for a in eng.pool.arenas] == [
        (cfg.num_layers, 64, 16, 1, cfg.row_dim), (cfg.num_layers, 64, 16, 1, cfg.index_dim)]
    ask = {"prompt": _prompt(123, 75), "max_new_tokens": 6, "return_logits": True}
    results = []
    for _ in range(2):
        seq = batching._Sequence(dict(ask))
        while not seq.done:
            eng.step([seq])
        assert seq._error is None
        results.append(seq._result)
    first, again = results
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 64)
    assert again["tokens"] == first["tokens"]
    assert np.array_equal(again["logits"], first["logits"])
    # the blocks that were reused hold rows in both arenas, and no block is all zeros there
    blocks = eng.prefix.match(chain_hashes(ask["prompt"], 16))
    assert len(blocks) == 4
    held = [np.asarray(a) for a in eng.pool.read_block(blocks[0])]
    assert [h.shape[-1] for h in held] == [cfg.row_dim, cfg.index_dim]
    assert all(np.abs(h).max() > 0 for h in held)
