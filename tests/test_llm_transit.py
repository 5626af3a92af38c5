"""What crosses the host-device link in a serving call: one int32 buffer up,
one int32 array down (the ids sampled on the device, an expert layer's
counters behind them), and the picked rows only for a lane that needs them on
the host. The ids are the ones the host would have picked from those rows."""

import numpy as np
import pytest

from ray_tpu.models import cohere2_moe, gpt, keye_vl2
from ray_tpu.serve import batching, llm

ENGINE = dict(
    num_blocks=64, block_size=16, prefill_chunk=32, prefill_lanes=2,
    lane_buckets=(1, 2, 4), prefill_token_buckets=(16, 32),
    cache_buckets=(64, 128), prefix_caching=False,
)
CONFIGS = {
    "gpt": gpt.gpt_nano, "cohere2_moe": cohere2_moe.cohere2_moe_nano,
    "keye_vl2": keye_vl2.keye_vl2_nano,
}
LENGTHS, NEW = (20, 40, 9, 33), 5


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def engine(request):
    cfg = CONFIGS[request.param]()
    adapter = llm.random_lora(cfg, rank=4, seed=3, scale=4.0)
    return llm.LLMEngine(
        cfg, adapter_loader=lambda mid: (adapter["A"], adapter["B"], adapter["scale"]),
        **ENGINE)


def _requests(cfg, seed, asks=({},) * len(LENGTHS)):
    rng = np.random.RandomState(seed)
    return [
        batching._Sequence({
            "prompt": [int(t) for t in rng.randint(0, cfg.vocab_size, n)],
            "max_new_tokens": NEW, **ask,
        })
        for n, ask in zip(LENGTHS, asks)
    ]


def _drive(eng, seqs):
    steps = 0
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 200
    for s in seqs:
        assert s._error is None, s._error
    return [s._result for s in seqs]


def _calls(eng, monkeypatch):
    """Every device call from here on, kept at its launch and filled in at its
    landing (a call later): its lanes' states, what it brought home and what
    each half added to the engine's counters."""
    kept, launch, land = [], eng._launch, eng._land
    up, down = ("h2d_transfers", "h2d_bytes"), ("d2h_transfers", "ids_only_calls", "d2h_bytes")

    def added(keys, before):
        return {k: getattr(eng, k) - v for k, v in zip(keys, before)}

    def kept_launch(lanes, chunks, tc, emits):
        before = [getattr(eng, k) for k in up]
        flight, ahead = eng._flight, eng.calls_ahead
        states = [st for _, st in lanes]
        # a lane whose rows the host needs, with those rows still in flight
        waits = any(st.on_host and st.call is flight for st in states if flight is not None)
        call = launch(lanes, chunks, tc, emits)        # lands the call before it
        kept.append({
            "call": call, "states": states, "emits": list(emits), "waits": waits,
            "ahead": eng.calls_ahead - ahead, **added(up, before),
        })
        return call

    def kept_land():
        before = [getattr(eng, k) for k in down]
        call = land()
        # the newest call is kept only when its launch returns: it lands later
        next(c for c in kept if c["call"] is call).update(
            sampled=call.sampled, **added(down, before))
        return call

    monkeypatch.setattr(eng, "_launch", kept_launch)
    monkeypatch.setattr(eng, "_land", kept_land)
    return kept


@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_ids_sampled_on_the_device_are_the_argmax_of_the_rows_a_twin_brings_home(engine, seed):
    plain = _drive(engine, _requests(engine.cfg, seed))
    twins = _drive(
        engine, _requests(engine.cfg, seed, ({"return_logits": True},) * len(LENGTHS)))
    for got, twin in zip(plain, twins):
        assert "logits" not in got and twin["logits"].shape == (NEW, engine.cfg.vocab_size)
        assert got["tokens"] == twin["tokens"] == [int(np.argmax(r)) for r in twin["logits"]]


@pytest.mark.parametrize("tc,counted", [(1, False), (4, True)], ids=["decode", "chunk+counters"])
def test_a_tie_goes_to_the_first_index_as_on_the_host(engine, tc, counted):
    """Rows with their maximum at two, at three and at every index, through
    the page-back program itself (it writes no token: the count is 0), which
    samples the one row a lane that ``extend`` made."""
    import jax.numpy as jnp

    cfg, b = engine.cfg, 4
    rng = np.random.RandomState(5)
    rows = rng.standard_normal((b, cfg.vocab_size)).astype(np.float32)
    top = rows.max() + 1.0
    rows[0, [7, 3]] = top                               # written 7 first: 3 wins
    rows[1, [cfg.vocab_size - 1, 100, 200]] = top
    rows[2] = 0.0                                       # all equal: 0 wins
    operands = np.zeros((b, engine._operand_width), np.int32)
    news = [
        jnp.zeros((cfg.num_layers, b, tc) + tuple(each), engine.pool.dtype)
        for each in cfg.cache_arrays]
    counters = (jnp.asarray([5, 6, 7, 8], jnp.int32),) if counted else ()
    home = np.asarray(engine.pool.page_back(
        news, jnp.asarray(operands), jnp.asarray(rows), counters, b + 3))
    assert home.dtype == np.int32
    assert home[:b].tolist() == np.argmax(rows, axis=-1).tolist()
    assert home[:3].tolist() == [3, 100, 0]
    # the next call reads the ids here too: one width, whatever the lanes
    assert home[b:].tolist() == [0] * 3 + ([5, 6, 7, 8] if counted else [])


def test_a_mixed_call_gives_each_lane_what_it_gave_before(engine, monkeypatch):
    """An adapter lane, a lane that returns its logits and two plain lanes in
    the same calls: each lane's token is what the host made of the rows, as
    it was when every row came home."""
    cfg = engine.cfg
    asks = ({"model_id": "lora:a", "return_logits": True}, {"return_logits": True}, {}, {})
    calls = _calls(engine, monkeypatch)
    seqs = _requests(cfg, 23, asks)
    results = _drive(engine, seqs)
    a, bmat, scale = seqs[0].state.adapter
    want = {id(s.state): ([], []) for s in seqs}        # tokens, logits rows
    mixed = 0
    for call in calls:
        adapted = any(st.adapter is not None for st in call["states"])
        asked = any(st.return_logits for st in call["states"])
        mixed += adapted and len(call["states"]) > 2
        # the host makes these lanes' tokens: nothing is fed to them ahead
        assert not (call["waits"] and call["ahead"])
        for st, (tok, row, hidden), emits in zip(call["states"], call["sampled"], call["emits"]):
            assert (row is not None) == (adapted or asked)
            assert (hidden is not None) == adapted
            if not emits:
                continue
            if row is not None:
                assert tok == np.argmax(row)            # the device's id is the host's
            if st.adapter is not None:
                delta = scale * (hidden @ a) @ bmat
                assert np.abs(delta).max() > 0
                row = row + delta
                tok = int(np.argmax(row))
            want[id(st)][0].append(tok)
            want[id(st)][1].append(row)
    assert mixed                                        # the lanes did share calls
    for s, got, ask in zip(seqs, results, asks):
        tokens, rows = want[id(s.state)]
        assert got["tokens"] == tokens and len(tokens) == NEW
        assert ("logits" in got) == bool(ask.get("return_logits"))
        if "logits" in got:
            assert np.array_equal(got["logits"], np.stack(rows))
    # the adapter moved the lane: without it the same prompt decodes otherwise
    base = _drive(engine, _requests(cfg, 23)[:1])[0]
    assert base["tokens"] != results[0]["tokens"]
    # and the plain lanes decode as they do with no such lane beside them
    alone = _drive(engine, _requests(cfg, 23))
    assert [r["tokens"] for r in alone[2:]] == [r["tokens"] for r in results[2:]]


def test_a_call_crosses_the_link_once_each_way_unless_a_lane_needs_its_rows(engine, monkeypatch):
    cfg, b_of = engine.cfg, lambda call: batching.bucket_pad_size(
        len(call["states"]), engine.lane_buckets)
    # what ``extend`` counts rides home behind the ids: an expert layer's four
    # counters, an indexer's four more, none for a model that counts nothing
    counters = 4 * len(getattr(cfg, "counters", ()))
    assert len(getattr(cfg, "counters", ())) == {
        gpt.GPTConfig: 0, cohere2_moe.Cohere2MoeConfig: 4, keye_vl2.KeyeVL2Config: 8}[type(cfg)]
    calls = _calls(engine, monkeypatch)
    before = engine.stats()
    _drive(engine, _requests(cfg, 31))
    after = engine.stats()
    assert calls and all(
        (c["h2d_transfers"], c["d2h_transfers"], c["ids_only_calls"]) == (1, 1, 1) for c in calls)
    for c in calls:
        assert c["h2d_bytes"] == 4 * b_of(c) * engine._operand_width
        assert c["d2h_bytes"] == 4 * engine.lane_buckets[-1] + counters    # one width
    dispatched = after["phase_n"]["dispatch"] - before["phase_n"]["dispatch"]
    assert len(calls) == dispatched
    for k in ("h2d_transfers", "d2h_transfers", "ids_only_calls"):
        assert after[k] - before[k] == dispatched, k

    del calls[:]
    asks = ({"return_logits": True}, {}, {}, {"model_id": "lora:a"})
    seqs = _requests(cfg, 31, asks)
    _drive(engine, seqs)
    kinds = set()
    for c in calls:
        adapted = any(st.adapter is not None for st in c["states"])
        asked = any(st.return_logits for st in c["states"])
        rows = 2 if adapted else 1 if asked else 0      # logits and hidden; logits; neither
        kinds.add(rows)
        assert (c["h2d_transfers"], c["d2h_transfers"]) == (1, 1 + rows)
        assert c["ids_only_calls"] == (rows == 0)
        assert c["d2h_bytes"] == 4 * engine.lane_buckets[-1] + counters + 4 * b_of(c) * (
            (rows > 0) * cfg.vocab_size + (rows > 1) * cfg.embed_dim)
    assert kinds == {0, 1, 2}
