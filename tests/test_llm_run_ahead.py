"""The engine runs a call behind the host: call n+1 is launched before call n's
ids are home, and reads a lane's token from them on the device. What is held
here: the tokens are those of the same requests served one at a time, whatever
ends a lane while a call is in flight (its ``eos_token``, a cancel, a shed);
lanes whose token the host makes are never fed ahead and get the ids and rows
they get alone (to a program's rounding); a forward that raises, at the launch or at the landing, leaves
no lease and no call behind; and the two counters read what a schedule implies."""

import threading

import numpy as np
import pytest

from ray_tpu.models import cohere2_moe, gpt
from ray_tpu.serve import batching, llm
from ray_tpu.serve.handle import BackPressureError

ENGINE = dict(
    num_blocks=14, block_size=16, prefill_chunk=16, prefill_lanes=2,
    lane_buckets=(1, 2), prefill_token_buckets=(8, 16), cache_buckets=(64, 128),
    prefix_caching=False,
)
CONFIGS = {"gpt": gpt.gpt_nano, "cohere2_moe": cohere2_moe.cohere2_moe_nano}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def engine(request):
    cfg = CONFIGS[request.param]()
    adapter = llm.random_lora(cfg, rank=4, seed=3, scale=4.0)
    return llm.LLMEngine(
        cfg, adapter_loader=lambda mid: (adapter["A"], adapter["B"], adapter["scale"]),
        **ENGINE)


def _ask(cfg, seed, n, new, **ask):
    rng = np.random.RandomState(seed)
    return batching._Sequence({
        "prompt": [int(t) for t in rng.randint(0, cfg.vocab_size, n)],
        "max_new_tokens": new, **ask})


def _drive(eng, seqs, each_step=lambda step: None):
    """Step until every sequence is done, as the batcher does, and no more: a
    call launched past an ``eos_token`` has no sequence left to ask for the
    step that would land it, so the step that launched it has."""
    steps = 0
    while not all(s.done for s in seqs):
        each_step(steps)
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 300
    assert eng._flight is None
    return steps


def _alone(eng, make):
    """The result of each request of ``make()`` served with no other beside it."""
    out = []
    for s in make():
        _drive(eng, [s])
        assert s._error is None, s._error
        out.append(s._result)
    return out


def _counted(eng, run):
    before = eng.stats()
    got = run()
    after = eng.stats()
    d = {k: after[k] - before[k] for k in (
        "steps", "calls_ahead", "tokens_fed_on_device", "decode_tokens", "prefill_tokens")}
    d["calls"] = after["phase_n"]["dispatch"] - before["phase_n"]["dispatch"]
    d["landed"] = after["phase_n"]["fetch"] - before["phase_n"]["fetch"]
    return got, d


def test_a_mixed_schedule_emits_what_each_request_gets_alone(engine):
    """Prompts longer than a chunk, more decoding sequences than the widest
    lane bucket, a sequence that ends by its ``eos_token`` while the call
    launched past it is in flight, one cancelled in flight and one shed for
    blocks: every other request gets the tokens it gets when served alone."""
    cfg = engine.cfg
    plain = lambda: [                                   # noqa: E731
        _ask(cfg, 41, 40, 6), _ask(cfg, 42, 20, 8), _ask(cfg, 46, 9, 7), _ask(cfg, 44, 33, 12)]
    want = [r["tokens"] for r in _alone(engine, plain)]
    # the third request ends at the first token it had not given before, with
    # at least one more to go by count: that one is launched before this lands
    ends = next(i for i in range(1, 6) if want[2][i] not in want[2][:i])
    eos = want[2][ends]
    cancel = threading.Event()

    def mixed():
        seqs = plain()
        seqs[2].item["eos_token"] = eos
        seqs[3].item[llm._CANCEL_KEY] = cancel
        # eight blocks, with nine of fourteen held by the four before it
        return seqs + [_ask(cfg, 45, 120, 4)]

    seqs = mixed()
    seen = {}

    def each_step(step):
        if step == 6:
            seen["flight"], seen["out"] = engine._flight, list(seqs[3].state.out)
            cancel.set()

    (_, d) = _counted(engine, lambda: _drive(engine, seqs, each_step))
    assert [s._result["tokens"] for s in seqs[:2]] == want[:2]
    assert seqs[2]._result["tokens"] == want[2][:ends + 1]
    # cancelled with its next token launched: what had landed is what it got
    assert type(seqs[3]._error).__name__ == "TaskCancelledError"
    assert seqs[3].state in [st for _, st in seen["flight"].lanes]
    assert 0 < len(seen["out"]) < 12 and seqs[3].state.out == seen["out"] == want[3][:len(seen["out"])]
    assert isinstance(seqs[4]._error, BackPressureError)
    assert engine.pool.in_use() == 0
    # every call was landed, most behind their successor, and the id launched
    # past the end of a lane was dropped: no more tokens than asked for
    assert d["landed"] == d["calls"] and d["calls"] / 2 < d["calls_ahead"] < d["calls"]
    assert 0 < d["tokens_fed_on_device"] < d["decode_tokens"]
    assert d["decode_tokens"] <= (6 - 1) + (8 - 1) + (ends + 1) + len(seen["out"])
    # ... and the engine serves the next request as if nothing had happened
    (again,) = _alone(engine, lambda: plain()[:1])
    assert again["tokens"] == want[0]


def test_a_lane_the_host_samples_is_not_fed_ahead_and_gets_what_it_gets_alone(engine):
    """An adapter lane and a lane that returns its logits: alone, none of
    their calls is launched while another is in flight (the old order); among
    plain lanes that do run ahead, they get the same ids and the same rows."""
    cfg = engine.cfg
    asks = ({"model_id": "lora:a", "return_logits": True}, {"return_logits": True}, {}, {})
    make = lambda: [                                    # noqa: E731
        _ask(cfg, 51 + i, n, 5, **ask) for i, (n, ask) in enumerate(zip((24, 40, 9, 20), asks))]
    alone, d = _counted(engine, lambda: _alone(engine, lambda: make()[:2]))
    assert d["calls_ahead"] == d["tokens_fed_on_device"] == 0 and d["landed"] == d["calls"]
    alone += _alone(engine, lambda: make()[2:])
    seqs, again = make(), make()
    _, d = _counted(engine, lambda: _drive(engine, seqs))
    assert 0 < d["calls_ahead"] < d["calls"] and d["tokens_fed_on_device"] > 0
    _drive(engine, again)
    for s, twin, want, ask in zip(seqs, again, alone, asks):
        assert s._error is None and s._result["tokens"] == want["tokens"]
        assert ("logits" in s._result) == bool(ask)
        if ask:
            # the same rows as alone, to what another lane bucket's program
            # rounds otherwise; the same bits whenever the schedule is the same
            assert np.allclose(s._result["logits"], want["logits"], rtol=0, atol=1e-5)
            assert np.array_equal(s._result["logits"], twin._result["logits"])
    assert alone[0]["tokens"] != _alone(engine, lambda: [_ask(cfg, 51, 24, 5)])[0]["tokens"]


class _Numpy:
    """The engine's numpy where a forward raised on the device: the next call
    takes what that call left there, and the copy to the host raises."""

    def __init__(self, lost):
        self._lost = lost

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        if any(a is x for x in self._lost):
            raise RuntimeError("forward crashed on the device")
        return np.asarray(a, *args, **kwargs)


@pytest.mark.parametrize("where", ["launch", "landing"])
def test_a_forward_that_raises_releases_every_lease_once_and_leaves_nothing_in_flight(
        engine, monkeypatch, where):
    cfg = engine.cfg
    make = lambda: [_ask(cfg, 61, 40, 6), _ask(cfg, 62, 20, 8), _ask(cfg, 63, 9, 5)]   # noqa: E731
    want = [r["tokens"] for r in _alone(engine, make)]
    real, calls, lost = (engine._extend_call, engine.pool.page_back), [], []

    def crashes(*args, **kwargs):
        calls.append(None)
        if len(calls) == 4:
            raise RuntimeError("forward crashed")
        return real[0](*args, **kwargs)

    def loses(*args, **kwargs):
        calls.append(None)
        home = real[1](*args, **kwargs)
        lost.extend([home] * (len(calls) == 4))
        return home

    if where == "launch":
        monkeypatch.setattr(engine, "_extend_call", crashes)
    else:
        monkeypatch.setattr(engine.pool, "page_back", loses)
        monkeypatch.setattr(llm, "np", _Numpy(lost))
    seqs, freed = make(), engine.pool.freed_total
    with pytest.raises(RuntimeError, match="forward crashed"):
        _drive(engine, seqs)
    held = [s for s in seqs if s.state is not None]
    assert len(calls) >= 4 and len(held) == 3 and not any(s.done for s in seqs)
    assert engine._flight is None and engine.pool.in_use() == 0
    assert all(s.state.lease.released for s in held)
    assert engine.pool.freed_total - freed >= 3 + 2 + 1
    freed = engine.pool.freed_total
    for s in seqs:                          # the batcher fails every caller, then
        s._release()                        # runs the release hooks: nothing more
    assert engine.pool.freed_total == freed and engine.pool.in_use() == 0
    monkeypatch.undo()
    assert [r["tokens"] for r in _alone(engine, make)] == want


class _Device:
    """A device whose runtime counts ``free`` bytes free."""

    def __init__(self, free):
        self.free, self.asked = free, 0

    def memory_stats(self):
        self.asked += 1
        return {"bytes_limit": 2**30, "bytes_in_use": 2**30 - self.free}


def _pair_and_outputs(eng, b, tc, cap):
    cfg = eng.cfg
    pair = 2 * cfg.num_layers * b * cap * cfg.kv_heads * cfg.head_dim * eng.pool.dtype.itemsize
    return pair + eng._output_bytes[b, tc]


@pytest.mark.parametrize("asks,free,ahead,fed", [
    ({}, None, 4, 3), ({"return_logits": True}, None, 0, 0),
    # room for a decode call's buffers and not for a chunk's
    ({}, "a decode call", 3, 3), ({}, "nothing", 0, 0),
], ids=["plain", "return_logits", "room-for-a-decode-call", "no-room"])
def test_the_counters_read_what_the_schedule_implies(engine, monkeypatch, asks, free, ahead, fed):
    """One request of two chunks and four tokens makes five calls. Plain: the
    second chunk is launched behind the first, the first decode call behind
    the second chunk (its token read on the device), each decode call behind
    the one before, and a fifth step lands the last. With its logits asked
    for, every call lands before the lane is fed again; so does a call whose
    pair and outputs the runtime reports no room for, and that call alone."""
    (want,) = _alone(engine, lambda: [_ask(engine.cfg, 71, 20, 4)])
    if free is not None:
        device = _Device({"nothing": 0, "a decode call": _pair_and_outputs(engine, 1, 1, 64)}[free])
        assert _pair_and_outputs(engine, 1, 8, 64) > device.free
        monkeypatch.setattr(engine, "_device", device)
        monkeypatch.setattr(engine, "_counts_bytes", True)
    (result,), d = _counted(
        engine, lambda: _alone(engine, lambda: [_ask(engine.cfg, 71, 20, 4, **asks)]))
    assert result["tokens"] == want["tokens"] and len(want["tokens"]) == 4
    assert free is None or device.asked == 4        # of every launch behind a call
    assert d == {
        "steps": 5, "calls": 5, "landed": 5, "prefill_tokens": 20,
        "decode_tokens": 3, "calls_ahead": ahead, "tokens_fed_on_device": fed,
    }


@pytest.mark.parametrize("beside", [0, 1], ids=["alone", "beside-another"])
@pytest.mark.parametrize("why", ["return_logits", "no-room"])
def test_a_lane_ended_by_the_landing_its_launch_waited_for_is_not_launched(
        engine, monkeypatch, why, beside):
    """A launch that must land the call before it first (the host makes the
    lane's token, or the device has no room for two calls' buffers) may see
    that landing end a lane by its ``eos_token``. Nothing is launched for it
    then, alone or beside a lane that goes on: as many device calls as in the
    old order, no token counted that was not asked for, and nothing in flight
    once the requests are done."""
    cfg = engine.cfg
    make = lambda **asks: [_ask(cfg, 46, 9, 7, **asks), _ask(cfg, 42, 20, 8)][:1 + beside]  # noqa: E731
    want = [r["tokens"] for r in _alone(engine, make)]
    ends = next(i for i in range(1, 6) if want[0][i] not in want[0][:i])
    asks = {"eos_token": want[0][ends]}
    if why == "return_logits":
        asks["return_logits"] = True
    else:
        monkeypatch.setattr(engine, "_device", _Device(0))
        monkeypatch.setattr(engine, "_counts_bytes", True)
    seqs = make(**asks)
    _, d = _counted(engine, lambda: _drive(engine, seqs))
    assert [s._result["tokens"] for s in seqs] == [want[0][:ends + 1]] + want[1:]
    assert d["decode_tokens"] == ends + beside * (8 - 1) and d["landed"] == d["calls"]
    if not beside:
        # the prompt's one chunk, then a decode call for each token after the first
        assert d == {
            "steps": 1 + ends, "calls": 1 + ends, "landed": 1 + ends, "prefill_tokens": 9,
            "decode_tokens": ends, "calls_ahead": 0, "tokens_fed_on_device": 0,
        }
    assert engine.pool.in_use() == 0


@pytest.mark.parametrize("arch", sorted(CONFIGS))
@pytest.mark.parametrize("short", ["admission", "growth"])
def test_an_allocation_short_of_blocks_waits_for_the_call_in_flight(arch, short):
    """A pool of fourteen blocks, all held: seven by a request whose last
    token is in the call in flight, six by one still decoding, one by a short
    one. The next step wants a block more (for a new request's prompt, or for
    the short one's seventeenth token). In the old order the first request had
    finished by then and given its blocks back; here its call is landed first,
    and nothing is shed that would not have been."""
    cfg = CONFIGS[arch]()
    eng = llm.LLMEngine(cfg, **{**ENGINE, "lane_buckets": (1, 2, 4)})
    ending, decoding, little = _ask(cfg, 81, 100, 4), _ask(cfg, 82, 90, 6), _ask(cfg, 83, 15, 4)
    late = _ask(cfg, 84, 80, 2)
    want = [r["tokens"] for r in _alone(eng, lambda: [
        _ask(cfg, 83, 15, 4), _ask(cfg, 84, 80, 2)])]
    seqs = [ending, decoding]
    for _ in range(8):                      # seven chunks, and two tokens each
        eng.step(seqs)
    seqs.append(little)
    eng.step(seqs)                          # the first's last token is launched
    assert ending.state.sent == 4 and not ending.done and eng.pool.in_use() == 14
    assert ending.state.call is eng._flight and little.state.length == 16
    if short == "admission":
        seqs.append(late)
    ahead, calls = eng.calls_ahead, eng.phase_n["dispatch"]
    eng.step([s for s in seqs if not s.done])
    assert ending.done and ending._error is None and len(ending._result["tokens"]) == 4
    # the step's first call had to wait for that landing: it is not ahead
    assert eng.calls_ahead - ahead == eng.phase_n["dispatch"] - calls - 1
    _drive(eng, seqs)
    assert [s._error for s in seqs] == [None] * len(seqs)
    assert little._result["tokens"] == want[0]
    assert short == "growth" or late._result["tokens"] == want[1]
    assert eng.pool.in_use() == 0
