"""The serving engine's step on the profiler's clock: the ``llm.*`` spans as a
real ``jax.profiler`` session records them and as the benchmark's reducer
labels idle gaps with them, the counters beside them against what the shapes
give, per form of call and a second time over the steps a session recorded,
the record of each device call on its spans and the name of the program it
runs as (a module name of its own for every shape, the same string on the
span, on the host's ``PjitFunction`` event and in ``stats()["programs"]``),
queue time per request, the slowest step's own record, what the spans cost
outside a session, and the stable device names (kernels, scopes)."""

import contextlib
import dataclasses
import gc
import glob
import os
import re
import sys
import threading
import time
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import trace_reduce
from ray_tpu._private import accelerator, internal_metrics
from ray_tpu.models import gpt
from ray_tpu.models.training import (
    abstract_state,
    default_optimizer,
    make_train_step,
)
from ray_tpu.serve import batching, llm

NANO = gpt.gpt_nano()
# wide enough that a device call outweighs the python between the phases
WIDE = dataclasses.replace(
    NANO, num_layers=4, embed_dim=256, num_heads=4, head_dim=64, mlp_dim=1024,
    vocab_size=2048,
)
ENGINE = dict(
    num_blocks=64, block_size=16, prefill_chunk=32, prefill_lanes=2,
    lane_buckets=(1, 2, 4), prefill_token_buckets=(16, 32),
    cache_buckets=(64, 128), prefix_caching=False, deployment="spans",
)
# of one device call, in order: the launch, which waits for nothing, and (a call later) the landing
LAUNCH_PHASES, LANDING_PHASES = ("upload", "kv_gather", "dispatch", "kv_scatter"), ("fetch", "sample")
CALL_PHASES = LAUNCH_PHASES + LANDING_PHASES
assert CALL_PHASES == tuple(p for p in llm.LEAF_PHASES if p != "admit")

# Three requests of 20, 40 and 9 prompt tokens, 3 new tokens each, through
# ENGINE (two prefill lanes, chunks of 32): the device calls as (lanes, lane
# bucket, tokens fed, cache bucket, tokens resident in the lanes' caches). A
# call is landed after the next is launched; "<-" marks a lane whose token the
# call reads on the device, from the call still in flight.
#   step 1: launch prefill A+B (chunks 20, 32); launch decode A<-, land the chunk
#   step 2: launch prefill B+C (chunks 8, 9), land the decode; launch decode
#           A, B<-, C<- (A's last), land the chunk
#   step 3: launch decode B<-, C<- (their last), land the decode before (A finishes)
#   step 4: nothing to launch: land the last call (B and C finish)
LENGTHS, NEW = (20, 40, 9), 3
CALLS = [
    (2, 2, 32, 64, 0), (1, 1, 1, 64, 20),
    (2, 2, 16, 64, 32), (3, 4, 1, 64, 21 + 40 + 9),
    (2, 2, 1, 64, 41 + 10),
]
STEPS, AHEAD, FED_ON_DEVICE = 4, len(CALLS) - 1, 1 + 2 + 2
PREFILL, DECODE = [CALLS[0], CALLS[2]], [CALLS[1], CALLS[3], CALLS[4]]
# the lanes of each call whose row of logits is read (``heads``): those that emit. A's
# prompt ends in the first chunk and B's does not; every decode lane emits
HEADS = (1, 1, 2, 3, 2)
# the step that launches each call, and the one that lands it
LAUNCHED_IN, LANDED_IN = (1, 1, 2, 2, 3), (1, 2, 2, 3, 4)


def _by_form(calls, tokens):
    """``stats()["calls"][form]`` less ``busy_s`` for these ``CALLS``, which fed ``tokens``."""
    return {
        "n": len(calls), "lanes_used": sum(c[0] for c in calls),
        "lane_slots": sum(c[1] for c in calls),
        "heads": sum(HEADS[CALLS.index(c)] for c in calls), "tokens": tokens,
        "token_slots": sum(b * tc for _, b, tc, _, _ in calls),
        "cache_tokens": sum(c[4] for c in calls),
        "cache_slots": sum(b * cap for _, b, _, cap, _ in calls),
        "paged": 0,     # a GPT's extend reads no pages: every call gathers
    }


def _requests(lengths=LENGTHS, new=NEW, vocab=NANO.vocab_size):
    return [
        batching._Sequence({
            "prompt": [(7 * i + j) % vocab for j in range(n)],
            "max_new_tokens": new,
        })
        for i, n in enumerate(lengths)
    ]


def _drive(eng, seqs):
    """Step until every sequence is done; returns the number of steps."""
    steps = 0
    while not all(s.done for s in seqs):
        eng.step([s for s in seqs if not s.done])
        steps += 1
        assert steps < 200
    return steps


def _delta(after, before, key):
    """``after[key] - before[key]``, through groups of numbers."""
    if isinstance(after[key], dict):
        return {k: _delta(after[key], before[key], k) for k in after[key]}
    return after[key] - before[key]


def _key_tree(stats):
    """The keys of ``stats()``, groups within groups; ``slowest_step`` is a
    record of one step (None before the first), not a group of counters."""
    return {
        k: _key_tree(v) if isinstance(v, dict) and k != "slowest_step" else None
        for k, v in stats.items()}


@contextlib.contextmanager
def _session(where):
    """A real profiler session, as the benchmark's: host spans from annotations alone."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(where), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _xplane_of(where):
    found = glob.glob(os.path.join(str(where), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    return found[0]


def _host_events(path):
    from jax.profiler import ProfileData

    host = next(
        p for p in ProfileData.from_file(path).planes if p.name == trace_reduce.HOST_PLANE)
    return [e for line in host.lines for e in line.events]


def _recorded(path, name):
    """``(start, end, metadata)`` of every host span ``name``, in order."""
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for e in _host_events(path) if e.name == name)


def _ran(path, pattern):
    """``(start, end, name)`` of every host event whose name ``pattern`` matches whole."""
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for e in _host_events(path) if re.fullmatch(pattern, e.name))


@pytest.fixture(scope="module")
def engine():
    """A warm ``gpt_nano`` engine: every shape of the known workload compiled."""
    eng = llm.LLMEngine(NANO, **ENGINE)
    _drive(eng, _requests())
    return eng


@pytest.fixture(scope="module")
def session(engine, tmp_path_factory):
    """The known workload under a real profiler session, while a batcher
    waits for work on a thread of its own: the path of the trace, and the
    engine's ``stats()`` just outside the session on either side."""
    idle = batching._ContinuousBatcher(
        lambda seqs: [s.finish(len(s.item)) for s in seqs], 4, 0.0, None, name="idle",
    )
    where = tmp_path_factory.mktemp("profile")
    before = engine.stats()
    try:
        with _session(where):
            assert idle.submit("ab") == 2       # the wait that follows starts in the session
            _drive(engine, _requests())
            assert idle.submit("abc") == 3      # ... and ends in it
    finally:
        idle.shutdown()
    return types.SimpleNamespace(
        xplane=_xplane_of(where), before=before, after=engine.stats())


@pytest.fixture(scope="module")
def xplane(session):
    return session.xplane


@pytest.fixture(scope="module")
def planes(xplane):
    """The trace as the benchmark's loader reads it (host lines merged by
    name: every python thread is ``python``)."""
    return trace_reduce.load_xplane(xplane)


def _spans(events, name):
    return [(s, s + d) for n, s, d in events if n == name]


def _engine_line(planes):
    lines = [
        events for events in planes[trace_reduce.HOST_PLANE].values()
        if any(n.startswith("llm.") for n, _, _ in events)
    ]
    assert len(lines) == 1
    return lines[0]


# -- (a) the spans, nested as the table says, on one thread -----------------


def test_every_phase_is_a_span_nested_in_its_parent(planes):
    events = _engine_line(planes)
    names = {n for n, _, _ in events if n.startswith("llm.")}
    assert names == {"llm." + p for p in llm.PHASES}

    def inside(child, parents):
        return any(a <= child[0] and child[1] <= b for a, b in parents)

    steps = _spans(events, "llm.step")
    assert len(steps) == STEPS
    calls = _spans(events, "llm.prefill") + _spans(events, "llm.decode")
    assert len(calls) == len(CALLS)
    for top in ("admit", "prefill", "decode"):
        assert all(inside(s, steps) for s in _spans(events, "llm." + top)), top
    assert len(_spans(events, "llm.admit")) == STEPS
    for phase in CALL_PHASES:
        spans = _spans(events, "llm." + phase)
        assert len(spans) == len(CALLS), phase        # one of each per device call
        assert all(inside(s, steps) for s in spans), phase
        # a launch is its call's own; a landing follows the next call's launch,
        # but for the last, which has the last step to itself
        held = spans if phase in LAUNCH_PHASES else spans[:-1]
        assert all(inside(s, calls) for s in held), phase
        assert phase in LAUNCH_PHASES or not inside(spans[-1], calls)
    # a launch's phases follow one another, and so do a landing's: the first
    # call's launch, then every other launch with the landing of the call
    # before, then the last landing
    order = [n[4:] for n, _, _ in sorted(events, key=lambda e: e[1]) if n[4:] in CALL_PHASES]
    assert tuple(order) == (
        LAUNCH_PHASES + (LAUNCH_PHASES + LANDING_PHASES) * (len(CALLS) - 1) + LANDING_PHASES)


def test_spans_are_on_the_thread_that_does_the_work(xplane):
    """The engine's on the thread that steps it, the batcher's wait for work
    on the batcher's."""
    from jax.profiler import ProfileData

    host = next(
        p for p in ProfileData.from_file(xplane).planes if p.name == trace_reduce.HOST_PLANE
    )
    threads = [{e.name for e in line.events} for line in host.lines]
    engine = [names for names in threads if any(n.startswith("llm.") for n in names)]
    waiting = [names for names in threads if "serve.batch_idle" in names]
    assert len(engine) == 1 and len(waiting) == 1 and not engine[0] & waiting[0]


def test_the_batcher_does_not_import_jax_to_say_it_is_idle(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    assert isinstance(batching._idle_span(), contextlib.nullcontext)
    assert "jax" not in sys.modules


# -- (b) what the benchmark's reducer makes of them --------------------------


def test_reducer_labels_gaps_with_the_phase_under_them(planes):
    """A synthetic device that is busy except during the middle third of every
    leaf phase: each gap's midpoint lies in one leaf, and the reducer must
    name that leaf, not its ``prefill`` / ``decode`` / ``step`` or ``python``."""
    events = [e for e in _engine_line(planes) if e[0].startswith("llm.")]
    lo, hi = trace_reduce.annotation_window(
        {trace_reduce.HOST_PLANE: {"engine": events}}, "llm.step"
    )
    holes, want = [], {}
    for name, start, dur in events:
        if name[4:] in llm.LEAF_PHASES:
            holes.append((start + dur / 3, start + 2 * dur / 3))
            label = "llm.step:" + name
            want[label] = want.get(label, 0.0) + dur / 3 / 1e9
    ops = [
        (f"fusion.{i}", a, b - a)
        for i, (a, b) in enumerate(trace_reduce.subtract([(lo, hi)], sorted(holes)))
    ]
    reduced = trace_reduce.reduce({
        trace_reduce.HOST_PLANE: {"engine": events},
        "/device:TPU:0": {trace_reduce.OPS_LINE: ops},
    }, "llm.step")
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"llm.step:llm." + p for p in llm.LEAF_PHASES}
    assert gaps == pytest.approx(want, rel=1e-6)
    assert reduced["window_s"] - reduced["busy_s"] == pytest.approx(sum(want.values()))


# -- (c) the counters against what the shapes give ---------------------------


def test_counters_equal_what_the_shapes_give(engine):
    before = engine.stats()
    assert _drive(engine, _requests()) == STEPS
    after = engine.stats()
    block = ENGINE["block_size"]
    want = {
        "steps": STEPS, "admitted": 3,
        "prefill_tokens": sum(LENGTHS),
        "decode_tokens": len(LENGTHS) * (NEW - 1),
        "lanes_used": sum(c[0] for c in CALLS),
        "lane_slots": sum(c[1] for c in CALLS),
        "cache_tokens": sum(c[4] for c in CALLS),
        "cache_slots": sum(b * cap for _, b, _, cap, _ in CALLS),
        # int32 up, one buffer a call: a lane's length, last row, count and
        # where its token is, then tokens, the page-back's rows and slots and
        # the block table, each as wide as the widest of them in any bucket
        # (32 tokens; 128 / 16 blocks); the pair never leaves the device
        "h2d_bytes": sum(4 * b * (4 + 4 * max(32, 128 // block)) for _, b, _, _, _ in CALLS),
        "h2d_transfers": len(CALLS),
        # int32 down, one array a call: the id a lane sampled on the device,
        # as wide as the widest lane bucket whatever the lanes
        "d2h_bytes": 4 * max(ENGINE["lane_buckets"]) * len(CALLS),
        "d2h_transfers": len(CALLS),
        "ids_only_calls": len(CALLS),
        # every call but the first is launched behind the one in flight, and a
        # decode lane reads its token there unless that call has landed
        "calls_ahead": AHEAD, "tokens_fed_on_device": FED_ON_DEVICE,
    }
    assert {k: _delta(after, before, k) for k in want} == want
    assert want["lanes_used"] < want["lane_slots"]
    calls = _delta(after, before, "phase_n")
    assert calls == {
        "step": STEPS, "admit": STEPS, "prefill": 2, "decode": 3,
        **dict.fromkeys(CALL_PHASES, len(CALLS)),
    }
    assert _delta(after, before, "queue_s") > 0
    # the two forms of call, by hand; the totals are their sums
    by_form = _delta(after, before, "calls")
    busy = {form: by_form[form].pop("busy_s") for form in llm.FORMS}
    assert by_form == {
        "prefill": _by_form(PREFILL, sum(LENGTHS)),
        "decode": _by_form(DECODE, len(LENGTHS) * (NEW - 1)),
    }
    for total in ("lanes_used", "lane_slots", "cache_tokens", "cache_slots"):
        assert want[total] == sum(by_form[form][total] for form in llm.FORMS), total
    assert (want["prefill_tokens"], want["decode_tokens"]) == tuple(
        by_form[form]["tokens"] for form in llm.FORMS)
    # a call's time on the device ends at its landing and begins no earlier
    # than the landing before it: the calls' times do not overlap
    assert all(v > 0 for v in busy.values())
    assert sum(busy.values()) <= _delta(after, before, "phase_s")["step"]


def test_a_host_away_from_the_engine_is_not_the_devices_time(engine):
    """The call in flight when ``step`` returns is landed by the next step,
    whenever that comes: where the device had finished it by then (a batcher
    that waits, a profiler that starts: here, a sleep), the wait is not its."""
    seqs, before = _requests(), engine.stats()
    t0 = time.perf_counter()
    while not all(s.done for s in seqs):
        engine.step([s for s in seqs if not s.done])
        time.sleep(0.2)
    wall_s, spent = time.perf_counter() - t0, _delta(engine.stats(), before, "calls")
    assert wall_s > STEPS * 0.2 > 0.2 > sum(spent[form]["busy_s"] for form in llm.FORMS) > 0


def test_kv_stats_has_every_key_from_construction():
    """Two reads bound a window only for a key both hold: every counter, every
    group and every key of a group is there, at zero, before the first step;
    the group of every program the buckets allow once ``warm()`` has run them."""
    eng = llm.LLMEngine(NANO, **ENGINE)
    assert eng.stats()["programs"] == {} == eng.stats()["traced"]["programs"]
    eng.warm()
    built = eng.stats()
    assert all(v == 0 for v in built["calls"]["decode"].values())
    assert built["traced"]["steps"] == 0 and built["traced"]["calls"] == built["calls"]
    assert built["traced"]["programs"] == built["programs"] == {
        llm._extend_name(*shape): {"n": 0, "busy_s": 0.0} for shape in eng.extend_shapes()}
    assert (built["programs_cold"], built["programs_cold_s"]) == (0, 0.0)
    assert set(built["traced"]) == set(eng._work()) < set(built)
    # what the host's work cost and what held it: the engine's own units at zero, no
    # step held, the ring empty; the collector's totals are the process's
    units = dict.fromkeys(llm.HOST_UNITS, dict(
        n=0, wall_s=0.0, cpu_s=0.0, others_cpu_s=0.0, gc_s=0.0, switched=0, faults=0))
    nothing_held = {"n": 0, "excess_s": 0.0, **{c: {"n": 0, "s": 0.0} for c in accelerator.CAUSES}}
    assert built["host"] == units == built["traced"]["host"]
    assert built["held"] == nothing_held == built["traced"]["held"]
    assert built["held_steps"] == [] and "held_steps" not in built["traced"]
    assert set(built["gc"]) == {"n", "s", "longest_s", "generations"} and "gc" not in built["traced"]
    _drive(eng, _requests())
    assert _key_tree(eng.stats()) == _key_tree(built)
    after = eng.stats()["host"]
    assert after["llm.step"]["n"] == STEPS and after["llm.between"]["n"] == STEPS - 1


# -- (c') the same counters over the steps a session recorded, and the calls' records


def test_traced_is_the_counters_over_exactly_the_recorded_steps(engine, session, planes):
    before, after = session.before, session.after
    traced = {k: _delta(after["traced"], before["traced"], k) for k in after["traced"]}
    whole = {k: _delta(after, before, k) for k in traced}
    assert traced["steps"] == STEPS == len(_spans(_engine_line(planes), "llm.step"))
    assert traced["calls"]["decode"]["n"] == len(DECODE)

    def flat(group):
        return [x for v in group.values() for x in (flat(v) if isinstance(v, dict) else [v])]

    # seconds are summed step by step here and all at once there
    for seconds in ("phase_s", "calls", "programs", "queue_s"):
        assert flat({"": traced.pop(seconds)}) == pytest.approx(
            flat({"": whole.pop(seconds)}), rel=1e-9)
    assert traced == whole
    # and steps outside a session leave it as it is
    _drive(engine, _requests())
    assert engine.stats()["traced"] == after["traced"]


def test_a_calls_counts_go_to_the_step_that_launched_it(tmp_path):
    """What ``extend`` counts on the device comes home a step after the call's
    launch. A session that starts between the two leaves the call out of
    ``traced``; a call launched in a session's last step is in it, though it
    lands after the session has stopped. 40 prompt tokens in chunks of 32 + 8,
    then two decode calls, three layers: 3 x 42 queries, 3 x 32 in the first call."""
    from ray_tpu.models import keye_vl2

    eng = llm.LLMEngine(
        keye_vl2.keye_vl2_nano(), num_blocks=16, block_size=16, prefill_chunk=32,
        lane_buckets=(1,), prefill_token_buckets=(32,), cache_buckets=(64,),
        prefix_caching=False)
    eng.warm()
    built = eng.stats()

    def request():
        return [batching._Sequence({"prompt": list(range(1, 41)), "max_new_tokens": 3})]

    def counted(after, before):
        return tuple(
            _delta(book(after), book(before), "sparse_queries")
            for book in (lambda s: s, lambda s: s["traced"]))

    # launched before the session, landed in it
    seqs, before = request(), built
    eng.step(seqs)
    assert eng._flight.shape == (1, 32, 64) and not eng._flight.recorded
    with _session(tmp_path / "late"):
        steps = _drive(eng, seqs)
    after = eng.stats()
    assert counted(after, before) == (3 * 42, 3 * 42 - 3 * 32)
    assert _delta(after["traced"], before["traced"], "steps") == steps == 3
    assert _delta(after["traced"], before["traced"], "calls")["prefill"]["n"] == 1
    # ... and each landing's span says what its call counted, under the configuration's names
    fetched = [what for _, _, what in _recorded(_xplane_of(tmp_path / "late"), "llm.fetch")]
    assert [what["call"] for what in fetched] == [1, 2, 3, 4]
    assert [what["sparse_queries"] for what in fetched] == [3 * 32, 3 * 8, 3, 3]
    assert set(eng.cfg.counters) <= set(fetched[0])

    # launched in the session's last step, landed after it
    seqs, before = request(), after
    with _session(tmp_path / "early"):
        eng.step(seqs)
    assert eng._flight.recorded and eng.stats()["traced"]["sparse_queries"] == before[
        "traced"]["sparse_queries"]
    _drive(eng, seqs)
    after = eng.stats()
    assert counted(after, before) == (3 * 42, 3 * 32)
    assert _delta(after["traced"], before["traced"], "steps") == 1
    assert _delta(after["traced"], before["traced"], "calls")["prefill"]["busy_s"] > 0
    assert _key_tree(after) == _key_tree(built)


def test_dispatch_and_fetch_say_which_call_and_what_it_is(xplane, planes):
    dispatched = _recorded(xplane, "llm.dispatch")
    fetched = _recorded(xplane, "llm.fetch")
    steps = _spans(_engine_line(planes), "llm.step")
    first = dispatched[0][2]["call"]
    assert [what for _, _, what in dispatched] == [
        {
            "call": first + i, "form": "prefill" if call in PREFILL else "decode",
            "lanes": call[0], "lane_slots": call[1], "heads": heads, "tokens": tokens,
            "token_slots": call[1] * call[2], "cache_tokens": call[4],
            "cache_slots": call[1] * call[3], "paged": 0, "ahead": int(i > 0),
            "program": llm._extend_name(*call[1:4]),
        }
        for i, (call, tokens, heads) in enumerate(zip(CALLS, (20 + 32, 1, 8 + 9, 3, 2), HEADS))
    ]
    # a landing names the call it lands and nothing else (``gpt_nano`` counts nothing)
    assert [what for _, _, what in fetched] == [{"call": first + i} for i in range(len(CALLS))]

    def step_of(span):
        return 1 + next(i for i, (a, b) in enumerate(steps) if a <= span[0] and span[1] <= b)

    assert tuple(map(step_of, dispatched)) == LAUNCHED_IN
    assert tuple(map(step_of, fetched)) == LANDED_IN
    assert sum(a != b for a, b in zip(LAUNCHED_IN, LANDED_IN)) == STEPS - 1


def test_a_call_that_reads_pages_has_no_gather_phase_and_says_paged(tmp_path):
    """A model whose ``extend`` offers ``table=`` (MiMo-V2-Flash): a decode call's launch
    is ``upload``, ``dispatch``, ``kv_scatter`` with no ``kv_gather`` between them, its
    ``llm.dispatch`` says ``paged`` 1, and ``stats()["calls"]["decode"]["paged"]`` counts
    every decode call (``traced`` too); a chunk gathers as ever and says 0."""
    from ray_tpu.models import mimo_v2_flash

    cfg = mimo_v2_flash.mimo_v2_flash_nano()
    eng = llm.LLMEngine(
        cfg, num_blocks=32, block_size=8, prefill_chunk=16, prefill_lanes=1,
        lane_buckets=(1, 2), prefill_token_buckets=(16,), cache_buckets=(32, 64),
        prefix_caching=False, state_slots=8)
    assert llm.reads_pages(eng._extend) and not llm.reads_pages(llm.LLMEngine(NANO, **ENGINE)._extend)
    eng.warm()
    before = eng.stats()
    seqs = _requests(lengths=(20, 9), new=4, vocab=cfg.vocab_size)
    with _session(tmp_path):
        _drive(eng, seqs)
    after = eng.stats()
    path = _xplane_of(tmp_path)
    dispatched = [what for _, _, what in _recorded(path, "llm.dispatch")]
    assert {what["form"] for what in dispatched} == {"prefill", "decode"}
    assert all(what["paged"] == (what["form"] == "decode") for what in dispatched)
    decodes = sum(what["form"] == "decode" for what in dispatched)
    for book in (lambda s: s, lambda s: s["traced"]):
        calls = _delta(book(after), book(before), "calls")
        assert calls["decode"]["paged"] == calls["decode"]["n"] == decodes > 0
        assert calls["prefill"]["paged"] == 0 < calls["prefill"]["n"]
    # a gather a chunk and none a decode call, in the counts and among the spans
    chunks = len(dispatched) - decodes
    assert _delta(after, before, "phase_n")["kv_gather"] == chunks
    assert _delta(after, before, "phase_n")["dispatch"] == len(dispatched)
    launches = sorted(
        (start, end, name[4:]) for start, end, name in _ran(path, r"llm\.\w+")
        if name[4:] in LAUNCH_PHASES)
    order = "".join(name[0] for _, _, name in launches)       # u(pload) k(v_..) d(ispatch) k
    assert order.count("ukdk") == chunks and order.replace("ukdk", "") == "udk" * decodes
    # and no gather program ran for a decode call: the chunks' one lane alone
    gathers = [name for _, _, name in _ran(path, r"PjitFunction\(gather_\w+\)")]
    assert gathers and all(name.startswith("PjitFunction(gather_1x") for name in gathers)


def test_heads_says_how_many_lanes_of_a_call_have_their_row_of_logits_read(tmp_path):
    """A prompt of 70 tokens alone (three chunks of 32, 32 and 6) and 3 new tokens,
    recorded: ``heads`` is on every ``llm.dispatch`` span, 0 for the prompt's chunks
    but its last (no lane emits: the head does not run), the emitting lanes
    otherwise, and ``stats()["calls"]`` sums it a form, in ``traced`` too."""
    engine = llm.LLMEngine(NANO, **ENGINE)      # the module's keeps its session's record
    before = engine.stats()
    with _session(tmp_path):
        _drive(engine, _requests((70,)))
    after = engine.stats()
    dispatched = [what for _, _, what in _recorded(_xplane_of(tmp_path), "llm.dispatch")]
    assert [(what["form"], what["lanes"], what["heads"]) for what in dispatched] == [
        ("prefill", 1, 0), ("prefill", 1, 0), ("prefill", 1, 1), ("decode", 1, 1),
        ("decode", 1, 1)]
    for work in (_delta(after, before, "calls"), _delta(after["traced"], before["traced"], "calls")):
        assert (work["prefill"]["n"], work["prefill"]["heads"]) == (3, 1)
        assert work["decode"]["heads"] == work["decode"]["lanes_used"] == NEW - 1


# -- (c'') every program under a name of its own, and the call's record says which


def _module(lowered):
    """``jit_extend_decode_1x1x64`` of a lowered program's text."""
    return re.match(r"module @(\w+) ", lowered.as_text())[1]


def test_two_shapes_are_two_modules_and_one_shape_twice_is_one():
    eng = llm.LLMEngine(NANO, **ENGINE)
    family = eng._extend_call

    def lowered(b, tc, cap):
        return family.lower(
            llm._extend_name(b, tc, cap), *eng._extend_args(jax.ShapeDtypeStruct, b, tc, cap), tc=tc)

    assert _module(lowered(1, 1, 64)) == "jit_extend_decode_1x1x64"
    assert _module(lowered(2, 16, 128)) == "jit_extend_prefill_2x16x128"
    made = family.names()
    assert _module(lowered(1, 1, 64)) == "jit_extend_decode_1x1x64" and family.names() == made
    assert family.member("extend_decode_1x1x64") is family.member("extend_decode_1x1x64")
    assert len(set(made)) == len(made) and "extend_decode_1x1x64" in made
    # the pool's: a gather per (lanes, cache), a page-back per (lanes, tokens),
    # compiled when the engine was built; the state store's copy by its own name
    paging = llm._paging_programs()
    assert {f"gather_{b}x{cap}" for b in (1, 2, 4) for cap in (64, 128)} <= set(
        paging.gather.names())
    assert {f"page_back_{b}x{tc}" for b in (1, 2, 4) for tc in (1, 16, 32)} <= set(
        paging.page_back.names())
    operands = jax.ShapeDtypeStruct((2, eng._operand_width), jnp.int32)
    assert _module(paging.gather.lower("gather_2x64", eng.pool.arenas, operands, 4)) == (
        "jit_gather_2x64")
    assert llm._state_programs().copy.__name__ == "state_copy"
    # a name is a component of every op_name of its program, and the reducer
    # takes any dotted component for a scope: no name has a dot, none can
    for name in made + paging.gather.names() + paging.page_back.names():
        assert re.fullmatch(r"[A-Za-z0-9_]+", name), name
        assert trace_reduce.scope_of(f"jit({name})/dot_general") == trace_reduce.NO_SCOPE
    with pytest.raises(ValueError, match="no dot"):
        family.member("extend.decode_1x1x64")


def test_a_dispatch_names_the_program_its_call_runs_as(engine, xplane):
    """One string on the engine's span, on the runtime's own event of the call
    under it (``PjitFunction(<name>)``: what a chip's ``XLA Modules`` line shows
    as ``jit_<name>(<id>)``) and in the lowered module."""
    dispatched = _recorded(xplane, "llm.dispatch")
    ran = _ran(xplane, r"PjitFunction\(extend_\w+\)")
    assert len(dispatched) == len(CALLS)
    # (the runtime writes the event of a call twice, one inside the other)
    assert all(any(a <= at and until <= b for a, b, _ in dispatched) for at, until, _ in ran)
    for (start, end, what), call in zip(dispatched, CALLS):
        assert {event for at, until, event in ran if start <= at and until <= end} == {
            f"PjitFunction({what['program']})"}
        assert what["program"] == llm._extend_name(*call[1:4]) and "cold" not in what
        b, tc, cap = call[1:4]
        assert _module(engine._extend_call.lower(
            what["program"], *engine._extend_args(jax.ShapeDtypeStruct, b, tc, cap), tc=tc)
        ) == "jit_" + what["program"]
    # every other program of a step has a name of its own too
    others = {name for _, _, name in _ran(xplane, r"PjitFunction\(\w+\)")}
    assert {"PjitFunction(gather_2x64)", "PjitFunction(page_back_2x32)"} <= others
    assert not {"PjitFunction(extend_call)", "PjitFunction(gather)"} & others


def test_programs_split_the_calls_by_name_and_traced_holds_recorded_steps_alone(
        engine, session):
    eng = llm.LLMEngine(NANO, **ENGINE)
    eng.warm()
    before = eng.stats()
    _drive(eng, _requests())
    after = eng.stats()
    ran = _delta(after, before, "programs")
    assert {name: counts["n"] for name, counts in ran.items() if counts["n"]} == {
        "extend_prefill_2x32x64": 1, "extend_prefill_2x16x64": 1, "extend_decode_1x1x64": 1,
        "extend_decode_4x1x64": 1, "extend_decode_2x1x64": 1}
    calls = _delta(after, before, "calls")
    assert sum(c["busy_s"] for c in ran.values()) == pytest.approx(
        sum(calls[form]["busy_s"] for form in llm.FORMS), rel=1e-9)
    assert all((c["busy_s"] > 0) == (c["n"] > 0) for c in ran.values())
    assert _delta(after, before, "programs_cold") == 0
    # no session recorded a step of that engine
    assert after["traced"]["programs"] == before["traced"]["programs"]
    assert not any(c["n"] for c in after["traced"]["programs"].values())
    # the module's engine, under its session: the recorded steps' calls and no other
    traced = _delta(session.after["traced"], session.before["traced"], "programs")
    assert sum(c["n"] for c in traced.values()) == len(CALLS)
    assert sum(c["n"] for c in _delta(session.before, engine.stats(), "programs").values()) < 0
    assert engine.stats()["traced"]["programs"] == session.after["traced"]["programs"]


def test_a_shape_the_warm_up_left_out_is_counted_cold_once_with_its_seconds(tmp_path):
    eng = llm.LLMEngine(NANO, **ENGINE)
    left_out = (4, 1, 64)                   # CALLS[3]'s program
    shapes = [s for s in eng.extend_shapes() if s != left_out]
    eng.extend_shapes = lambda: shapes
    eng.warm()
    before = eng.stats()
    assert llm._extend_name(*left_out) not in before["programs"]
    with _session(tmp_path):
        _drive(eng, _requests())
        inside = eng.stats()
        _drive(eng, _requests())
    after = eng.stats()
    assert _delta(inside, before, "programs_cold") == 1 == _delta(after, before, "programs_cold")
    assert 0 < _delta(inside, before, "programs_cold_s") == _delta(
        after, before, "programs_cold_s") <= _delta(inside, before, "phase_s")["dispatch"]
    assert after["traced"]["programs_cold"] == 1
    assert after["traced"]["programs_cold_s"] == pytest.approx(after["programs_cold_s"])
    assert after["programs"][llm._extend_name(*left_out)]["n"] == 2 == after["traced"][
        "programs"][llm._extend_name(*left_out)]["n"]
    cold = [
        what.get("cold") for _, _, what in _recorded(_xplane_of(tmp_path), "llm.dispatch")]
    assert cold == [None] * 3 + [1] + [None] * 6
    assert _key_tree(after) == _key_tree(inside) != _key_tree(before)


def test_leaf_phases_add_up_to_the_step():
    eng = llm.LLMEngine(WIDE, **ENGINE)
    requests = lambda: _requests((60, 50, 40, 30), 8, WIDE.vocab_size)  # noqa: E731
    _drive(eng, requests())
    before = eng.stats()
    _drive(eng, requests())
    spent = _delta(eng.stats(), before, "phase_s")
    assert all(v > 0 for v in spent.values())
    leaves = sum(spent[p] for p in llm.LEAF_PHASES)
    assert leaves == pytest.approx(spent["step"], rel=0.03)
    assert spent["prefill"] + spent["decode"] + spent["admit"] <= spent["step"]


# -- (d) queue time per request ----------------------------------------------


def _observed(name):
    series = internal_metrics.get(name)._snapshot()["series"]
    return series.get((("deployment", ENGINE["deployment"]),), {"count": 0, "sum": 0.0})


def test_queue_time_is_in_every_result_and_counts_the_wait_for_a_slot(engine):
    first, held = _requests((20, 9))
    before, seen0 = engine.stats(), _observed("ray_tpu_llm_queue_seconds")
    engine.step([first])                    # ``held`` waits for a slot meanwhile
    engine.step([first])
    waited = _delta(engine.stats(), before, "phase_s")["step"]
    _drive(engine, [first, held])
    after = engine.stats()
    results = [s._result for s in (first, held)]
    for r in results:
        assert 0 <= r["queue_s"] <= r["ttft_s"]
    assert results[1]["queue_s"] >= waited > results[0]["queue_s"]
    assert _delta(after, before, "admitted") == 2
    assert _delta(after, before, "queue_s") == pytest.approx(
        sum(r["queue_s"] for r in results)
    )
    # observed where the time to the first token is, once a request
    seen = _observed("ray_tpu_llm_queue_seconds")
    assert seen["count"] - seen0["count"] == 2
    assert seen["sum"] - seen0["sum"] == pytest.approx(sum(r["queue_s"] for r in results))


# -- (e) the slowest step's own record ---------------------------------------


@pytest.mark.parametrize(
    "phase, owner, method",
    # the call of extend and the dispatch of the page-back program
    [("dispatch", "engine", "_extend_call"), ("kv_scatter", "pool", "page_back")],
)
def test_slowest_step_names_the_step_and_the_phase_that_stalled(
    engine, monkeypatch, phase, owner, method
):
    engine.stats()                          # a read starts the record anew
    owner = engine if owner == "engine" else engine.pool
    real, calls, planted = getattr(owner, method), [], {}

    def stalls_once(*args, **kwargs):
        calls.append(None)
        if len(calls) == 3:                 # the third device call opens the second step
            planted["at"] = time.time()
            time.sleep(0.25)
        return real(*args, **kwargs)

    monkeypatch.setattr(owner, method, stalls_once)
    _drive(engine, _requests())
    slow = engine.stats()["slowest_step"]
    assert 0.25 <= slow["phase_s"][phase] <= slow["wall_s"] == slow["phase_s"]["step"]
    assert max(llm.LEAF_PHASES, key=lambda p: slow["phase_s"].get(p, 0.0)) == phase
    assert planted["at"] <= slow["at"] <= planted["at"] + slow["wall_s"] + 0.05
    assert slow["lanes"] == CALLS[2][0] + CALLS[3][0]       # the second step's calls


def test_two_reads_bound_the_slowest_step(engine, monkeypatch):
    """A stall shorter than an earlier one shows once the earlier was read."""
    real, stall = engine._extend_call, [0.3]

    def stalls(*args, **kwargs):
        time.sleep(stall.pop() if stall else 0.0)
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "_extend_call", stalls)
    engine.stats()
    _drive(engine, _requests())
    assert engine.stats()["slowest_step"]["wall_s"] >= 0.3
    stall.append(0.1)
    _drive(engine, _requests())
    assert 0.1 <= engine.stats()["slowest_step"]["wall_s"] < 0.3
    assert engine.stats()["slowest_step"] is None           # no step since


# -- (e') a step that stood still keeps its cause and its stack --------------------


class _Node:
    pass


def _cycles(n=500_000):
    """A large graph of cycles built with the collector off: dropping it leaves
    the collector some 0.1 s of work."""
    graph = []
    for _ in range(n):
        a, b = _Node(), _Node()
        a.other, b.other = b, a
        graph.append(a)
    return graph


def _spin(seconds):
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        pass


def _plant_gc(state):
    state["graph"].clear()
    gc.collect()


def _plant_spinner(state):
    """The engine's thread waits for a thread that spins."""
    done = threading.Event()
    state["over"] = threading.Event()

    def spins():
        _spin(0.25)
        done.set()
        state["over"].wait(5.0)         # alive when the step ends: its clock can be read

    threading.Thread(target=spins, name="spinner", daemon=True).start()
    done.wait(5.0)


def _sleeps_here(state):
    time.sleep(0.2)


@pytest.mark.limit(120)
@pytest.mark.parametrize("cause, method, plant, where", [
    ("gc", "_extend_call", _plant_gc, "dispatch"),
    ("python", "_admit", lambda state: _spin(0.2), "admit"),
    ("threads", "_extend_call", _plant_spinner, "dispatch"),
    ("machine", "_extend_call", _sleeps_here, "dispatch"),
])
def test_a_held_step_keeps_its_cause_its_phase_and_its_stack(
        engine, monkeypatch, cause, method, plant, where):
    real, calls, state = getattr(engine, method), [], {}

    def held_once(*args, **kwargs):
        calls.append(None)
        if len(calls) == 3:
            plant(state)
        return real(*args, **kwargs)

    for _ in range(3):                      # the engine's usual step: warm, a millisecond
        _drive(engine, _requests())
    gc.collect()
    before = engine.stats()
    gc.disable()                            # nothing but the planted collection collects
    try:
        state["graph"] = _cycles() if cause == "gc" else []
        monkeypatch.setattr(engine, method, held_once)
        _drive(engine, _requests())
    finally:
        gc.enable()
        state.get("over", threading.Event()).set()
    after = engine.stats()
    held = _delta(after, before, "held")
    # (the planted one, and whatever a loaded machine held beside it)
    new = [r for r in after["held_steps"] if r not in before["held_steps"]]
    assert 1 <= held["n"] == len(new) <= 3
    record = max(new, key=lambda r: r["excess_s"])
    assert record["unit"] == "llm.step" and record["where"] == where
    if record["cause"] != cause:
        # a machine with more to run than CPUs gives the busy thread under half of one,
        # and then says so itself: the thread was switched out, or its CPU is the most there was
        assert record["cause"] == "machine" and cause in ("python", "threads")
        assert record["switched"] > 0 if cause == "python" else record["busiest"][0][0] == "spinner"
        pytest.skip("the machine took the CPU the planted cause was to burn: it reads `machine`")
    assert held[cause]["n"] >= 1
    assert held["excess_s"] == pytest.approx(sum(r["excess_s"] for r in new))
    assert 0.05 < record["excess_s"] <= record["wall_s"] < 1.0
    # (a collection holds the interpreter lock from its start to its end: the watcher
    # cannot look while it lasts, and the cause is the answer there)
    assert len(record["stack"]) <= 12
    assert cause == "gc" or "held_once" in ";".join(record["stack"])
    if cause == "threads":
        assert record["busiest"][0][0] == "spinner"
    if cause == "machine":
        assert record["stack"][-1].startswith("test_llm_spans.py:_sleeps_here:")
    if cause == "gc":
        assert _delta(after, before, "gc")["generations"]["2"]["n"] == 1
        assert record["gc_s"] == pytest.approx(_delta(after, before, "gc")["s"], rel=0.05)
    # the slowest step since the read before is that step, with the record's fields
    slow = after["slowest_step"]
    assert slow["cause"] == cause and slow["where"] == where and slow["stack"] == record["stack"]
    assert slow["wall_s"] == record["wall_s"] and slow["excess_s"] == record["excess_s"]
    assert slow["cpu_s"] == record["cpu_s"] and slow["gc_s"] == record["gc_s"]


@pytest.mark.limit(60)
def test_a_landing_its_program_does_not_explain_is_held_in_fetch(engine, monkeypatch):
    """A call's landing later than its program's usual call explains: the wait is
    the device's (or the runtime's), so the step is held in ``fetch``; the usual
    wait for a call is explained, and no step of a plain load is held."""
    before = engine.stats()
    _drive(engine, _requests())
    assert _delta(engine.stats(), before, "held")["python"]["n"] == 0
    real, landed = np.asarray, []

    def lands_late(a, *args, **kwargs):
        if isinstance(a, jax.Array) and a.dtype == jnp.int32:
            landed.append(None)
            if len(landed) == 2:
                time.sleep(0.4)
        return real(a, *args, **kwargs)

    monkeypatch.setattr(llm.np, "asarray", lands_late)
    _drive(engine, _requests())
    monkeypatch.undo()
    after = engine.stats()
    assert 1 <= _delta(after, before, "held")["n"] <= 3
    record = max(
        (r for r in after["held_steps"] if r not in before["held_steps"]), key=lambda r: r["excess_s"])
    assert record["where"] == "fetch" and record["cause"] == "machine"
    assert 0.15 <= record["excess_s"] <= record["wall_s"]
    assert "lands_late" in ";".join(record["stack"])
    assert after["slowest_step"]["where"] == "fetch"


@pytest.mark.limit(60)
def test_the_time_between_two_steps_is_a_unit_while_a_sequence_is_active(engine):
    """From a step's return to the next step: the batcher's loop. Held there, it
    says so; with nothing active (the last step landed everything) it is nobody's."""
    before = engine.stats()
    seqs = _requests()
    engine.step(seqs)

    def the_batcher_stands_still():
        time.sleep(0.15)

    the_batcher_stands_still()
    _drive(engine, seqs)
    time.sleep(0.15)                        # nothing is active: no unit is open
    _drive(engine, _requests())
    after = engine.stats()
    host = _delta(after, before, "host")
    assert host["llm.step"]["n"] == 2 * STEPS and host["llm.between"]["n"] == 2 * (STEPS - 1)
    assert 1 <= _delta(after, before, "held")["n"] <= 3
    (record,) = [
        r for r in after["held_steps"] if r not in before["held_steps"] and r["unit"] == "llm.between"]
    assert record["where"] == "between"
    assert record["cause"] == "machine" and 0.1 < record["excess_s"] < 0.3
    assert record["stack"][-1].startswith("test_llm_spans.py:the_batcher_stands_still:")
    assert 0.15 < host["llm.between"]["wall_s"] < 0.3
    # and the steps' seconds are the phase's: one clock serves both
    assert host["llm.step"]["wall_s"] == pytest.approx(_delta(after, before, "phase_s")["step"])


@pytest.mark.limit(240)
def test_a_recorded_hold_is_a_span_and_an_idle_gap_under_it_is_filed_there(
        engine, monkeypatch, tmp_path):
    """Inside a real profiler session a planted collection and a planted sleep, each
    in a step of its own, and a third hold outside it: ``traced.held`` counts the
    two; the collection is a span ``host.gc`` inside ``llm.dispatch`` on the engine's
    thread, the sleep a span ``host.held`` from the moment the watcher saw it to the
    step's end, on the watcher's thread, which ``load_xplane`` merges with every
    python thread's; and the reducer files a device's idle gap under either as
    ``bench.engine_step:host.gc`` / ``:host.held``, not under the call they interrupted."""
    for _ in range(3):
        _drive(engine, _requests())
    real, calls, graph = engine._extend_call, [], []

    def held(*args, **kwargs):
        calls.append(None)
        if len(calls) == 2:                 # the first step's decode call
            graph.clear()
            gc.collect()
        if len(calls) in (4, 9):            # the second step's; and the fourth of the load after
            time.sleep(0.25)
        return real(*args, **kwargs)

    def steps_as_the_benchmark_runs_them(seqs):
        while not all(s.done for s in seqs):
            with accelerator.span("bench.engine_step"):
                engine.step([s for s in seqs if not s.done])

    monkeypatch.setattr(engine, "_extend_call", held)
    gc.collect()
    before = engine.stats()
    gc.disable()
    try:
        graph.extend(_cycles())
        with _session(tmp_path):
            steps_as_the_benchmark_runs_them(_requests())
        inside = engine.stats()
        steps_as_the_benchmark_runs_them(_requests())
    finally:
        gc.enable()
    after = engine.stats()
    # (the three planted, and whatever a loaded machine held beside them)
    assert 3 <= _delta(after, before, "held")["n"] <= 5
    traced = _delta(after["traced"], before["traced"], "held")
    assert traced == _delta(inside, before, "held") and 2 <= traced["n"] <= 4
    assert traced["gc"]["n"] == 1 <= traced["machine"]["n"]
    assert _delta(after["traced"], before["traced"], "host")["llm.step"]["n"] == STEPS

    path = _xplane_of(tmp_path)
    (collection,) = [g for g in _recorded(path, "host.gc") if g[1] - g[0] > 50e6]
    assert collection[2]["generation"] == 2 and collection[2]["collected"] >= 500_000
    # (the watcher gets to look at the collecting step when the collection lets go
    # of the interpreter lock: a short span of its own, cause ``gc``)
    seen = max(
        (h for h in _recorded(path, "host.held") if h[2]["cause"] == "machine"),
        key=lambda h: h[1] - h[0])
    assert seen[2]["unit"] == "llm.step"
    assert 0.1e9 < seen[1] - seen[0] < 0.25e9        # from 50-100 ms into the sleep on

    def inside_one(span, others):
        return any(a <= span[0] and span[1] <= b for a, b, *_ in others)

    dispatches, steps = _recorded(path, "llm.dispatch"), _recorded(path, "llm.step")
    assert inside_one(collection, dispatches) and inside_one(collection, steps)
    assert not inside_one(seen, dispatches)          # it ends with the step, after the call
    # on the threads they ran on: the collection on the engine's, the watcher's span on its own
    from jax.profiler import ProfileData

    host = next(p for p in ProfileData.from_file(path).planes if p.name == trace_reduce.HOST_PLANE)
    threads = [{e.name for e in line.events} for line in host.lines]
    (engines,) = [names for names in threads if "llm.step" in names]
    assert "host.gc" in engines and "host.held" not in engines

    # a device that is busy but for the middle third of either span
    planes = trace_reduce.load_xplane(path)
    events = _engine_line(planes)
    assert {"host.gc", "host.held", "bench.engine_step"} <= {n for n, _, _ in events}
    lo, hi = trace_reduce.annotation_window(planes, "bench.engine_step")
    holes = [(a + (b - a) / 3, a + 2 * (b - a) / 3) for a, b, _ in (collection, seen)]
    ops = [
        (f"fusion.{i}", a, b - a)
        for i, (a, b) in enumerate(trace_reduce.subtract([(lo, hi)], sorted(holes)))
    ]
    reduced = trace_reduce.reduce(
        {**planes, "/device:TPU:0": {trace_reduce.OPS_LINE: ops}}, "bench.engine_step")
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"bench.engine_step:host.gc", "bench.engine_step:host.held"}
    assert gaps["bench.engine_step:host.gc"] == pytest.approx((collection[1] - collection[0]) / 3e9)
    assert gaps["bench.engine_step:host.held"] == pytest.approx((seen[1] - seen[0]) / 3e9)


# -- (f) what the spans cost outside a session -------------------------------


class _NoWatch:
    """The parent's step in the watch's place: it opened its phase ``step`` through
    ``_phase`` (a generator round the span's place and two reads of the wall clock),
    which the unit ``llm.step`` has taken over, and had nothing between two steps."""

    def __init__(self):
        self._unit = types.SimpleNamespace(
            began=(0.0,), explained_s=0.0, wall_s=0.0, measured=(0.0, 0.0, 0.0, 0, 0), open=False)
        self._phase = None

    @contextlib.contextmanager
    def _the_parents_phase(self, unit):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            unit.wall_s = time.perf_counter() - t0

    def read(self, after=None):
        return (0.0,)

    def open(self, name, book=None, at=None, again=None):
        if name == "llm.step":
            self._phase = self._the_parents_phase(self._unit)
            self._phase.__enter__()
        return self._unit

    def close(self, unit, at=None, where=None):
        if self._phase is not None:
            phase, self._phase = self._phase, None
            phase.__exit__(None, None, None)

    def drop(self, unit):
        pass


@pytest.mark.limit(240)
def test_phases_cost_less_than_their_budget_outside_a_session(engine, monkeypatch):
    """1,000 engine steps with ``_phase`` as it is (a call's ``dispatch`` and
    ``fetch`` with their metadata) and the host's watch as it is (a step's two
    units, ``llm.step`` and ``llm.between``), and 1,000 with ``_phase`` swapped for a
    bare no-op, the step's ``recording()`` for a constant and the watch for the
    parent's phase ``step``, turn about, the device programs and the upload
    stubbed so that a step is the engine's own python: the difference per step
    stays under ``PHASE_BUDGET_NS`` for each phase the step opens and
    ``HOST_BUDGET_NS`` for its units. None of the watched steps is held, and
    nothing of them was sampled."""
    class Home(np.ndarray):
        """What a call leaves for the host, already there."""

        def is_ready(self):
            return True

    ids = {b: np.zeros((b,), np.int32).view(Home) for b in engine.lane_buckets}
    monkeypatch.setattr(engine, "_extend_call", lambda *args, tc: (None,) * 4)
    monkeypatch.setattr(engine.pool, "gather", lambda operands, n: (None, None))
    monkeypatch.setattr(
        engine.pool, "page_back",
        lambda news, operands, logits, counted, width: ids[width])
    monkeypatch.setattr(jax, "device_put", lambda a: a)
    as_it_is, nothing = engine._phase, contextlib.nullcontext()
    asks, never = llm.accelerator.recording, lambda: False
    watch, no_watch = engine._watch, _NoWatch()

    def thousand_steps(phase, recording=asks, watch=watch):
        engine._phase, engine._watch = phase, watch
        monkeypatch.setattr(llm.accelerator, "recording", recording)
        # this thread's own time: a stubbed step waits for nothing, and the
        # other workers of a loaded machine are not the spans' cost
        steps0, t0, seqs = engine.steps, time.thread_time_ns(), []
        while engine.steps - steps0 < 1000:
            seqs = [s for s in seqs if not s.done] or _requests((9, 9), 60)
            engine.step(seqs)
        spent = time.thread_time_ns() - t0
        for s in seqs:
            s._release()                    # the unfinished give their blocks back
        # nothing is in flight (the stubbed call lands nowhere): nobody waits for a step
        engine._watch.drop(engine._between)
        engine._flight = engine._between = engine._unit = None
        return spent

    try:
        thousand_steps(as_it_is)
        before = engine.stats()
        runs = [
            (thousand_steps(as_it_is),
             thousand_steps(lambda name, **what: nothing, never, no_watch))
            for _ in range(5)
        ]
    finally:
        del engine._phase
        engine._watch = watch
    after = engine.stats()
    # (the phase ``step`` counts in the other five thousand steps too)
    opened = (sum(_delta(after, before, "phase_n").values()) - 5000) / 5000
    assert 8 <= opened <= 30
    with_phases, without = (min(r[i] for r in runs) for i in (0, 1))
    assert (with_phases - without) / 1000 < opened * llm.PHASE_BUDGET_NS + llm.HOST_BUDGET_NS
    # 5,000 watched steps, every one between two others: none held, none sampled
    assert _delta(after, before, "host")["llm.step"]["n"] == 5000 == _delta(after, before, "steps") - 5000
    # (a step that lands its last call leaves nothing to wait for the next)
    assert 4500 < _delta(after, before, "host")["llm.between"]["n"] < 5000
    # (a loaded machine takes a CPU away for 20 ms now and then: the watch's to say)
    held = _delta(after, before, "held")
    assert held["n"] == held["machine"]["n"] <= 3


# -- (g) stable device names --------------------------------------------------


def test_train_step_names_its_kernels_and_scopes(built_for_tpu):
    built_for_tpu(True)
    cfg = dataclasses.replace(NANO, num_heads=1, head_dim=64)
    optimizer = default_optimizer()
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    _, state = abstract_state(cfg, optimizer, tokens)
    step = make_train_step(cfg, optimizer, donate=False)
    text = jax.make_jaxpr(step)(nn.meta.unbox(state), tokens).pretty_print(name_stack=True)
    for name in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
        "train.forward", "train.loss", "train.optimizer",
    ):
        assert name in text, name
    # the backward pass runs under the forward's scope
    assert "transpose(jvp(train.forward))" in text


def test_extend_names_its_scopes():
    params = jax.eval_shape(lambda: llm.make_params(NANO))
    kv = jax.ShapeDtypeStruct((NANO.num_layers, 2, 64, NANO.num_heads, NANO.head_dim), jnp.float32)
    text = gpt.make_extend_fn(NANO).lower(
        params, jax.ShapeDtypeStruct((2, 8), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), kv, kv,
    ).as_text(debug_info=True)
    for scope in ("extend.embed", "extend.attention", "extend.mlp", "extend.logits"):
        assert f"{scope}/" in text, scope


@pytest.mark.parametrize("tc", [1, 8], ids=["decode", "prefill"])
def test_an_indexers_extend_names_its_scopes_inside_attentions(tc):
    """Both forms of the selection (a decode lane's row gather, a prefill
    chunk's mask) run under ``extend.attention.select``, the indexer under
    ``extend.attention.index``, both nested in ``extend.attention``: the
    benchmark's readers tell the three apart by the innermost name."""
    from ray_tpu.models import keye_vl2

    cfg = keye_vl2.keye_vl2_nano()
    params = jax.eval_shape(lambda: cfg.init_params(0))
    caches = [
        jax.ShapeDtypeStruct((cfg.num_layers, 2, 64) + tuple(each), jnp.float32)
        for each in cfg.cache_arrays]
    text = cfg.make_extend_fn().lower(
        params, jax.ShapeDtypeStruct((2, tc), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), *caches,
    ).as_text(debug_info=True)
    for scope in (
        "extend.embed", "extend.attention", "extend.attention/extend.attention.index",
        "extend.attention/extend.attention.select", "extend.moe.route", "extend.moe.experts",
        "extend.logits",
    ):
        assert f"{scope}/" in text, scope


@pytest.mark.parametrize("tc", [1, 8], ids=["decode", "prefill"])
def test_a_latent_attentions_extend_names_its_scopes(tc):
    """Both forms of call run the down-projections, the rotations, the
    absorption and the un-absorption under ``extend.attention.latent``, nested
    in ``extend.attention`` (cache updates, the attend, ``W_o``); the dense
    layer's MLP under ``extend.mlp``, the expert layers' three parts under
    ``extend.moe.*``: the benchmark's readers tell them apart by the innermost."""
    from ray_tpu.models import kimi_k2

    cfg = kimi_k2.kimi_k2_nano()
    params = jax.eval_shape(lambda: cfg.init_params(0))
    caches = [
        jax.ShapeDtypeStruct((cfg.num_layers, 2, 64) + tuple(each), jnp.float32)
        for each in cfg.cache_arrays]
    text = cfg.make_extend_fn().lower(
        params, jax.ShapeDtypeStruct((2, tc), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), *caches,
    ).as_text(debug_info=True)
    for scope in (
        "extend.embed", "extend.attention", "extend.attention/extend.attention.latent",
        "extend.mlp", "extend.moe.route", "extend.moe.experts", "extend.moe.shared",
        "extend.logits",
    ):
        assert f"{scope}/" in text, scope
    # the scores and the weighted sum of latents stand straight under extend.attention
    assert any(
        "extend.attention/" in line and "extend.attention.latent" not in line
        for line in text.splitlines() if "dot_general" in line)


def test_the_engine_sums_what_extend_counts_under_the_configurations_names():
    """The engine knows no counter of a model: it sums the int32 vector behind
    ``extend``'s new rows under ``cfg.counters``, and what ``cfg.count_gathered``
    gives for a call's padded caches; a model that counts nothing adds no key."""
    import dataclasses as dc

    from ray_tpu.models import keye_vl2
    from ray_tpu.serve import batching

    sizes = dict(
        num_blocks=16, block_size=16, prefill_chunk=32, lane_buckets=(1,),
        prefill_token_buckets=(32,), cache_buckets=(64,))
    cfg = keye_vl2.keye_vl2_nano()
    renamed = type("Renamed", (keye_vl2.KeyeVL2Config,), {
        "counters": tuple("x_" + n for n in cfg.counters),
        "count_gathered": lambda self, lanes, cache: {"x_gathered": lanes * cache},
    })(**dc.asdict(cfg))
    got = {}
    for c in (cfg, renamed):
        eng = llm.LLMEngine(c, **sizes)
        seq = batching._Sequence({"prompt": list(range(1, 41)), "max_new_tokens": 3})
        while not seq.done:
            eng.step([seq])
        got[type(c).__name__] = eng.stats()
    plain, other = got["KeyeVL2Config"], got["Renamed"]
    assert set(cfg.counters) | {"sparse_slots_gathered"} <= set(plain)
    assert not set(plain) & set(renamed.counters)
    assert [other[n] for n in renamed.counters] == [plain[n] for n in cfg.counters]
    # 40 prompt tokens in chunks of 32 + 8, then two decode calls; three layers
    assert plain["sparse_queries"] == plain["moe_tokens"] == 3 * 42
    assert plain["sparse_slots_gathered"] == 3 * other["x_gathered"] == 3 * 4 * 64
    assert not any(k.startswith(("moe_", "sparse_")) for k in llm.LLMEngine(NANO, **sizes).stats())
