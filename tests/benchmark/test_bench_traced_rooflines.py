"""The four serve rooflines that were estimates until PR 49
(``moe.experts_roofline``, ``kimi_k2.experts_roofline``, ``mla.attend_roofline``,
``sparse_attention.roofline``) read ``counters.traced``: what the program counted
in exactly the engine steps the profiler session recorded, with no scale from
the whole load. Each on a hand-made run whose recorded steps are a prefill-heavy
sub-window (more chunks than the load's mix has), so the traced counts are not
the load's times any share of its time."""

import copy

import pytest

import bench_helpers
from benchmark import manifest

BOOK = manifest.Manifest(bench_helpers.REPO)
FLOPS, BYTES = 197e12, 819e9          # one v5e chip's peaks (yardstick.PEAKS)

# the whole load: 20 s inside engine steps, mostly decode calls
LOAD = {
    "steps": 4_000, "phase_s": {"step": 20.0}, "phase_n": {"dispatch": 4_000},
    "cache_tokens": 10_000_000,
    "moe_tokens": 3_000_000, "moe_assignments": 700_000, "moe_experts_hit": 10_000,
    "mla_pairs_absorbed": 8_000_000_000, "mla_pairs_expanded": 0,
    "sparse_keys_scored": 8_000_000_000, "sparse_keys_attended": 1_000_000_000,
    "sparse_slots_read": 30_000_000,
}
# the recorded steps: 2.5 s of them (an eighth), three chunks in four calls
TRACED = {
    "steps": 200, "phase_s": {"step": 2.5}, "phase_n": {"dispatch": 200},
    "cache_tokens": 1_500_000,
    "moe_tokens": 100_000, "moe_assignments": 90_000, "moe_experts_hit": 1_500,
    "mla_pairs_absorbed": 1_000_000_000, "mla_pairs_expanded": 100_000_000,
    "sparse_keys_scored": 1_200_000_000, "sparse_keys_attended": 200_000_000,
    "sparse_slots_read": 4_000_000,
}
SCOPES = [
    ["extend.attention", 1.0], ["extend.attention.index", 0.2], ["extend.attention.select", 0.3],
    ["extend.attention.latent", 0.3], ["extend.moe.experts", 0.8], ["extend.moe.shared", 0.2],
    ["extend.moe.route", 0.05], ["(no scope)", 0.2],
]

# by hand, from the traced counts and each configuration's published sizes
COMMAND_A = 3 * 4096 * 4096                 # an expert: gate, up and down of 4096 x 4096
KIMI = 3 * 7168 * 2048                      # an expert: gate, up and down of 7168 x 2048
BY_HAND = {
    # 4 shared experts see every token; they and the hit experts are read once a call and
    # layer (4 layers): the weights bind; over 0.8 + 0.2 s
    "moe.experts_roofline": 100 * max(
        2 * COMMAND_A * (90_000 + 4 * 100_000) / FLOPS,
        2 * COMMAND_A * (1_500 + 4 * 4 * 200) / BYTES) / 1.0,
    # one shared expert, 6 expert layers
    "kimi_k2.experts_roofline": 100 * max(
        2 * KIMI * (90_000 + 100_000) / FLOPS,
        2 * KIMI * (1_500 + 6 * 200) / BYTES) / 1.0,
    # 64 heads x (576 scored + 512 summed) a pair absorbed, (192 + 128) expanded; a row of
    # 576 bfloat16 a live slot and layer (7); over extend.attention's 1.0 s
    "mla.attend_roofline": 100 * max(
        (2 * 64 * (576 + 512) * 1e9 + 2 * 64 * (192 + 128) * 1e8) / FLOPS,
        2 * 576 * 7 * 1.5e6 / BYTES) / 1.0,
    # 16 indexer heads x 64 a pair scored, 32 heads x 128 x 2 a key attended; an indexer key
    # of 64 bfloat16 a live slot and layer (6), K and V of 4 x 128 a slot read; over 1.5 s
    "sparse_attention.roofline": 100 * max(
        (2 * 16 * 64 * 1.2e9 + 4 * 32 * 128 * 2e8) / FLOPS,
        2 * (64 * 6 * 1.5e6 + 2 * 4 * 128 * 4e6) / BYTES) / 1.5,
}
READERS = list(BY_HAND)


def recorded_run():
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {**copy.deepcopy(LOAD), "traced": copy.deepcopy(TRACED)},
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 200, "in_step_s": 2.5},
            "ops_by_scope": copy.deepcopy(SCOPES),
        },
    }


@pytest.mark.parametrize("name", READERS)
def test_a_roofline_follows_the_recorded_steps_own_counts(name):
    got = BOOK.reader(name)(recorded_run())
    assert got == pytest.approx(BY_HAND[name]) and 0 < got < 100
    # the load's counts times the traced steps' share of its step time (the estimate these
    # readers made until PR 49) is another number on this run: the mixes differ
    run = recorded_run()
    run["counters"]["traced"] = {
        k: {kk: vv / 8 for kk, vv in v.items()} if isinstance(v, dict) else v / 8
        for k, v in LOAD.items()}
    assert BOOK.reader(name)(run) != pytest.approx(BY_HAND[name], rel=0.02)


@pytest.mark.parametrize("name", READERS)
def test_a_roofline_takes_no_scale_from_the_loads_step_time(name):
    read, run = BOOK.reader(name), recorded_run()
    want = read(run)
    run["trace"]["engine"]["in_step_s"] *= 2
    assert read(run) == want
    run["counters"]["phase_s"]["step"] *= 2
    assert read(run) == want
    run["counters"]["traced"]["phase_s"]["step"] *= 2
    assert read(run) == want
    del run["trace"]["engine"], run["counters"]["phase_s"]
    assert read(run) == want
    # the whole load's counts do not enter either
    for k in ("moe_assignments", "moe_experts_hit", "mla_pairs_absorbed", "sparse_keys_scored",
              "cache_tokens"):
        run["counters"][k] *= 3
    run["counters"]["phase_n"]["dispatch"] *= 3
    assert read(run) == want


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_keeps_no_traced_record_gives_a_roofline_nothing(name):
    read, run = BOOK.reader(name), recorded_run()
    del run["counters"]["traced"]
    assert read(run) is None
    assert read({**recorded_run(), "counters": {**LOAD, "traced": {}}}) is None
    # a session that recorded no step: the record is there and counts nothing
    empty = {k: ({kk: 0 for kk in v} if isinstance(v, dict) else 0) for k, v in TRACED.items()}
    assert read({**recorded_run(), "counters": {**LOAD, "traced": empty}}) is None
    assert read({**recorded_run(), "trace": None}) is None


@pytest.mark.parametrize("name", ["moe.experts_roofline", "kimi_k2.experts_roofline"])
def test_the_calls_that_read_the_shared_experts_are_the_recorded_steps(name):
    """``phase_n.dispatch`` enters the bytes (the shared experts' reads a call):
    the traced one. On these counts the weights bind, so twice the recorded
    calls move the reading and the load's calls do not."""
    read, run = BOOK.reader(name), recorded_run()
    want = read(run)
    run["counters"]["phase_n"]["dispatch"] *= 2
    assert read(run) == want
    run["counters"]["traced"]["phase_n"]["dispatch"] *= 2
    assert read(run) > 1.3 * want
