"""The seven readers of the engine's record per device call and of its counters
over recorded steps (``kv_stats()["calls"]`` and ``["traced"]``): each on a
hand-made run, and all of them on the tiny serve cell, traced, through the
benchmark's own ``_step``.

``ENTRIES`` are their ``per_layer`` entries. They are **not in**
``BENCHMARK.json`` **yet**: ``contract.emit`` prints no line that lacks a listed
metric, so a traced run of a program older than the counters (the parent of the
PR that brought them) would exit 3 in the four serve cells. Any later PR, whose
parent has the counters, adds them as they stand here; until then they are
laid over a copy of the manifest, the way such a PR would."""

import pytest

import bench_helpers
from benchmark import chip, contract, manifest, run as run_mod, yardstick

SERVE_CELLS = [
    "gptj-serve-chat-steady", "cmd-a-plus-serve-mixed-lengths",
    "keye-vl2-serve-long-context", "kimi-k2-serve-long-context",
]
ENTRIES = [
    {
        "name": name, "unit": unit, "better": better, "source": "program_counter",
        "layer": "serve engine", "moves": "request_latency_mean_s",
        "workloads": list(SERVE_CELLS),
    }
    for name, unit, better in (
        ("engine.traced_step_ms", "ms", "lower"),
        ("engine.host_share", "%", "lower"),
        ("engine.prefill_call_ms", "ms", "lower"),
        ("engine.decode_call_ms", "ms", "lower"),
        ("engine.prefill_token_fill", "%", "higher"),
        ("engine.decode_lane_fill", "%", "higher"),
        ("engine.decode_cache_fill", "%", "higher"),
    )
]

# two prefill calls of 2 x 32 and 2 x 16 token slots and three decode calls, 40
# steps of which 8 were recorded: every reader's number by hand
COUNTERS = {
    "steps": 40, "phase_s": {"step": 2.0, "fetch": 1.5},
    "calls": {
        "prefill": {
            "n": 2, "lanes_used": 4, "lane_slots": 4, "tokens": 69, "token_slots": 96,
            "cache_tokens": 32, "cache_slots": 256, "busy_s": 0.05,
        },
        "decode": {
            "n": 3, "lanes_used": 6, "lane_slots": 7, "tokens": 6, "token_slots": 7,
            "cache_tokens": 141, "cache_slots": 448, "busy_s": 0.018,
        },
    },
    "traced": {"steps": 8, "phase_s": {"step": 0.5, "fetch": 0.4}},
}
# the reader's number, and the one count without which it has nothing to divide by
BY_HAND = {
    "engine.traced_step_ms": (62.5, ("traced", "steps")),
    "engine.host_share": (20.0, ("traced", "phase_s", "step")),
    "engine.prefill_call_ms": (25.0, ("calls", "prefill", "n")),
    "engine.decode_call_ms": (6.0, ("calls", "decode", "n")),
    "engine.prefill_token_fill": (100 * 69 / 96, ("calls", "prefill", "token_slots")),
    "engine.decode_lane_fill": (100 * 6 / 7, ("calls", "decode", "lane_slots")),
    "engine.decode_cache_fill": (100 * 141 / 448, ("calls", "decode", "cache_slots")),
}


def _without(counters, path, zero=False):
    """``counters`` with the key at ``path`` taken away, or at zero."""
    head, *rest = path
    out = dict(counters)
    if rest:
        out[head] = _without(counters[head], rest, zero)
    elif zero:
        out[head] = 0
    else:
        del out[head]
    return out


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_a_reader_on_a_hand_made_run(entry):
    read = manifest.Manifest(bench_helpers.REPO).reader(entry["name"])
    value, path = BY_HAND[entry["name"]]
    assert read({"counters": COUNTERS}) == pytest.approx(value)
    assert not 100 < value and (entry["unit"] != "%" or 0 < value)
    # a program without the group (this PR's parent), or a load without such a call
    assert read({"counters": _without(COUNTERS, path[:1])}) is None
    assert read({"counters": _without(COUNTERS, path, zero=True)}) is None
    assert read({"counters": None}) is None and read({}) is None


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A copy of the benchmark with the entries appended and the tiny cells
    beside the cells they mirror, for a run on the CPU."""
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.edit_manifest(root, lambda book: book["per_layer"].extend(ENTRIES))
    bench_helpers.add_tiny_cells(root)
    return root


def test_the_entries_fit_the_manifest_and_a_traced_tiny_cell_reports_all_seven(root):
    book = manifest.Manifest(root)
    bench_helpers.check_manifest(book)
    for name in SERVE_CELLS:
        bench_helpers.check_cell(book, name)
    assert not {e["name"] for e in ENTRIES} & {
        m["name"] for m in manifest.Manifest(bench_helpers.REPO).data["per_layer"]}

    line, cell, run = run_mod.run_cell(root, "tiny-serve-cell", 2**31 + 11, 1.5, True)
    assert line["correct"] and line["failed"] == 0
    got = {e["name"]: line["metrics"][e["name"]]["value"] for e in ENTRIES}
    counters, engine = run["counters"], run["trace"]["engine"]
    # the recorded steps are exactly the units the benchmark's ``_step`` wrapped
    assert counters["traced"]["steps"] == engine["steps"] > 0
    assert counters["traced"]["steps"] < counters["steps"]
    # the program's clock inside the benchmark's, around the same steps
    assert 0 < got["engine.traced_step_ms"] <= line["metrics"]["engine.step_ms"]["value"]
    assert got["engine.traced_step_ms"] == pytest.approx(
        line["metrics"]["engine.step_ms"]["value"], rel=0.25)
    assert 0 < got["engine.host_share"] < 100
    for fill in ("engine.prefill_token_fill", "engine.decode_lane_fill", "engine.decode_cache_fill"):
        assert 0 < got[fill] <= 100, fill
    assert got["engine.prefill_call_ms"] > 0 and got["engine.decode_call_ms"] > 0
    calls = counters["calls"]
    for total in ("lanes_used", "lane_slots", "cache_tokens", "cache_slots"):
        assert counters[total] == calls["prefill"][total] + calls["decode"][total]
    assert (counters["prefill_tokens"], counters["decode_tokens"]) == (
        calls["prefill"]["tokens"], calls["decode"]["tokens"])
    # a profiler that starts and stops between two steps holds the engine, not the device
    busy = calls["prefill"]["busy_s"] + calls["decode"]["busy_s"]
    assert 0 < busy < 1.25 * counters["phase_s"]["step"]


def test_why_the_entries_wait_for_a_parent_that_has_the_counters():
    """A listed metric whose reader finds nothing is a contract violation: no
    last line, exit 3. That is what a program without ``calls`` and ``traced``
    would give in every traced run of a cell that lists these."""
    wanted = ENTRIES[:1]
    line = contract.build(
        correct=True, attempted=1, failed=0, values={wanted[0]["name"]: None},
        wanted=wanted, device={
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1,
            "busy_s": 1.0, "window_s": 2.0})
    assert contract.violations(line, wanted, True) == [
        "metric 'engine.traced_step_ms' is missing"]
    with pytest.raises(contract.ContractViolation):
        contract.emit(line, wanted, True)
