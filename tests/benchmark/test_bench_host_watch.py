"""The five readers that came with the host's watch (``accelerator.HostWatch``:
what an engine step and the time between two steps cost the engine's thread,
and the steps that stood still, by cause), each on a hand-made run, and on the
tiny serve cell, traced.

``engine.queue_p95_s`` reads ``records[].queue_s``, which the parent's runs hold
too: it is an entry of ``BENCHMARK.json`` for the seven serve cells whose tests
leave their set of metrics open. The other four read counters the parent of
their PR lacks (``held``, ``host``, ``gc``), and ``contract.emit`` prints no line
that lacks a listed metric, so they are files with their ``ENTRIES`` here, laid
over a copy of the manifest as ``test_bench_engine_calls.py`` and
``test_bench_program_names.py`` lay theirs, for a ``benchmark`` PR to append."""

import pytest

import bench_helpers
from benchmark import chip, manifest, run as run_mod, yardstick

pytestmark = pytest.mark.limit(300)

OPEN_CELLS = [
    "gptj-serve-chat-steady", "cmd-a-plus-serve-mixed-lengths",
    "keye-vl2-serve-long-context", "kimi-k2-serve-long-context",
    "mimo-v2-flash-serve-reasoning-turns", "qwen3-next-serve-concurrent-turns",
    "glm-5-serve-document-questions",
]
PINNED_CELLS = [
    "granite-4h-micro-serve-chat-tool-turns", "granite-4h-small-serve-agent-bursts",
    "minicpm-sala-serve-long-documents", "longcat-flash-serve-agent-turns",
]
ENTRIES = [
    {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": "serve engine", "moves": "request_latency_mean_s",
        "workloads": OPEN_CELLS + PINNED_CELLS,
    }
    for name, unit in (
        ("engine.held_share", "%"), ("engine.off_cpu_share", "%"),
        ("engine.gc_ms_per_step", "ms"), ("engine.between_ms", "ms"),
    )
]
QUEUE = {
    "name": "engine.queue_p95_s", "unit": "s", "better": "lower", "source": "program_counter",
    "layer": "serve engine", "moves": "request_latency_mean_s", "workloads": OPEN_CELLS,
}

# 40 steps of 2.0 s, 1.5 s of it waiting for the device, the engine's thread on a
# CPU for 0.4 s of the rest; 0.5 s between steps; two steps held 0.25 s together,
# and 60 ms of collections
COUNTERS = {
    "steps": 40, "phase_s": {"step": 2.0, "fetch": 1.5},
    "host": {
        "llm.step": {"n": 40, "wall_s": 2.0, "cpu_s": 0.4, "others_cpu_s": 0.1, "gc_s": 0.05,
                     "switched": 3, "faults": 0},
        "llm.between": {"n": 39, "wall_s": 0.5, "cpu_s": 0.02, "others_cpu_s": 0.0, "gc_s": 0.01,
                        "switched": 0, "faults": 0},
    },
    "held": {"n": 2, "excess_s": 0.25, "gc": {"n": 0, "s": 0.0}, "python": {"n": 0, "s": 0.0},
             "threads": {"n": 1, "s": 0.1}, "machine": {"n": 1, "s": 0.15}},
    "gc": {"n": 12, "s": 0.06, "longest_s": 0.03},
}
# the reader's number, the group a parent lacks, and the count it divides by
BY_HAND = {
    "engine.held_share": (100 * 0.25 / 2.5, "held", ("phase_s", "step")),
    "engine.off_cpu_share": (100 * (1 - 0.4 / 0.5), "host", ("phase_s", "step")),
    "engine.gc_ms_per_step": (1e3 * 0.06 / 40, "gc", ("steps",)),
    "engine.between_ms": (1e3 * 0.5 / 40, "host", ("steps",)),
}


def _reader(name):
    return manifest.Manifest(bench_helpers.REPO).reader(name)


def _without(counters, path, zero=False):
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
def test_a_watch_reader_on_a_hand_made_run(entry):
    read = _reader(entry["name"])
    value, group, divisor = BY_HAND[entry["name"]]
    assert read({"counters": COUNTERS}) == pytest.approx(value)
    # a program without the group (this PR's parent); an engine that ran no step
    assert read({"counters": _without(COUNTERS, (group,))}) is None
    assert read({"counters": _without(COUNTERS, divisor, zero=True)}) is None
    assert read({"counters": None}) is None and read({}) is None
    # nothing held, nothing collected: a number, not nothing
    quiet = {**COUNTERS, "held": {**COUNTERS["held"], "excess_s": 0.0}, "gc": {"n": 0, "s": 0.0}}
    if group != "host":
        assert read({"counters": quiet}) == 0.0


def test_queue_p95_on_hand_made_runs():
    read = _reader("engine.queue_p95_s")

    def run(records):
        return {"kind": "serve", "records": records, "drain_limit_s": 60.0}

    waits = [0.001 * i for i in range(1, 21)]
    records = [{"ok": True, "queue_s": w} for w in waits]
    assert read(run(records)) == pytest.approx(0.019)               # the 19th of 20
    # a failed request has no admission to count: left out, whatever it holds
    failed = {"ok": False, "error": "shed", "queue_s": 9.0}
    assert read(run(records + [failed, {"ok": False}])) == pytest.approx(0.019)
    # a record without ``queue_s`` (an engine older than PR 24), and one with None
    assert read(run(records[:3] + [{"ok": True}, {"ok": True, "queue_s": None}])) == pytest.approx(0.003)
    assert read(run([{"ok": True, "queue_s": 0.25}])) == 0.25        # one request
    assert read(run([{"ok": True}])) is None and read(run([failed])) is None
    assert read(run([])) is None and read({"kind": "train", "records": records}) is None
    assert read({}) is None


def test_queue_p95_is_listed_for_the_cells_whose_tests_leave_their_set_open():
    book = manifest.Manifest(bench_helpers.REPO)
    assert book.data["per_layer"][-1] == QUEUE                       # appended, nothing moved
    for name in OPEN_CELLS:
        assert "engine.queue_p95_s" in {m["name"] for m in book.cell(name).per_layer}
        bench_helpers.check_cell(book, name)
    # ``==`` on their sets of metrics, in files that are the benchmark's
    for name in PINNED_CELLS:
        assert "engine.queue_p95_s" not in {m["name"] for m in book.cell(name).per_layer}
    serve = {w["name"] for w in book.data["workloads"] if "serve" in w["name"]}
    assert serve == set(OPEN_CELLS + PINNED_CELLS)
    assert not {e["name"] for e in ENTRIES} & {m["name"] for m in book.data["per_layer"]}


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A copy of the benchmark with the four entries appended and the tiny cells
    beside the cells they mirror, for a run on the CPU."""
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.edit_manifest(root, lambda book: book["per_layer"].extend(ENTRIES))
    bench_helpers.add_tiny_cells(root)
    return root


def test_the_watch_entries_fit_the_manifest_and_a_traced_tiny_cell_reports_all_five(root):
    book = manifest.Manifest(root)
    bench_helpers.check_manifest(book)
    for name in OPEN_CELLS + PINNED_CELLS:
        bench_helpers.check_cell(book, name)
        assert {e["name"] for e in ENTRIES} <= {m["name"] for m in book.cell(name).per_layer}

    line, cell, run = run_mod.run_cell(root, "tiny-serve-cell", 2**31 + 17, 1.5, True)
    assert line["correct"] and line["failed"] == 0
    got = {name: line["metrics"][name]["value"] for name in [e["name"] for e in ENTRIES] + [QUEUE["name"]]}
    counters = run["counters"]
    # the groups arrive through ``counter_deltas`` with no edit to the generator: numbers
    # within groups within groups, the ring (a list) left out
    host, held = counters["host"], counters["held"]
    assert set(host) == {"llm.step", "llm.between"} and "held_steps" not in counters
    assert host["llm.step"]["n"] == counters["steps"] > host["llm.between"]["n"] > 0
    assert host["llm.step"]["wall_s"] == pytest.approx(counters["phase_s"]["step"])
    assert set(held) == {"n", "excess_s", "gc", "python", "threads", "machine"}
    assert held["n"] == sum(held[cause]["n"] for cause in ("gc", "python", "threads", "machine"))
    assert held["excess_s"] == pytest.approx(
        sum(held[cause]["s"] for cause in ("gc", "python", "threads", "machine")))
    assert counters["gc"]["n"] > 0 and set(counters["gc"]["generations"]) == {"0", "1", "2"}
    # and ``traced`` keeps them over the recorded steps
    assert 0 < counters["traced"]["host"]["llm.step"]["n"] == counters["traced"]["steps"] < counters["steps"]
    assert counters["traced"]["held"]["n"] <= held["n"]
    # the readers' numbers, from the same counters
    loaded_s = counters["phase_s"]["step"] + host["llm.between"]["wall_s"]
    assert got["engine.held_share"] == pytest.approx(100 * held["excess_s"] / loaded_s)
    assert 0 <= got["engine.held_share"] < 100
    assert got["engine.off_cpu_share"] < 100
    assert got["engine.gc_ms_per_step"] == pytest.approx(1e3 * counters["gc"]["s"] / counters["steps"])
    assert 0 < got["engine.between_ms"] == pytest.approx(
        1e3 * host["llm.between"]["wall_s"] / counters["steps"])
    waits = sorted(r["queue_s"] for r in run["records"])
    assert 0 <= waits[0] and got["engine.queue_p95_s"] == yardstick.percentile(waits, 0.95)
    assert all(0 <= r["queue_s"] <= r["ttft_s"] for r in run["records"])
