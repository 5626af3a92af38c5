"""Why every program of the serving engine has a module name of its own, on a
hand-made trace reduced by ``trace_reduce.reduce`` as it stands, and the three
readers that came with the names.

``trace_reduce.load_scopes`` keeps one table of ``instruction -> op_name`` a
module **name** and merges the tables of same-named modules with
``dict.update``; ``reduce`` looks an instruction up in the table of the module
that ran it. An instruction's name is unique in its program only. While the
engine ran every shape of ``extend`` as ``jit_extend_call``, the table merged
last named every other program's ``fusion.N``: Kimi's expert roofline read
72.8 to 108.3 % on one tree (``PERF.md`` section 7, S7b (23)). The first test
keeps that case as the reason; the program's side of the repair is
``ray_tpu/_private/accelerator.py`` ``Programs`` (``tests/test_llm_spans.py``).

``extend.unscoped_share`` reads what a traced run of any program holds and is
an entry of ``BENCHMARK.json`` for the four serve cells whose tests leave
their set of metrics open. ``engine.cold_programs`` and
``engine.cold_compile_s`` read counters the parent of their PR lacks, so they
are files with their ``ENTRIES`` here, laid over a copy of the manifest as
``test_bench_engine_calls.py`` lays its seven, for a ``benchmark`` PR to append."""

import pytest

import bench_helpers
from benchmark import chip, manifest, run as run_mod, trace_reduce as tr, yardstick

STEP = "bench.engine_step"
OPEN_CELLS = [
    "gptj-serve-chat-steady", "cmd-a-plus-serve-mixed-lengths",
    "keye-vl2-serve-long-context", "kimi-k2-serve-long-context",
]
PINNED_CELLS = [
    "granite-4h-micro-serve-chat-tool-turns", "granite-4h-small-serve-agent-bursts",
    "minicpm-sala-serve-long-documents",
]
ENTRIES = [
    {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": "serve engine", "moves": "request_latency_mean_s",
        "workloads": OPEN_CELLS + PINNED_CELLS,
    }
    for name, unit in (("engine.cold_programs", "count"), ("engine.cold_compile_s", "s"))
]

# -- two programs that both hold a ``fusion.1`` --------------------------------

# a decode call's shared expert (0.3 us) and a chunk's attend (0.5 us); each
# program also has an instruction only it holds
DECODE = {"fusion.1": "jit({0})/jit(extend)/extend.moe.shared/dot_general",
          "fusion.7": "jit({0})/jit(extend)/extend.moe.route/reduce"}
PREFILL = {"fusion.1": "jit({0})/jit(extend)/extend.attention/dot_general",
           "fusion.9": "jit({0})/jit(extend)/while/body/dynamic_update_slice"}


def _reduced(decode: str, prefill: str):
    """A decode call then a prefill call, as the profiler lays them out, with
    the scopes merged table by table as ``load_scopes`` merges them."""
    planes = {
        "/device:TPU:0": {
            tr.MODULES_LINE: [(f"jit_{decode}(11)", 100, 400), (f"jit_{prefill}(12)", 600, 700)],
            tr.OPS_LINE: [
                ("fusion.1", 100, 300), ("fusion.7", 400, 100),
                ("fusion.1", 600, 500), ("fusion.9", 1100, 200),
            ],
        },
        tr.HOST_PLANE: {"python": [(STEP, 0, 1500)]},
    }
    scopes = {}
    for module, names in ((decode, DECODE), (prefill, PREFILL)):
        scopes.setdefault("jit_" + module, {}).update(
            {instruction: op.format(module) for instruction, op in names.items()})
    reduced = tr.reduce(planes, STEP, scopes=scopes)
    assert reduced["busy_s"] == pytest.approx(1100e-9)
    return {scope: seconds * 1e9 for scope, seconds in reduced["ops_by_scope"]}, reduced


def test_under_one_module_name_a_programs_seconds_are_filed_under_anothers_scope():
    filed, _ = _reduced("extend_call", "extend_call")
    # the chunk's table was merged last: the decode call's shared expert is "attention"
    assert filed == pytest.approx({
        "extend.attention": 300 + 500, "extend.moe.route": 100, tr.NO_SCOPE: 200})
    assert "extend.moe.shared" not in filed


def test_under_a_name_of_its_own_each_programs_seconds_land_in_its_scope():
    filed, reduced = _reduced("extend_decode_8x1x8192", "extend_prefill_1x512x8192")
    assert filed == pytest.approx({
        "extend.attention": 500, "extend.moe.shared": 300, "extend.moe.route": 100,
        tr.NO_SCOPE: 200})
    # and the name itself is no scope: an op_name's first component has no dot
    assert tr.scope_of("jit(extend_decode_8x1x8192)/jit(extend)/mul") == tr.NO_SCOPE
    read = manifest.Manifest(bench_helpers.REPO).reader("extend.unscoped_share")
    assert read({"trace": reduced}) == pytest.approx(100 * 200 / 1100)


# -- the three readers ----------------------------------------------------------


def _reader(name):
    return manifest.Manifest(bench_helpers.REPO).reader(name)


def test_unscoped_share_on_hand_made_runs():
    read = _reader("extend.unscoped_share")
    trace = {"busy_s": 1.10, "ops_by_scope": [["extend.mlp", 0.5], ["(no scope)", 0.059]]}
    assert read({"trace": trace}) == pytest.approx(100 * 0.059 / 1.10)
    # scopes were filed and every second is inside one: a number, not nothing
    assert read({"trace": {"busy_s": 2.0, "ops_by_scope": [["extend.mlp", 2.0]]}}) == 0.0
    # no device plane (a run on the CPU), no trace, an untraced run
    assert read({"trace": {"engine": {"steps": 3}}}) is None
    assert read({"trace": {"busy_s": 0.0, "ops_by_scope": [["(no scope)", 0.0]]}}) is None
    assert read({"trace": None}) is None and read({}) is None


@pytest.mark.parametrize("entry, key, value", [
    (ENTRIES[0], "programs_cold", 2), (ENTRIES[1], "programs_cold_s", 3.26)],
    ids=lambda x: x["name"] if isinstance(x, dict) else None)
def test_a_cold_reader_on_a_hand_made_run(entry, key, value):
    read = _reader(entry["name"])
    counters = {"steps": 40, "programs_cold": 0, "programs_cold_s": 0.0}
    assert read({"counters": counters}) == 0.0          # a warm-up that covers the load
    assert read({"counters": {**counters, key: value}}) == pytest.approx(value)
    # a program without the counter (this PR's parent), no counters at all
    assert read({"counters": {"steps": 40}}) is None
    assert read({"counters": None}) is None and read({}) is None


# -- where each is listed -----------------------------------------------------


def test_unscoped_share_is_listed_for_the_cells_whose_tests_leave_their_set_open():
    book = manifest.Manifest(bench_helpers.REPO)
    (entry,) = [m for m in book.data["per_layer"] if m["name"] == "extend.unscoped_share"]
    assert entry == {
        "name": "extend.unscoped_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "request_latency_mean_s",
        "workloads": OPEN_CELLS,
    }
    assert book.data["per_layer"][-1] == entry          # appended, nothing moved
    for name in OPEN_CELLS:
        assert "extend.unscoped_share" in {m["name"] for m in book.cell(name).per_layer}
        bench_helpers.check_cell(book, name)
    # ``==`` on their sets of metrics, in files that are the benchmark's
    for name in PINNED_CELLS:
        assert "extend.unscoped_share" not in {m["name"] for m in book.cell(name).per_layer}
    listed = {m["name"] for m in book.data["per_layer"]}
    assert not {e["name"] for e in ENTRIES} & listed


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A copy of the benchmark with the two entries appended and the tiny cells
    beside the cells they mirror, for a run on the CPU."""
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.edit_manifest(root, lambda book: book["per_layer"].extend(ENTRIES))
    bench_helpers.add_tiny_cells(root)
    return root


def test_the_cold_entries_fit_the_manifest_and_a_warmed_tiny_cell_reports_none_cold(root):
    book = manifest.Manifest(root)
    bench_helpers.check_manifest(book)
    for name in OPEN_CELLS + PINNED_CELLS:
        bench_helpers.check_cell(book, name)
        assert {e["name"] for e in ENTRIES} <= {m["name"] for m in book.cell(name).per_layer}

    line, cell, run = run_mod.run_cell(root, "tiny-serve-cell", 2**31 + 13, 1.5, True)
    assert line["correct"] and line["failed"] == 0
    # ``warm()`` ran every shape the buckets allow: nothing compiled inside traffic
    assert line["metrics"]["engine.cold_programs"] == {"value": 0.0, "unit": "count"}
    assert line["metrics"]["engine.cold_compile_s"] == {"value": 0.0, "unit": "s"}
    # the calls of the load, by the name of the program each ran as, the warmed
    # programs' groups from the first of the two reads on
    counters = run["counters"]
    programs, calls = counters["programs"], counters["calls"]
    assert all(name.startswith(("extend_decode_", "extend_prefill_")) for name in programs)
    assert sum(p["n"] for p in programs.values()) == calls["prefill"]["n"] + calls["decode"]["n"] > 0
    assert sum(p["busy_s"] for p in programs.values()) == pytest.approx(
        calls["prefill"]["busy_s"] + calls["decode"]["busy_s"])
    traced = counters["traced"]["programs"]
    assert set(traced) == set(programs)
    assert 0 < sum(p["n"] for p in traced.values()) < sum(p["n"] for p in programs.values())
    # the CPU has no device plane: no scopes were filed, and the reader says nothing
    assert "extend.unscoped_share" not in line["metrics"]
