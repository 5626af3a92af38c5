"""``train.moe_rows_ratio``: the sorted rows the backward of LFM2's trained expert
layers walks over the pairs they hold, from the steps' own counters; on a hand-made run with
and without the program's ``moe_rows_visited``, and on the tiny twin.

``ENTRY`` is its ``per_layer`` entry. It is **not in** ``BENCHMARK.json``: the
cell's accepted test (``test_bench_lfm2_moe.py``
``test_the_configuration_is_the_cut_its_file_states``) holds the set of the
cell's per-layer metrics, and that file is the benchmark's, not a ``perf_opt``
PR's to edit. The next ``benchmark`` issue appends the entry as it stands here
and widens that set; until then it is laid over a copy of the manifest."""

import pytest

import bench_helpers
from benchmark import chip, manifest, run as run_mod, yardstick

CELL = "lfm2-24b-a2b-train-1chip-fixed-batch"
NAME = "train.moe_rows_ratio"
ENTRY = {
    "name": NAME, "unit": "ratio", "better": "lower", "source": "program_counter",
    "layer": "expert layer", "moves": "train_tokens_per_s", "workloads": [CELL],
}
# four expert layers of 16,384 tokens and 4 choices each: 262,144 pairs routed a step
STEP = {
    "loss": 9.0, "grad_norm": 1.0, "step": 1.0, "step_s": 0.4, "moe_tokens": 65536.0,
    "moe_assignments": 191100.0, "moe_experts_hit": 128.0, "moe_load_max": 4 * 1800.0}


@pytest.fixture(scope="module")
def read():
    return manifest.Manifest(bench_helpers.REPO).reader(NAME)


@pytest.mark.parametrize("visited, ratio", [
    (None, 262144 / 191100),              # the parent: every pair of every token
    (262144.0, 262144 / 191100),          # every layer walks all its rows
    (3 * 49152.0 + 53248.0, 200704 / 191100),      # blocks of 4,096 rows
    (191100.0, 1.0),
])
def test_rows_visited_over_pairs_held(read, visited, ratio):
    step = dict(STEP) if visited is None else {**STEP, "moe_rows_visited": visited}
    assert read({"kind": "train", "step_metrics": [step] * 3}) == pytest.approx(ratio)


def test_the_sums_are_over_the_windows_steps(read):
    first = {**STEP, "moe_assignments": 131072.0, "moe_rows_visited": 4 * 40960.0}
    later = {**STEP, "moe_rows_visited": 4 * 49152.0}
    assert read({"step_metrics": [first, later, later]}) == pytest.approx(
        (4 * 40960 + 2 * 4 * 49152) / (131072 + 2 * 191100))


@pytest.mark.parametrize("run", [
    {"kind": "train", "step_metrics": [{"loss": 9.0, "grad_norm": 1.0, "step": 1.0}] * 3},
    {"kind": "train", "step_metrics": []}, {"kind": "serve", "step_metrics": None}, {},
], ids=["gptj-step", "no-steps", "serve", "empty"])
def test_a_step_without_the_counters_gives_nothing(read, run):
    assert read(run) is None


def test_the_entry_fits_the_manifest_and_the_tiny_twin_reports_it(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.edit_manifest(root, lambda book: book["per_layer"].append(ENTRY))
    bench_helpers.add_tiny_cells(root)
    book = manifest.Manifest(root)
    bench_helpers.check_manifest(book)
    bench_helpers.check_cell(book, CELL)
    assert NAME not in {m["name"] for m in manifest.Manifest(bench_helpers.REPO).data["per_layer"]}

    line, cell, run = run_mod.run_cell(root, "tiny-lfm2-train-cell", 2**31 + 13, 1.0, True)
    assert line["correct"] and run["steps"] > 2
    steps = run["step_metrics"]
    assert all(m["moe_rows_visited"] >= m["moe_assignments"] > 0 for m in steps)
    # a counter's reader reads on the CPU too
    assert line["metrics"][NAME]["value"] == pytest.approx(
        sum(m["moe_rows_visited"] for m in steps) / sum(m["moe_assignments"] for m in steps))
    assert 1.0 <= line["metrics"][NAME]["value"] <= 2 * 2 * 64 * 4 / min(
        m["moe_assignments"] for m in steps)
