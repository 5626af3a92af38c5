"""The proof that the benchmark takes another architecture without an edit to
any file it has: ``second_architecture/`` holds what a PR that adds one would
add (an architecture file with published keys under other names and a flop
count of its own, its reference, a configuration with ``published`` and
``reduced``, a traffic file, two cells, two per-layer metrics, its tiny twins),
copied into a temporary checkout of the benchmark's own directories."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_helpers
from benchmark import manifest, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ADDED = os.path.join(HERE, "second_architecture")


def add_second_architecture(root: str) -> None:
    for folder in ("benchmark", "tests"):
        shutil.copytree(
            os.path.join(ADDED, folder), os.path.join(root, folder), dirs_exist_ok=True,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    with open(os.path.join(ADDED, "entries.json")) as f:
        entries = json.load(f)

    def edit(book):
        for group in ("configs", "workloads", "per_layer"):
            book[group] += entries[group]
        for m in book["end_to_end"] + book["per_layer"]:
            m.get("workloads", []).extend(entries["joins"].get(m["name"], []))

    bench_helpers.edit_manifest(root, edit)


def test_a_second_architecture_is_added_by_new_files_alone(tmp_path):
    root = bench_helpers.copy_benchmark(tmp_path)
    before = bench_helpers.files_under(root)
    add_second_architecture(root)

    # the manifest's own tests hold on the copy, for the new configuration too
    book = manifest.Manifest(root)
    bench_helpers.check_manifest(book)
    assert [c["name"] for c in book.data["configs"]][-1] == "tinyalt-small"
    for entry in book.data["configs"]:
        bench_helpers.check_configuration(root, entry)
    bench_helpers.add_tiny_cells(root)
    book = manifest.Manifest(root)
    assert ("tinyalt", "tinyalt-twin-train") in bench_helpers.twins("train", 1, root)
    assert ("tinyalt", "tinyalt-twin-serve") in bench_helpers.twins("serve", 1, root)
    for name in ("tinyalt-train", "tinyalt-serve", "tinyalt-twin-train", "tinyalt-twin-serve"):
        cell = book.cell(name)
        assert cell.architecture == "benchmark.models.tinyalt"
        assert cell.reference == "benchmark.reference.tinyalt_reference"
    assert {m["name"] for m in book.cell("tinyalt-twin-serve").per_layer} == {
        "engine.tokens_per_step", "tinyalt.cache_fill",
    }

    # both flows, traced, in a process that imports the copy (its workers too)
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([root, bench_helpers.REPO]),
    }
    out = subprocess.run(
        [sys.executable, os.path.join(ADDED, "run_flows.py"), root,
         "tinyalt-twin-train", "tinyalt-twin-serve"],
        env=env, capture_output=True, text=True, timeout=900, cwd=root,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    flows = {
        f["cell"]: f for f in
        (json.loads(l[5:]) for l in out.stdout.splitlines() if l.startswith("FLOW "))
    }
    train, serve = flows["tinyalt-twin-train"], flows["tinyalt-twin-serve"]
    for flow in (train, serve):
        assert flow["line"]["correct"] and flow["line"]["failed"] == 0
        assert flow["module_file"] == os.path.join(root, "benchmark", "models", "tinyalt.py")
    # its own flop count (2 x 48 tokens, 2 layers, hidden 64, mlp 192, vocab 320)
    params = 2 * (4 * 64 * 64 + 2 * 64 * 192) + 64 * 320
    assert train["flops_per_step"] == 6.0 * params * 96 + 12.0 * 2 * 2 * 64 * 48 * 49 / 2
    assert set(train["line"]["metrics"]) == {"trainer.report_ms", "train.mfu_causal"}
    # the metric that reads a kv_stats counter the benchmark did not pass on before
    assert set(serve["line"]["metrics"]) == {"engine.tokens_per_step", "tinyalt.cache_fill"}
    assert 0 < serve["line"]["metrics"]["tinyalt.cache_fill"]["value"] <= 100
    # the CPU has no device plane; nothing else is missing from either line
    assert set(train["problems"]) == {
        "device.memory_peak_bytes is missing", "device.busy_s is missing",
        "device.window_s is missing", "metric 'tinyalt.flash_share' is missing",
    }
    assert not any(p.startswith("metric") for p in serve["problems"])

    # the metric that reads ops_by_kernel, on the trace recorded on the chip
    with open(os.path.join(HERE, "recorded_trace_kernels.json")) as f:
        recorded = json.load(f)
    reduced = trace_reduce.reduce(
        recorded["planes"], "bench.train_step", scopes=recorded["scopes"]
    )
    share = book.reader("tinyalt.flash_share")({"trace": reduced})
    ops = dict(map(tuple, reduced["ops_by_kernel"]))
    assert share == pytest.approx(
        100 * sum(ops[k] for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
        / sum(ops.values())
    )
    assert 0 < share < 100

    # and every file the benchmark had is there byte for byte
    after = bench_helpers.files_under(root)
    assert all(after[p] == content for p, content in before.items())
    assert len(after) > len(before)
