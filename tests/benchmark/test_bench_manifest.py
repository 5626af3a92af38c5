"""``BENCHMARK.json`` and every file it names load by name; a cell, a
configuration and a metric are added by new files and entries alone; the
yardstick's arithmetic."""

import json
import os
import subprocess
import sys

import pytest

import bench_helpers
from benchmark import manifest, yardstick

REPO = bench_helpers.REPO
BOOK = manifest.Manifest(REPO)


@pytest.fixture(scope="module")
def book():
    return BOOK


def test_manifest_keys_and_names(book):
    bench_helpers.check_manifest(book)
    four = [w for w in book.data["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(book.data["workloads"]) // 4)


def test_every_cell_loads_with_its_files_and_readers(book):
    for name in book.cell_names():
        bench_helpers.check_cell(book, name)


@pytest.mark.parametrize("entry", BOOK.data["configs"], ids=lambda c: c["name"])
def test_widths_are_as_published_and_what_differs_is_listed_as_reduced(book, entry):
    bench_helpers.check_configuration(book.root, entry)


def test_unknown_names_are_manifest_errors(book):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        book.cell("no-such-cell")
    with pytest.raises(manifest.ManifestError, match="no reader"):
        book.reader("no.such.metric")


def test_a_configuration_of_an_architecture_without_its_file_says_which(tmp_path):
    root = bench_helpers.copy_benchmark(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "gptj-6b-serve.json")
    with open(path) as f:
        config = json.load(f)
    with open(path, "w") as f:
        json.dump({**config, "model_type": "olmoe"}, f)
    with pytest.raises(manifest.ManifestError, match=r"model_type is 'olmoe'.*benchmark/models/olmoe\.py"):
        manifest.Manifest(root).cell("gptj-serve-chat-steady")
    with open(path, "w") as f:
        json.dump({**config, "reference": {"module": "olmoe_reference"}}, f)
    with pytest.raises(manifest.ManifestError, match=r"benchmark/reference/olmoe_reference\.py"):
        manifest.Manifest(root).cell("gptj-serve-chat-steady")


def test_a_cell_a_configuration_and_a_metric_are_added_by_new_files_alone(tmp_path):
    root = bench_helpers.copy_benchmark(tmp_path)
    before = bench_helpers.files_under(root)
    bench_helpers.add_tiny_cells(root)
    with open(os.path.join(root, "benchmark", "metrics", "train.last_loss.py"), "w") as f:
        f.write("def read(run):\n    return run.get('last_loss')\n")
    bench_helpers.edit_manifest(root, lambda data: data["per_layer"].append({
        "name": "train.last_loss", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train-cell"],
    }))
    book = manifest.Manifest(root)
    cell = book.cell("tiny-train-cell")
    assert cell.config["job"]["batch"] == [2, 64]
    assert cell.traffic["generator"] == "train_fixed_batch"
    assert "train.last_loss" in [m["name"] for m in cell.per_layer]
    assert book.reader("train.last_loss")({"last_loss": 1.5}) == 1.5
    assert book.cell("tiny-serve-cell").traffic["rate_rps"] == 8.0
    # the old cells do not see the new metric, and no old file was edited
    assert "train.last_loss" not in [
        m["name"] for m in book.cell("gptj-train-1chip-fixed-batch").per_layer
    ]
    after = bench_helpers.files_under(root)
    assert all(after[p] == content for p, content in before.items())


def test_peaks_percentiles_and_a_kernels_share_of_its_roofline():
    assert yardstick.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError, match="no peaks on record"):
        yardstick.peak("TPU v9", "bf16_flops")
    assert yardstick.percentile(list(range(1, 21)), 0.95) == 19
    assert yardstick.percentile([3.0, 1.0, 2.0], 0.95) == 3.0
    assert yardstick.median([4, 1, 3, 2]) == 2
    # bound by compute: 197 TFLOP in 2 s is half the peak; the bytes are few
    assert yardstick.roofline_share(197e12, 1e9, 2.0, "TPU v5 lite") == pytest.approx(50.0)
    # bound by memory: 819 GB in 4 s is a quarter of the bandwidth
    assert yardstick.roofline_share(1e9, 819e9, 4.0, "TPU v5 lite") == pytest.approx(25.0)
    with pytest.raises(KeyError, match="no peaks on record"):
        yardstick.roofline_share(1.0, 1.0, 1.0, "cpu")


def test_off_chip_the_command_prints_no_result():
    """No accelerator: non-zero exit, the reason on stderr, no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "gptj-train-1chip-fixed-batch", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "RAYTPU_TPU_TOPOLOGY": ""},
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode != 0
    assert "no result" in out.stderr and "TPU chip" in out.stderr
    assert '"correct"' not in out.stdout


def test_without_the_program_the_command_prints_no_result(tmp_path):
    """A directory that holds only the manifest and the benchmark's own paths."""
    root = bench_helpers.copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "gptj-serve-chat-steady", "--seed", "1", "--seconds", "1", "--trace", "1"],
        env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=300, cwd=root,
    )
    assert out.returncode != 0 and "no result" in out.stderr
    assert '"correct"' not in out.stdout
