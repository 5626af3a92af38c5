"""``BENCHMARK.json`` and every file it names load by name; a cell, a
configuration and a metric are added by new files and entries alone; the
yardstick's arithmetic."""

import json
import os
import re
import subprocess
import sys

import pytest

import bench_helpers
from benchmark import manifest, yardstick

REPO = bench_helpers.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def book():
    return manifest.Manifest(REPO)


def test_manifest_keys_and_names(book):
    data = book.data
    assert set(data) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
        "per_layer",
    }
    assert 1 <= data["run_seconds"] <= 51 and data["paths"][0] == "benchmark"
    four = [w for w in data["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(data["workloads"]) // 4)
    e2e = {m["name"] for m in data["end_to_end"]}
    assert "setup_s" in e2e
    for m in data["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in data["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e and "bound" not in m
    for c in data["configs"]:
        assert c["file"].startswith("benchmark/configs/") and len(c["why"]) <= 200
    assert len(json.dumps(data)) < 64 * 1024


def test_every_cell_loads_with_its_files_and_readers(book):
    for name in book.cell_names():
        cell = book.cell(name)
        assert cell.config["n_embd"] == 4096 and cell.config["n_head"] == 16   # widths never cut
        assert cell.config["vocab_size"] == 50400 and cell.config["rotary_dim"] == 64
        assert callable(book.generator(cell).run)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert book.reader(m["name"])({}) is None          # nothing to read: nothing
        for m in cell.per_layer:
            assert m["moves"] in e2e
    for c in book.data["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            config = json.load(f)
        assert set(c["reduced"]) == set(config["reduced"])
        assert config["source"] == c["source"] and config["assumed"]


def test_unknown_names_are_manifest_errors(book):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        book.cell("no-such-cell")
    with pytest.raises(manifest.ManifestError, match="no reader"):
        book.reader("no.such.metric")


def test_a_cell_a_configuration_and_a_metric_are_added_by_new_files_alone(tmp_path):
    root = bench_helpers.copy_benchmark(tmp_path)
    before = {
        p: open(os.path.join(d, p), "rb").read()
        for d, _, files in os.walk(os.path.join(root, "benchmark")) for p in files
    }
    bench_helpers.add_tiny_cells(root)
    with open(os.path.join(root, "benchmark", "metrics", "train.last_loss.py"), "w") as f:
        f.write("def read(run):\n    return run.get('last_loss')\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["per_layer"].append({
        "name": "train.last_loss", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train-cell"],
    })
    with open(path, "w") as f:
        json.dump(data, f)
    book = manifest.Manifest(root)
    cell = book.cell("tiny-train-cell")
    assert cell.config["n_embd"] == 64 and cell.traffic["generator"] == "train_fixed_batch"
    assert "train.last_loss" in [m["name"] for m in cell.per_layer]
    assert book.reader("train.last_loss")({"last_loss": 1.5}) == 1.5
    assert book.cell("tiny-serve-cell").traffic["rate_rps"] == 8.0
    # the old cells do not see the new metric, and no old file was edited
    assert "train.last_loss" not in [
        m["name"] for m in book.cell("gptj-train-1chip-fixed-batch").per_layer
    ]
    for p, content in before.items():
        found = [
            os.path.join(d, p) for d, _, files in os.walk(os.path.join(root, "benchmark"))
            if p in files
        ]
        assert any(open(x, "rb").read() == content for x in found)


def test_flops_count_what_causal_attention_requires():
    model = {"n_embd": 4096, "n_head": 16, "n_inner": None, "n_layer": 6,
             "vocab_size": 50400}
    assert yardstick.matmul_params(model) == 6 * (4 * 4096**2 + 2 * 4096 * 16384) + 4096 * 50400
    flops = yardstick.train_step_flops(model, 4, 2048)
    assert flops == pytest.approx(72.0e12, rel=0.01)
    # about half of the full square the program's own count takes
    attention = flops - 6.0 * yardstick.matmul_params(model) * 8192
    assert attention == pytest.approx(12 * 6 * 4 * 4096 * 2048 * 2048 / 2, rel=1e-3)


def test_peaks_and_percentiles():
    assert yardstick.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError, match="no peaks on record"):
        yardstick.peak("TPU v9", "bf16_flops")
    assert yardstick.percentile(list(range(1, 21)), 0.95) == 19
    assert yardstick.percentile([3.0, 1.0, 2.0], 0.95) == 3.0
    assert yardstick.median([4, 1, 3, 2]) == 2


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
