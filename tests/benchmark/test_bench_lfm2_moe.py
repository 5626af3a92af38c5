"""LFM2-24B-A2B's files in the benchmark: the configuration is the cut its file
says it is, the architecture's counts are the deployment's arithmetic, the four
readers read what a traced train run holds and nothing from a program that
lacks it, and the tiny twin runs the flow with the counters in its steps."""

import json
import os

import pytest

import bench_helpers
from benchmark import chip, manifest, run as run_mod, yardstick
from benchmark.manifest import published_keys
from benchmark.models import lfm2_moe as architecture

REPO = bench_helpers.REPO
CELL = "lfm2-24b-a2b-train-1chip-fixed-batch"
READERS = (
    "train.moe_share", "train.conv_share", "lfm2.experts_roofline", "train.moe_load_ratio")


@pytest.fixture(scope="module")
def book():
    return manifest.Manifest(REPO)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs", "lfm2-24b-a2b-train-ep2.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_cut_its_file_states(book, config):
    published = config["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert published == row["config"] and config["source"] == row["source_url"]
    # what the list and the group say, as the scalars an architecture is handed
    letters = {"conv": "c", "full_attention": "a"}
    assert config["layer_pattern"] == "".join(letters[k] for k in config["layer_types"])
    assert config["rope_theta"] == config["rope_parameters"]["rope_theta"]
    assert config["rope_type"] == config["rope_parameters"]["rope_type"]
    assert config["rope_parameters"] == published["rope_parameters"]
    # the router scores every published expert and picks as many as published
    assert config["router_experts"] == published["num_experts"] == 64
    assert config["num_experts"] == 32 and config["expert_offset"] == 0
    # one whole period behind one dense layer, as the published model orders them
    assert config["layer_types"] == published["layer_types"][1:6]
    assert config["head_dim"] * config["num_attention_heads"] == config["hidden_size"]
    entry = next(c for c in book.data["configs"] if c["name"] == config["name"])
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"}
    cell = book.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "fixed-batch"
    assert cell.config["job"]["batch"] == [4, 4096]
    assert {m["name"] for m in cell.per_layer} == {
        *READERS, "train.mfu_causal", "trainer.report_ms", "device.idle_share.train"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}


def test_the_programs_model_is_the_deployments_arithmetic(config):
    keys = published_keys(config)
    cfg = architecture.program_config(keys)
    assert cfg.num_params() == 1_375_254_912           # 11.00 GB at 8 B a parameter
    assert f"{cfg.num_params():,}" in config["deployment"]
    assert cfg.layer_types == tuple(config["layer_types"]) and cfg.periods == 1
    assert (cfg.router_experts, cfg.num_experts, cfg.experts_per_token) == (64, 32, 4)
    per_token = architecture.matmul_params(keys)
    assert per_token == pytest.approx(242.8e6, rel=1e-3)
    experts = 4 * 2 * architecture.expert_params(keys)
    assert experts / per_token == pytest.approx(0.311, abs=1e-3)
    flops = architecture.train_step_flops(keys, 4, 4096)
    assert flops == pytest.approx(24.7e12, rel=5e-3)
    # the whole published model, from the same functions
    whole = architecture.program_config({
        **keys, **{k: v for k, v in config["published"].items() if not isinstance(v, (dict, list))},
        "router_experts": 64, "layer_pattern": "cc" + "accc" * 9 + "ac"})
    assert whole.num_params() == 23_843_661_440
    assert f"{whole.num_params():,}" in config["deployment"]


def test_experts_work_counts_three_passes_a_pair_and_four_touches_of_a_hit_expert(config):
    work = architecture.experts_work(config, {"moe_assignments": 1000.0, "moe_experts_hit": 10.0})
    assert work["flops"] == 3 * 2 * 9_437_184 * 1000
    assert work["bytes"] == 4 * 2 * 9_437_184 * 10 + 3 * 2 * 2 * 2048 * 1000


def traced_run(steps=3, units=2):
    step = {
        "loss": 9.0, "grad_norm": 1.0, "step": 1.0, "step_s": 0.4, "moe_tokens": 65536.0,
        "moe_assignments": 131072.0, "moe_experts_hit": 128.0, "moe_load_max": 4 * 1280.0}
    return {
        "kind": "train", "step_metrics": [dict(step) for _ in range(steps)],
        "device": {"kind": "TPU v5 lite"},
        "trace": {
            "units": units, "busy_s": 0.8, "window_s": 0.8,
            "ops_by_scope": [
                ["train.moe.experts", 0.3], ["train.moe.route", 0.02], ["train.conv", 0.1],
                ["train.forward", 0.05], ["train.optimizer", 0.05]]},
    }


def test_the_four_readers_read_a_traced_train_run(book, config):
    run = traced_run()
    read = {name: book.reader(name)(run) for name in READERS}
    assert read["train.moe_share"] == pytest.approx(100 * 0.32 / 0.8)
    assert read["train.conv_share"] == pytest.approx(100 * 0.1 / 0.8)
    assert read["train.moe_load_ratio"] == pytest.approx(32 * 4 * 1280 / 131072)
    work = architecture.experts_work(
        config, {"moe_assignments": 2 * 131072.0, "moe_experts_hit": 2 * 128.0})
    assert read["lfm2.experts_roofline"] == pytest.approx(
        yardstick.roofline_share(work["flops"], work["bytes"], 0.3, "TPU v5 lite"))
    assert 0 < read["lfm2.experts_roofline"] < 100


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_scopes_and_counters_gives_a_reader_nothing(book, name):
    """GPT-J's step, and this PR's parent: scopes ``train.forward`` / ``.loss`` /
    ``.optimizer`` alone, and ``loss``, ``grad_norm``, ``step`` a step."""
    run = traced_run()
    run["trace"]["ops_by_scope"] = [["train.forward", 0.5], ["train.optimizer", 0.1]]
    run["step_metrics"] = [{"loss": 9.0, "grad_norm": 1.0, "step": 1.0, "step_s": 0.4}] * 3
    assert book.reader(name)(run) is None
    assert book.reader(name)({"kind": "train", "trace": None, "step_metrics": []}) is None
    assert book.reader(name)({}) is None


def test_the_tiny_twin_reports_the_counters_step_by_step(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    line, cell, run = run_mod.run_cell(root, "tiny-lfm2-train-cell", 2**31 + 11, 1.0, True)
    assert line["correct"] and run["steps"] > 2
    tokens = 2 * 64
    for m in run["step_metrics"]:
        assert m["moe_tokens"] == 4 * tokens and 0 < m["moe_assignments"] < 4 * tokens * 2
        assert 0 < m["moe_experts_hit"] <= 16 and m["moe_load_max"] <= m["moe_assignments"]
    # the counter's reader reads on the CPU too; the three that need a device trace do not
    assert line["metrics"]["train.moe_load_ratio"]["value"] >= 1.0
    assert not {"train.moe_share", "train.conv_share", "lfm2.experts_roofline"} & set(line["metrics"])
