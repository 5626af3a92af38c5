"""Nemotron-3-Nano's files in the benchmark: the configuration is the cut its file
says it is, the architecture's counts are the deployment's arithmetic, the three new
readers read what a traced train run holds and nothing from a program that lacks it,
and the tiny twin runs the flow with the counters in its steps."""

import json
import os

import pytest

import bench_helpers
from benchmark import chip, manifest, run as run_mod, yardstick
from benchmark.manifest import published_keys
from benchmark.models import nemotron_h as architecture

REPO = bench_helpers.REPO
CELL = "nemotron-3-nano-train-1chip-fixed-batch"
CONFIG = "nemotron-3-nano-30b-a3b-train-ep8"
READERS = (
    "train.ssm_share", "nemotron_3_nano.scan_roofline", "nemotron_3_nano.experts_roofline",
    "train.moe_share")


@pytest.fixture(scope="module")
def book():
    return manifest.Manifest(REPO)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_the_cut_its_file_states(book, config):
    published = config["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert published == row["config"] and config["source"] == row["source_url"]
    # the first nine layers of the published model, as it orders them
    assert config["hybrid_override_pattern"] == published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert config["num_hidden_layers"] == 9 and config["residual_layers"] == published["num_hidden_layers"]
    # the router scores every published expert and picks as many as published
    assert config["router_experts"] == published["n_routed_experts"] == 128
    assert config["n_routed_experts"] == 16 and config["expert_offset"] == 0
    assert config["vocab_size"] * 8 == published["vocab_size"]
    # the cell is there (wherever in the list later cells put it), with the issue's traffic
    entry = next(c for c in book.data["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}
    assert CELL in book.cell_names()
    cell = book.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "fixed-batch" and cell.config_name == CONFIG
    assert cell.config["job"]["batch"] == [2, 8192]
    assert {m["name"] for m in cell.per_layer} == {
        *READERS, "train.mfu_causal", "trainer.report_ms", "device.idle_share.train"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert cell.config["job"]["min_kernels"] == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1, "gmm": 16, "tgmm": 8}


def test_the_programs_model_is_the_deployments_arithmetic(config):
    keys = published_keys(config)
    cfg = architecture.program_config(keys)
    assert cfg.num_params() == 986_254_848             # 7.89 GB at 8 B a parameter
    assert f"{cfg.num_params():,}" in config["deployment"]
    assert cfg.pattern == "MEMEM*EME" and cfg.ssm_groups == 8 and cfg.ssm_chunk == 128
    assert (cfg.router_experts, cfg.num_experts, cfg.experts_per_token) == (128, 16, 6)
    assert cfg.routed_scale == 2.5 and cfg.residual_layers == 52
    per_token = architecture.matmul_params(keys)
    assert per_token == 333_398_016
    per_layer = architecture.mixer_matmul_params(keys)
    assert 4 * per_layer["M"] / per_token == pytest.approx(0.464, abs=1e-3)
    assert 4 * 0.75 * architecture.expert_params(keys) / per_token == pytest.approx(0.090, abs=1e-3)
    flops = architecture.train_step_flops(keys, 2, 8192)
    assert flops == pytest.approx(36.7e12, rel=5e-3)
    # the whole published model, from the same functions
    whole = architecture.program_config({
        **keys, **config["published"], "router_experts": 128, "residual_layers": 52})
    assert whole.num_params() == 31_577_940_288
    assert f"{whole.num_params():,}" in config["deployment"]
    with pytest.raises(ValueError, match="does not name"):
        architecture.program_config({**keys, "num_hidden_layers": 8})
    with pytest.raises(ValueError, match="one nemotron_h block"):
        architecture.program_config({**keys, "mlp_hidden_act": "silu"})


def test_experts_work_counts_three_passes_a_pair_and_four_touches_of_a_hit_expert(config):
    work = architecture.experts_work(config, {"moe_assignments": 1000.0, "moe_experts_hit": 10.0})
    assert architecture.expert_params(config) == 9_977_856          # two matrices, no gate
    assert work["flops"] == 3 * 2 * 9_977_856 * 1000
    assert work["bytes"] == 4 * 2 * 9_977_856 * 10 + 3 * 2 * 2 * 2688 * 1000


def test_scan_work_counts_three_passes_a_token_and_the_states_once(config):
    work = architecture.scan_work(config, 2, 8192)
    tokens = 2 * 8192 * 4                                          # four Mamba layers
    forward = 2 * (8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128)
    assert forward == 3_407_872
    assert work["flops"] == 3 * forward * tokens == pytest.approx(0.67e12, rel=1e-2)
    rows = 2 * (2 * 64 * 64 + 64 + 2 * 8 * 128)
    states = 2 * 4 * 64 * 64 * 128 * tokens // 128
    assert work["bytes"] == 3 * rows * tokens + states
    # twice the tokens, twice the work; no Mamba layer, none
    twice = architecture.scan_work(config, 4, 8192)
    assert twice == {k: 2 * v for k, v in work.items()}
    none = architecture.scan_work({**config, "hybrid_override_pattern": "E*", "num_hidden_layers": 2}, 2, 8192)
    assert none == {"flops": 0.0, "bytes": 0.0}


def traced_run(steps=3, units=2):
    step = {
        "loss": 9.7, "grad_norm": 1.0, "step": 1.0, "step_s": 0.8, "moe_tokens": 65536.0,
        "moe_assignments": 49152.0, "moe_experts_hit": 64.0, "moe_load_max": 900.0,
        "moe_rows_visited": 73728.0}
    return {
        "kind": "train", "step_metrics": [dict(step) for _ in range(steps)],
        "device": {"kind": "TPU v5 lite"},
        "trace": {
            "units": units, "busy_s": 1.6, "window_s": 1.6,
            "ops_by_scope": [
                ["train.ssm.proj", 0.4], ["train.ssm.scan", 0.3], ["train.moe.experts", 0.25],
                ["train.moe.shared", 0.15], ["train.ssm.norm", 0.1], ["train.ssm.conv", 0.08],
                ["train.attention", 0.1], ["train.moe.route", 0.02], ["train.loss", 0.1],
                ["train.forward", 0.05], ["train.optimizer", 0.05]]},
    }


def test_the_readers_read_a_traced_train_run(book, config):
    run = traced_run()
    read = {name: book.reader(name)(run) for name in READERS}
    assert read["train.ssm_share"] == pytest.approx(100 * 0.88 / 1.6)
    assert read["train.moe_share"] == pytest.approx(100 * 0.42 / 1.6)
    scan = architecture.scan_work(config, 2, 8192)
    assert read["nemotron_3_nano.scan_roofline"] == pytest.approx(yardstick.roofline_share(
        2 * scan["flops"], 2 * scan["bytes"], 0.3, "TPU v5 lite"))
    experts = architecture.experts_work(
        config, {"moe_assignments": 2 * 49152.0, "moe_experts_hit": 2 * 64.0})
    assert read["nemotron_3_nano.experts_roofline"] == pytest.approx(
        yardstick.roofline_share(experts["flops"], experts["bytes"], 0.25, "TPU v5 lite"))
    assert all(0 < read[name] < 100 for name in READERS)


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


def test_lfm2s_step_gives_the_scans_readers_nothing(book):
    """A step with expert layers and no recurrence: ``train.moe_share`` reads it, the
    two readers of ``train.ssm.*`` do not."""
    run = traced_run()
    run["trace"]["ops_by_scope"] = [["train.moe.experts", 0.3], ["train.conv", 0.1]]
    assert book.reader("train.moe_share")(run) is not None
    assert book.reader("train.ssm_share")(run) is None
    assert book.reader("nemotron_3_nano.scan_roofline")(run) is None


def test_the_tiny_twin_reports_the_counters_step_by_step(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    line, cell, run = run_mod.run_cell(root, "tiny-nemotron-train-cell", 2**31 + 13, 1.0, True)
    assert line["correct"] and run["steps"] > 2
    tokens = 2 * 64
    for m in run["step_metrics"]:
        assert m["moe_tokens"] == 4 * tokens and 0 < m["moe_assignments"] < 4 * tokens * 3
        assert 0 < m["moe_experts_hit"] <= 16 and m["moe_load_max"] <= m["moe_assignments"]
    # what needs a device trace is not read on the CPU
    assert not set(READERS) & set(line["metrics"])
    assert run["flops_per_step"] == architecture.train_step_flops(
        published_keys(cell.config), 2, 64)
