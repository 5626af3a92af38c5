"""granite-4.0-h-small (``granitemoehybrid`` with routed experts, served as
``granitemoehybrid_moe``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in chunks
through the state store and the expert layers, decode, the same prompt again
from a state snapshot, against the reference's token-by-token recurrence and
expert-by-expert loop), what the comparison's limit catches, ``experts_work`` by
hand, the readers of the three metrics on a hand-made run, and the
configuration's and the traffic's files against the catalog's row and the
issue. float32 throughout; the projections and the router are scaled up so that
the logits are of order 1 and the router's weights are far from uniform."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import granitemoehybrid_moe as arch
from benchmark.reference import granitemoehybrid_moe_reference as ref

TINY = bench_helpers.tiny("granitemoehybrid_moe")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "granite-4h-small-serve-agent-bursts"
FILE = BOOK.root + "/benchmark/configs/granite-4.0-h-small-serve-ep2.json"
NEW_METRICS = (
    "granite_4h_small.experts_roofline", "granite_4h_small.scan_roofline",
    "granite_4h_small.experts_hit_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: what each omission reads at this size, as a multiple of the limit it must pass
CAUGHT = {
    "no_shared": 50, "no_residual_multiplier": 50, "uniform_weights": 50, "eight_choices": 50,
    "wrong_offset": 50, "fp8_weights": 50}


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 8.0
        if path[-1].key in ("kernel", "wi", "wo", "embedding", "router")
        and "conv" not in [getattr(k, "key", None) for k in path] else a,
        cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the server, twice: a prompt of 60 tokens in chunks of
    32, then 8 decoded tokens; then the same again, 48 tokens from the prefix
    cache and the state after them from its snapshot."""
    from ray_tpu.serve import llm

    cfg, program = weights
    server = llm.LLMServer(cfg, params=program, **ENGINE)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, cfg.vocab_size, size=60)]
    ask = {"prompt": prompt, "max_new_tokens": 8, "return_logits": True}
    before = server.kv_stats()
    out = server(ask)
    after = server.kv_stats()
    return server, prompt, out, server(ask), before, after


@pytest.fixture(scope="module")
def wanted(weights, served):
    _, program = weights
    _, prompt, out, _, _, _ = served
    fed = prompt + out["tokens"][:-1]
    return fed, np.asarray(ref.program_logits(program, fed, CONFIG, 8))


def test_prefill_decode_and_the_snapshot_match_the_reference(weights, served, wanted):
    cfg, _ = weights
    _, _, out, again, _, _ = served
    _, want = wanted
    assert out["logits"].shape == (8, cfg.vocab_size) == (8, 128)
    assert float(np.std(want)) > 0.1
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"]
    np.testing.assert_array_equal(again["logits"], out["logits"])


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    # ("eight_choices" at this size is all eight experts for the tiny model's three)
    other = np.asarray(ref.program_logits(program, fed, CONFIG, 8, wrong))
    assert yardstick.logits_error(out["logits"], other) > CAUGHT[wrong] * LIMIT, wrong


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    one_period = {**CONFIG, "num_hidden_layers": 4}
    assert yardstick.logits_error(
        np.asarray(ref.program_logits(program, fed, one_period, 8)), want) > 50 * LIMIT


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls: 6 Mamba
    layers and 8 expert layers, one lane, 9 calls; the store copies a state for
    the repeat's prefix hit and for nothing else."""
    server, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("ssm_", "state_", "moe_"))}
    assert d["ssm_tokens"] == 6 * (60 + 7) and d["ssm_state_passes"] == 6 * 9
    assert d["moe_tokens"] == 8 * (60 + 7)
    # three choices a token of eight experts, four held: about half computed here
    assert 0.3 * 3 * d["moe_tokens"] < d["moe_assignments"] < 0.7 * 3 * d["moe_tokens"]
    assert d["moe_assignments"] >= d["moe_load_max"] >= d["moe_experts_hit"] > 0
    assert d["moe_experts_hit"] <= 4 * 8 * 9                    # held x layers x calls
    assert d["state_restores"] == 0 and d["state_bytes_moved"] == 0
    after_again = server.kv_stats()
    assert after_again["state_restores"] - after["state_restores"] == 1
    assert after_again["state_bytes_moved"] == server._engine.pool.state_bytes


# -- the readers ----------------------------------------------------------------


def test_experts_work_by_hand_for_one_call():
    with open(FILE) as f:
        keys = json.load(f)
    assert arch.expert_params(keys) == 9_437_184 and arch.shared_params(keys) == 18_874_368
    # a decode call of 8 lanes: 80 tokens x layers, 41 pairs held, 33 experts hit in 10 layers
    work = arch.experts_work(keys, {
        "moe_tokens": 80, "moe_assignments": 41, "moe_experts_hit": 33,
        "phase_n": {"dispatch": 1}})
    assert work["flops"] == 2 * 9_437_184 * 41 and work["bytes"] == 18_874_368 * 33
    assert work["shared_flops"] == 2 * 18_874_368 * 80
    assert work["shared_bytes"] == 37_748_736 * 10
    assert work["flops"] / 197e12 < work["bytes"] / 819e9          # a decode call: memory
    # a chunk of 512 tokens: every held expert of every layer hit, half the pairs held
    chunk = arch.experts_work(keys, {
        "moe_tokens": 5120, "moe_assignments": 25600, "moe_experts_hit": 360,
        "phase_n": {"dispatch": 1}})
    assert chunk["bytes"] == 18_874_368 * 360 and chunk["shared_bytes"] == 37_748_736 * 10
    assert chunk["flops"] == 2 * 9_437_184 * 25600
    # what the block shares with the configuration without experts is that module's
    assert arch.scan_flops_per_token(keys) == 4 * 128 * 64 * 128 == 4_194_304
    scan = arch.scan_work(keys, {"ssm_tokens": 72, "ssm_state_passes": 72})
    assert scan["state_bytes"] == 72 * 2 * 4_194_304
    assert scan["bytes"] == scan["state_bytes"] + 72 * 2 * (8192 + 256 + 128 + 8192)
    assert arch.mamba_params(keys) == 4096 * 16768 + 8192 * 4096
    assert arch.attention_params(keys) == 41_943_040
    assert arch.matmul_params(keys) == (
        9 * arch.mamba_params(keys) + 41_943_040
        + 10 * (4096 * 72 + 18_874_368 + 10 * 9_437_184) + 4096 * 50176)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "moe_tokens": 9_000_000, "moe_assignments": 45_000_000, "moe_experts_hit": 900_000,
            "ssm_tokens": 8_100_000, "ssm_state_passes": 200_000, "state_bytes_moved": 0,
            "phase_s": {"step": 40.0}, "phase_n": {"dispatch": 5000},
            "traced": {
                "moe_tokens": 60_000, "moe_assignments": 300_000, "moe_experts_hit": 18_000,
                "ssm_tokens": 54_000, "ssm_state_passes": 3_600, "steps": 100,
                "phase_n": {"dispatch": 100},
            },
        },
        "trace": {
            "busy_s": 1.25, "window_s": 1.5, "engine": {"steps": 100, "in_step_s": 1.4},
            "ops_by_scope": [
                ["extend.moe.experts", 0.5], ["extend.moe.shared", 0.1],
                ["extend.moe.route", 0.05], ["extend.ssm", 0.2], ["extend.ssm.scan", 0.08],
                ["extend.attention", 0.05], ["(no scope)", 0.2],
            ],
            "ops_by_kernel": [
                ["gmm", 0.45], ["fusion", 0.3], ["copy-done", 0.2], ["ssm_step", 0.06],
                ["sort", 0.01],
            ],
        },
    }


def test_the_three_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    # the traced steps' own counts, unscaled: the routed experts over the 0.45 s of the kernel
    # gmm, by its name: a fusion that takes extend.moe.experts for its root's scope moves
    # nothing (the shared MLP's weights arrive by the scan's prefetch, under no scope: left out)
    moved, done = 18_874_368 * 18_000, 2 * 9_437_184 * 300_000
    assert moved / 819e9 > done / 197e12
    assert read["granite_4h_small.experts_roofline"](run) == pytest.approx(
        100 * moved / 819e9 / 0.45)
    assert 0 < read["granite_4h_small.experts_roofline"](run) < 100
    stray = {**run, "trace": {**run["trace"], "ops_by_scope": [
        ["extend.moe.experts", 0.56], ["extend.logits", 0.01], ["extend.ssm.scan", 0.08]]}}
    assert read["granite_4h_small.experts_roofline"](stray) == read[
        "granite_4h_small.experts_roofline"](run)
    # this configuration's widths, not micro's: a state of 4 MB a layer
    scan = 3_600 * 2 * 4_194_304 + 54_000 * 2 * (8192 + 256 + 128 + 8192)
    assert read["granite_4h_small.scan_roofline"](run) == pytest.approx(
        100 * scan / 819e9 / 0.08)
    assert read["granite_4h_small.scan_roofline"](run) == pytest.approx(
        2 * BOOK.reader("ssm.scan_roofline")(run), rel=0.05)
    assert read["granite_4h_small.experts_hit_share"](run) == pytest.approx(100 * 18_000 / (360 * 100))
    assert BOOK.reader("extend.moe_share")(run) == pytest.approx(100 * 0.65 / 1.25)
    assert BOOK.reader("engine.state_copy_ratio")(run) == 0
    # a run of a program without the counters, the record or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    untraced = {**run, "counters": {k: v for k, v in run["counters"].items() if k != "traced"}}
    no_scopes = {**run, "trace": {
        **run["trace"], "ops_by_scope": [["extend.mlp", 1.0]], "ops_by_kernel": [["fusion", 1.0]]}}
    micro = {**run, "counters": {**run["counters"], "traced": {
        "ssm_tokens": 54_000, "ssm_state_passes": 3_600, "phase_n": {"dispatch": 100}}}}
    for other in (bare, untraced, {}):
        assert all(read[n](other) is None for n in NEW_METRICS)
    assert read["granite_4h_small.experts_roofline"](no_scopes) is None
    assert read["granite_4h_small.scan_roofline"](no_scopes) is None
    assert read["granite_4h_small.experts_roofline"](micro) is None
    assert read["granite_4h_small.experts_hit_share"](micro) is None


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_four_keys_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
    assert config["source"] == row["source_url"]
    # the harness finds the module by this key; the published value stands beside it
    assert config["model_type"] == "granitemoehybrid_moe" and "model_type" not in published
    assert config["published_model_type"] == row["config"]["model_type"] == "granitemoehybrid"
    assert any("model_type" in d for d in config["departures"])
    cut = {"num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176}
    for key, value in row["config"].items():
        if key != "model_type":
            assert published[key] == value, key
            assert config[key] == cut.get(key, value), key
    assert set(config["reduced"]) == set(cut) | {"param_dtype"}
    assert (published["param_dtype"], config["param_dtype"]) == ("float32", "bfloat16")
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    assert (config["router_experts"], config["expert_offset"]) == (72, 0)
    assert config["router_experts"] == published["num_local_experts"]
    assert "intermediate_size" in config["assumed"] and "inference" in config["assumed"][
        "intermediate_size"]
    # one whole period of the published pattern
    assert arch.layer_pattern(config["layer_types"]) == {
        "layer_period": config["layer_period"],
        "attention_layer_offset": config["attention_layer_offset"]} == {
        "layer_period": 10, "attention_layer_offset": 5}
    cfg = arch.program_config(manifest.published_keys(config))
    assert list(cfg.layer_types) == config["layer_types"][:10] and cfg.periods == 1
    assert (cfg.embed_dim, cfg.mlp_dim, cfg.expert_dim, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim) == (4096, 1536, 768, 32, 8, 128)
    assert (cfg.router_experts, cfg.num_experts, cfg.expert_offset, cfg.experts_per_token) == (
        72, 36, 0, 10)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk, cfg.conv_width) == (
        128, 64, 128, 256, 4)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling, cfg.norm_eps) == (12.0, 0.22, 0.0078125, 16.0, 1e-5)
    assert cfg.state_dtype == np.float32 and cfg.cache_arrays == ((1, 1024), (1, 1024))
    assert "experts 36 held of 72 from 0, 10 a token, width 768" in arch.describe(cfg)
    # 4.76 B parameters = 9.51 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 4_757_211_776
    assert "4,757,211,776 parameters = 9.51 GB" in config["deployment"]
    for part in ("102,286,976", "41,943,040", "339,738,624", "4,551,686,784", "205,520,896"):
        assert part in config["deployment"], part
    assert "ep2" in config["deployment"] and "pp4" in config["deployment"]
    assert "38,204,928 B a sequence" in config["assumed"]["state_dtype"]
    assert "71 rows" in config["assumed"]["experts_rows"]
    assert config["assumed"]["init"] and len(config["departures"]) >= 5
    assert config["reference"]["module"] == "granitemoehybrid_moe_reference"
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.2
    engine = config["engine"]
    weights = 2 * cfg.num_params()
    assert weights >= 0.25 * 16.91e9 * 2                        # the floor on weights alone
    resident = weights + engine["num_blocks"] * engine["block_size"] * 4096 + (
        engine["state_slots"] * 38_204_928)
    assert engine["state_slots"] - 1 == 8 + 8 + 40          # executing, queued, snapshots
    assert 12.4e9 < resident < 12.6e9
    stated = config["compiled_bytes_per_device"]
    assert stated["decode"]["shape"] == [8, 1, 8192] and stated["prefill"]["shape"] == [1, 512, 8192]
    assert 13.5e9 <= stated["peak_bytes_in_use"] <= 15.9e9


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "granite-4.0-h-small-serve-ep2"
    assert cell.traffic_name == "agent-bursts"
    assert len(cell.why) <= 200 and "half" in cell.why
    assert {m["name"] for m in cell.per_layer} == set(NEW_METRICS) | {
        "extend.moe_share", "extend.ssm_share", "extend.attention_share",
        "engine.state_copy_ratio", "engine.step_ms", "engine.tokens_per_step",
        "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s"}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    for name in NEW_METRICS:
        (entry,) = (m for m in BOOK.data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "request_latency_mean_s"
    assert sum(w["chips"] == 4 for w in BOOK.data["workloads"]) == 1
    assert BOOK.data["workloads"][-1]["name"] == CELL
    assert BOOK.data["configs"][-1]["name"] == cell.config_name
    prompts, outputs = traffic["prompt_tokens"], traffic["output_tokens"]
    assert prompts == [1024, 512, 2048, 768, 256, 4096, 384, 1536, 192, 6144]
    assert outputs == [96, 64, 128, 48, 192, 64, 256, 160, 320, 96]
    assert (sum(prompts) / 10, sum(outputs) / 10) == (1696.0, 142.4)
    assert sum(-(-p // 512) for p in prompts) == 35
    engine = config["engine"]
    assert max(p + o for p, o in zip(prompts, outputs)) == 6240 <= engine["cache_buckets"][-1] == 8192
    assert engine["block_size"] == config["mamba_chunk_size"] == 256
    assert engine["prefill_chunk"] == 512 and engine["prefill_lanes"] == 1
    assert engine["lane_buckets"] == [1, 2, 4, 8]
    # bursts: four turns within 0.6 of an interval, then one every 1.5
    offsets = traffic["due_offsets"]
    assert offsets == [0.0, -0.8, -1.6, -2.4, -2.5, -2.0, -1.5, -1.0, -0.5, 0.0]
    assert np.allclose(
        [i + o for i, o in enumerate(offsets)],
        [0, 0.2, 0.4, 0.6, 1.5, 3.0, 4.5, 6.0, 7.5, 9.0])
    # the issue's rule: 0.8 of the knee, rounded down to whole cycles of the ten pairs in the
    # 51 s window: 100 a window, whatever a set of six seeds spreads there (the file says what)
    cycles = traffic["rate_rps"] * 51 / 10
    assert cycles == pytest.approx(round(cycles), abs=1e-4)
    assert round(cycles) == int(0.8 * traffic["knee_rps"] * 51 / 10) == 10
    assert int(traffic["rate_rps"] * 51) == 100
    assert traffic["rate_rps"] <= 0.8 * traffic["knee_rps"] == pytest.approx(2.08)
    assert str(traffic["knee_rps"]) in traffic["rate"]
    for reading in ("4.59 %", "6.25 %", "4.94 %"):
        assert reading in traffic["rate"], reading
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0
    # the traced sub-window opens where the last whole cycle's burst lands, and is shorter than
    # the other serve cells' 1.5 s (the file's "trace" says what the profiler's stop costs here)
    interval = 1 / traffic["rate_rps"]
    assert traffic["trace_from"] * 51 == pytest.approx(90 * interval, abs=1e-3)
    assert traffic["trace_seconds"] == 1.0 and "60 s" in traffic["trace"]
    # every due time of the schedule is the same in every run, and only the ids are the seed's
    from benchmark.traffic import serve_open_loop

    params = {**traffic, "vocab_size": config["vocab_size"]}
    one, other = (serve_open_loop.schedule(params, seed, 51.0) for seed in (1, 2147483659))
    assert [r["due"] for r in one] == [r["due"] for r in other]
    assert [r["prompt"] for r in one] != [r["prompt"] for r in other]
    assert all(0 <= t < 50176 for r in other for t in r["prompt"])
    measured = [r for r in one if r["measured"]]
    assert len(measured) == round(cycles) * 10
    burst = [r["due"] for r in measured if 90 <= r["index"] < 94]
    start = traffic["trace_from"] * 51
    assert np.allclose(burst, [start + i * 0.2 * interval for i in range(4)], atol=1e-3)
    assert max(burst) + 0.5 < start + traffic["trace_seconds"]
    # the gate: the reusable end of 4600 tokens lies in the middle of its last chunk
    n, block, chunk = traffic["gate_prompt_tokens"], engine["block_size"], engine["prefill_chunk"]
    assert n == 4600 and traffic["gate_new_tokens"] >= 16
    reused = (n - 1) // block * block
    assert reused == 4352 and reused % chunk == 256 and n - reused < chunk
    assert -(-n // chunk) == 9 and n + traffic["gate_new_tokens"] <= 8192


def test_a_block_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.program_config({**keys, "tie_word_embeddings": False})
    with pytest.raises(ValueError, match="position_embedding_type"):
        arch.program_config({**keys, "position_embedding_type": "rope"})
    with pytest.raises(ValueError, match="mamba_n_groups"):
        arch.program_config({**keys, "mamba_n_groups": 8})
    with pytest.raises(ValueError, match="mamba_expand"):
        arch.program_config({**keys, "mamba_expand": 4})
    with pytest.raises(ValueError, match="the router chooses some"):
        arch.program_config({**keys, "num_experts_per_tok": 0})
    with pytest.raises(ValueError, match="not among the 72"):
        arch.program_config({**keys, "expert_offset": 40})
    # experts are what this module is for, and the accepted one still refuses them
    from benchmark.models import granitemoehybrid

    with pytest.raises(ValueError, match="num_local_experts"):
        granitemoehybrid.program_config(keys)
    assert arch.program_config(keys).router_experts == 72
