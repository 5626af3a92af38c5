"""MiMo-V2-Flash (``mimo_v2_flash``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in chunks
through the pool's two arenas and the window store, decode that reads a slot's rows,
the same prompt again from the prefix cache with the windows' snapshot), what the
comparison's limit catches, ``attend_work`` and ``experts_work`` by hand, the readers
of the three new metrics on a hand-made run, and the configuration's and the
traffic's files against the catalog's row and the issue. float32 throughout; the
projections are scaled up so that the logits are of order 1."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import mimo_v2_flash as arch
from benchmark.reference import mimo_v2_flash_reference as ref

TINY = bench_helpers.tiny("mimo_v2_flash")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "mimo-v2-flash-serve-reasoning-turns"
FILE = BOOK.root + "/benchmark/configs/mimo-v2-flash-serve-ep16.json"
NEW_METRICS = (
    "extend.window_share", "mimo_v2_flash.attend_roofline", "mimo_v2_flash.experts_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: what each omission reads at this size, as a multiple of the limit it must pass
CAUGHT = {
    "no_sink": 50, "no_value_scale": 50, "window_one_short": 50, "bases_swapped": 2,
    "rotate_all": 5, "no_router_bias": 5, "bf16_scores": 1, "fp8_weights": 20}


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0 if path[-1].key in ("kernel", "wi", "wo", "embedding") else a,
        cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the server, twice: a prompt of 90 tokens in chunks of 32
    (four windows a chunk), then 8 decoded tokens; then the same again, 80 tokens from
    the prefix cache with the four windows' snapshot at that boundary."""
    from ray_tpu.serve import llm

    cfg, program = weights
    server = llm.LLMServer(cfg, params=program, **ENGINE)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, cfg.vocab_size, size=90)]
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


def test_prefill_decode_and_the_prefix_hit_match_the_reference(weights, served, wanted):
    cfg, _ = weights
    _, _, out, again, _, _ = served
    _, want = wanted
    assert out["logits"].shape == (8, cfg.vocab_size) == (8, 256)
    assert float(np.std(want)) > 0.1
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 80)
    assert again["tokens"] == out["tokens"]
    np.testing.assert_array_equal(again["logits"], out["logits"])


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    other = np.asarray(ref.program_logits(program, fed, CONFIG, 8, wrong))
    assert yardstick.logits_error(out["logits"], other) > CAUGHT[wrong] * LIMIT, wrong


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    one_period = {
        **CONFIG, "num_hidden_layers": 4, "hybrid_layer_pattern": [0, 1, 1, 0],
        "moe_layer_freq": [0, 1, 1, 1]}
    assert yardstick.logits_error(
        np.asarray(ref.program_logits(program, fed, one_period, 8)), want) > 50 * LIMIT


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """90 prompt tokens in chunks of 32 + 32 + 26, then 7 decode calls: 4 sliding
    layers (a window of 8) and 3 full ones, 6 expert layers, one lane; the store
    copies the windows for the repeat's prefix hit and for nothing else; nothing is
    gathered for a sliding layer."""
    server, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(
        ("full_", "window_", "moe_", "state_", "cache_"))}
    seen = [t + 1 for t in range(97)]
    assert d["full_keys"] == 3 * sum(seen)
    assert d["window_keys"] == 4 * sum(min(s, 8) for s in seen)
    assert d["moe_tokens"] == 6 * 97
    assert d["window_slots"] == 0 == d["window_slots_outside"]
    # the first two chunks' calls lie in the 64 bucket, later ones in 128: lanes x cache
    assert d["cache_slots"] == 64 + 64 + 128 + 7 * 128
    assert d["state_restores"] == 0 and d["state_bytes_moved"] == 0
    after_again = server.kv_stats()
    assert after_again["state_restores"] - after["state_restores"] == 1
    pool = server._engine.pool
    assert after_again["state_bytes_moved"] == pool.state_bytes == 4 * 8 * 4 * (24 + 16) * 4
    assert pool.layers == 3 and [a.shape[0] for a in pool.arenas] == [3, 3]
    assert [s.shape for s in pool.states] == [(4, 12, 8, 96), (4, 12, 8, 64)]


# -- the readers ----------------------------------------------------------------


def test_the_work_functions_by_hand():
    with open(FILE) as f:
        keys = json.load(f)
    assert arch.pattern(keys) == keys["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert arch.rotary_features(keys) == 64 == ref.rotary_features(keys)
    assert arch.expert_params(keys) == 3 * 4096 * 2048 == 25_165_824
    assert arch.attention_params(keys, False) == 89_128_960
    assert arch.attention_params(keys, True) == 94_371_840
    assert arch.matmul_params(keys) == (
        2 * 89_128_960 + 5 * 94_371_840 + 201_326_592 + 6 * (4096 * 256 + 8 * 25_165_824)
        + 4096 * 19072)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096
    # a decode call of 8 lanes at a context of 10,000: 64 heads x 640 operations a pair;
    # the two full layers read every live row of 4 K/V heads, the five sliding ones a
    # window of 8 K/V heads a lane
    counted = {
        "full_keys": 2 * 8 * 10_000, "window_keys": 5 * 8 * 128, "cache_tokens": 8 * 9_999,
        "calls": {"decode": {"lanes_used": 8}, "prefill": {"tokens": 0}}}
    work = arch.attend_work(keys, counted)
    assert work["flops"] == 2 * 320 * 64 * (160_000 + 5_120)
    assert work["bytes"] == 2 * 320 * (2 * 4 * 79_992 + 5 * 8 * 128 * 8)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9          # a decode call reads
    # a chunk of 512 at 8,192: its own rows in the sliding layers, every pair computed
    chunk = arch.attend_work(keys, {
        "full_keys": 2 * sum(range(8193, 8705)), "window_keys": 5 * 512 * 128,
        "cache_tokens": 8192, "calls": {"prefill": {"tokens": 512}}})
    assert chunk["bytes"] == 2 * 320 * (2 * 4 * 8192 + 5 * 8 * 512)
    assert chunk["flops"] / 197e12 > chunk["bytes"] / 819e9        # a chunk computes
    # the experts: 2 operations a parameter and pair, an expert's weights a hit
    experts = arch.experts_work(keys, {"moe_assignments": 1536, "moe_experts_hit": 96})
    assert experts == {"flops": 2.0 * 25_165_824 * 1536, "bytes": 2.0 * 25_165_824 * 96}
    assert arch.attend_work(keys, {}) == {"flops": 0.0, "bytes": 0.0}


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    traced = {
        "full_keys": 60_000_000, "window_keys": 4_000_000, "cache_tokens": 3_000_000,
        "moe_tokens": 30_000, "moe_assignments": 15_000, "moe_experts_hit": 3_000,
        "moe_load_max": 4_000, "steps": 120, "phase_n": {"dispatch": 125},
        "calls": {
            "decode": {"n": 120, "lanes_used": 500}, "prefill": {"n": 5, "tokens": 2_500}},
    }
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            **{k: 40 * v for k, v in traced.items() if isinstance(v, int)},
            "phase_s": {"step": 40.0}, "phase_n": {"dispatch": 5000}, "traced": traced},
        "trace": {
            "busy_s": 1.25, "window_s": 1.5, "engine": {"steps": 120, "in_step_s": 1.4},
            "ops_by_scope": [
                ["extend.moe.experts", 0.5], ["extend.attention", 0.3],
                ["extend.attention.window", 0.15], ["extend.dense", 0.1],
                ["extend.moe.route", 0.05], ["extend.logits", 0.05], ["(no scope)", 0.1],
            ],
            "ops_by_kernel": [["fusion", 0.7], ["gmm", 0.45], ["masked_attention", 0.02]],
        },
    }


def test_the_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.window_share"](run) == pytest.approx(100 * 0.15 / 1.25)
    # the traced steps' own counts, unscaled, over the seconds under both attention scopes
    flops = 2 * 320 * 64 * 64_000_000
    moved = 2 * 320 * (2 * 4 * 3_000_000 + 5 * 8 * (128 * 500 + 2_500))
    assert moved / 819e9 > flops / 197e12
    assert read["mimo_v2_flash.attend_roofline"](run) == pytest.approx(
        100 * moved / 819e9 / (0.3 + 0.15))
    assert 0 < read["mimo_v2_flash.attend_roofline"](run) < 100
    weights = 2 * 25_165_824 * 3_000
    assert weights / 819e9 > 2 * 25_165_824 * 15_000 / 197e12
    assert read["mimo_v2_flash.experts_roofline"](run) == pytest.approx(
        100 * weights / 819e9 / 0.5)
    assert 0 < read["mimo_v2_flash.experts_roofline"](run) < 100
    # the accepted readers this cell is listed under read it as they stand
    assert BOOK.reader("extend.attention_share")(run) == pytest.approx(100 * 0.3 / 1.25)
    assert BOOK.reader("extend.moe_share")(run) == pytest.approx(100 * 0.55 / 1.25)
    # and those of other architectures' layers find nothing here
    for name in ("extend.linear_share", "extend.ssm_share", "extend.index_share",
                 "extend.latent_share", "minicpm_sala.sparse_roofline", "mla.attend_roofline"):
        assert BOOK.reader(name)(run) is None, name
    # a run of a program without the counters, the record or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    untraced = {**run, "counters": {k: v for k, v in run["counters"].items() if k != "traced"}}
    no_scopes = {**run, "trace": {
        **run["trace"], "ops_by_scope": [["extend.mlp", 1.0]], "ops_by_kernel": [["fusion", 1.0]]}}
    assert all(read[n]({}) is None for n in NEW_METRICS)
    assert all(read[n](bare) is None for n in NEW_METRICS[1:])     # the share reads scopes alone
    for name in NEW_METRICS[1:]:
        assert read[name](untraced) is None and read[name](no_scopes) is None
    assert read["extend.window_share"](no_scopes) is None
    # another program's traced steps (Command A+'s: experts and attention, no window
    # store): the window's readers find nothing, whatever its expert counters say
    other = {**run, "trace": {**run["trace"], "ops_by_scope": [
        ["extend.moe.experts", 0.5], ["extend.attention", 0.3]]}}
    other["counters"] = {**run["counters"], "traced": {
        k: v for k, v in run["counters"]["traced"].items() if not k.endswith("_keys")}}
    assert read["extend.window_share"](other) is None
    assert read["mimo_v2_flash.attend_roofline"](other) is None


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_its_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    assert config["source"] == row["source_url"]
    assert config["model_type"] == row["config"]["model_type"] == "mimo_v2_flash"
    cut = {
        "num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
        "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 16, "vocab_size": 19072}
    for key, value in row["config"].items():
        assert published[key] == value, key
        assert config[key] == cut.get(key, value), key
    assert set(config["reduced"]) == set(cut) | {"param_dtype"}
    assert (published["param_dtype"], config["param_dtype"]) == ("float32", "bfloat16")
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the cut is the published layers 0 and 6..11: a whole period in the published ratio
    assert published["hybrid_layer_pattern"][:1] + published["hybrid_layer_pattern"][6:12] == (
        config["hybrid_layer_pattern"])
    assert published["moe_layer_freq"][:7] == config["moe_layer_freq"]
    assert published["hybrid_layer_pattern"][1:6] == [1, 1, 1, 1, 0]      # a layer short
    assert "one sliding layer short" in config["published_why"]
    # what the harness hands the architecture says what the lists say
    assert [int(x) for x in config["layer_pattern"].split(",")] == config["hybrid_layer_pattern"]
    assert config["router_experts"] == published["n_routed_experts"] == 256
    assert config["published_num_hidden_layers"] == published["num_hidden_layers"] == 48
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert (config["n_routed_experts"], config["expert_offset"]) == (16, 0)
    assumed = config["assumed"]
    assert "4.0" in assumed["attention_sink_bias"] and config["attention_sink_bias_std"] == 4.0
    assert "0.01" in assumed["e_score_correction_bias"]
    assert config["e_score_correction_bias_std"] == 0.01
    for said in ("RMSNorm", "pre-norm"):
        assert said in assumed["block"], said
    assert "t - 128 < s <= t" in assumed["sliding_mask"]
    assert "unused" in assumed["attention_chunk_size"]
    assert "3,276,800 B a sequence" in assumed["window_store"] and "ring" in assumed["window_store"]
    departures = " ".join(config["departures"])
    for said in ("multi-token-prediction", "32768 of the model's 262144", "expert exchange",
                 "random from --seed"):
        assert said in departures, said
    cfg = arch.program_config(manifest.published_keys(config))
    assert list(map(int, cfg.sliding_layers)) == config["hybrid_layer_pattern"]
    assert (cfg.period, cfg.periods, cfg.window_layers, cfg.cache_layers) == (6, 1, 5, 2)
    assert (cfg.embed_dim, cfg.num_heads, cfg.head_dim, cfg.v_dim, cfg.kv_heads,
            cfg.sliding_kv_heads, cfg.rotary_dim, cfg.sliding_window, cfg.mlp_dim,
            cfg.expert_dim, cfg.router_experts, cfg.num_experts, cfg.experts_per_token,
            cfg.vocab_size) == (
        4096, 64, 192, 128, 4, 8, 64, 128, 16384, 2048, 256, 16, 8, 19072)
    assert (cfg.rope_base, cfg.sliding_rope_base, cfg.value_scale, cfg.routed_scale,
            cfg.norm_eps) == (5e6, 1e4, 0.707, 1.0, 1e-5)
    assert cfg.cache_arrays == ((1, 768), (1, 512))
    assert cfg.state_chunk == 128 and config["engine"]["block_size"] % 128 == 0
    assert "5 sliding layers" in arch.describe(cfg) and "16 held of 256" in arch.describe(cfg)
    with pytest.raises(ValueError, match="not one with"):
        arch.program_config({**manifest.published_keys(config), "add_full_attention_sink_bias": True})
    with pytest.raises(ValueError, match="K/V heads alone"):
        arch.program_config({**manifest.published_keys(config), "swa_head_dim": 128})
    # 3.43 B parameters = 6.86 GB in bfloat16: the issue's count and the file's arithmetic
    assert cfg.num_params() == 3_429_955_392
    assert "3,429,955,392 parameters = 6.86 GB" in config["deployment"]
    for part in ("89,128,960", "94,371,904", "290,463,744", "498,082,112", "492,839,168",
                 "78,118,912", "ep16 x pp8"):
        assert part in config["deployment"], part
    assert config["reference"]["module"] == "mimo_v2_flash_reference"
    assert config["reference"]["max_logits_error"] == 0.03
    why = config["reference"]["why"]
    for caught in ("rotate_all", "bases_swapped", "no_value_scale", "no_sink", "no_router_bias"):
        assert caught in why.split("WHAT IT DOES NOT CATCH")[0], caught
    for missed in ("window_one_short", "bf16_scores"):
        assert missed in why.split("WHAT IT DOES NOT CATCH")[1], missed
    engine = config["engine"]
    weights = 2 * cfg.num_params()
    assert weights >= 0.25 * 16.91e9                            # the floor on weights alone
    snapshot = sum(layers * int(np.prod(shape)) * 2 for layers, shape, _ in cfg.state_arrays)
    assert snapshot == 3_276_800
    resident = weights + engine["num_blocks"] * engine["block_size"] * 5120 + (
        engine["state_slots"] * snapshot)
    assert resident >= 0.60 * 16_909_336_064                    # weights + pool + slots
    assert engine["lane_buckets"] == [1, 2, 4, 8] and engine["prefill_token_buckets"] == [512]
    assert engine["cache_buckets"][-1] == 32768 and engine["prefill_chunk"] == 512
    stated = config["compiled_bytes_per_device"]
    assert stated["decode"]["shape"] == [8, 1, 32768] and stated["prefill"]["shape"] == [1, 512, 32768]
    assert 0.60 * 16_909_336_064 <= stated["built_peak_bytes"] <= stated["peak_bytes_in_use"]
    assert stated["peak_bytes_in_use"] <= 16_909_336_064


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "mimo-v2-flash-serve-ep16" and cell.traffic_name == "reasoning-turns"
    assert len(cell.why) <= 200 and "host" in cell.why and "idle" in cell.why
    assert set(NEW_METRICS) | {
        "extend.attention_share", "extend.moe_share", "engine.step_ms", "engine.tokens_per_step",
        "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s",
    } <= {m["name"] for m in cell.per_layer}
    assert "extend.unscoped_share" not in {m["name"] for m in cell.per_layer}
    assert {"request_latency_mean_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    for name in NEW_METRICS:
        (entry,) = (m for m in BOOK.data["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "request_latency_mean_s"
    # in the book once, whatever a later PR appends behind it
    assert [w["name"] for w in BOOK.data["workloads"]].count(CELL) == 1
    assert [c["name"] for c in BOOK.data["configs"]].count(cell.config_name) == 1
    bench_helpers.check_cell(BOOK, CELL)
    prompts, outputs = traffic["prompt_tokens"], traffic["output_tokens"]
    assert prompts == [2048, 6144, 1024, 12288, 3072, 8192, 1536, 15360, 4096, 5120]
    assert outputs == [512, 256, 768, 192, 384, 640, 1024, 256, 320, 448]
    assert (sum(prompts) / 10, sum(outputs) / 10) == (5888.0, 480.0)
    assert min(prompts) == 8 * config["sliding_window"]
    engine = config["engine"]
    longest = max(p + o for p, o in zip(prompts, outputs))
    assert longest == 15616 <= 16384 and 16384 in engine["cache_buckets"]
    offsets = traffic["due_offsets"]
    assert offsets == [float(x) for x in np.random.default_rng(56).uniform(-0.3, 0.3, size=10)]
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0
    # the issue's rule: 0.8 of the knee, rounded down to whole cycles of the ten pairs in
    # the 51 s window and not under two cycles
    cycles = traffic["rate_rps"] * 51 / 10
    assert cycles == pytest.approx(round(cycles), abs=1e-3) and round(cycles) >= 2
    assert round(cycles) == max(2, int(0.8 * traffic["knee_rps"] * 51 / 10))
    assert int(traffic["rate_rps"] * 51) == 10 * round(cycles) >= 20
    assert str(traffic["knee_rps"]) in traffic["rate"] and "rung" in traffic["rate"]
    # the gate: 6144 + 64, 23 blocks reused with the windows' snapshot
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (6144, 64)
    assert (6144 - 1) // 256 * 256 == 5888 and "5888" in traffic["gate"]
    assert (traffic["trace_from"], traffic["trace_seconds"]) == (0.93, 1.0)     # short and late
