"""MiniCPM-SALA (``minicpm_sala``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in chunks
through the pool's three arenas and the state store, decode that gathers its blocks'
rows, the same prompt again from the prefix cache), what the comparison's limit
catches, ``linear_work`` and ``sparse_work`` by hand, the readers of the four new
metrics and of Keye's two on a hand-made run, and the configuration's and the
traffic's files against the catalog's row and the issue. float32 throughout; the
projections are scaled up so that the logits are of order 1."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import minicpm_sala as arch
from benchmark.reference import minicpm_sala_reference as ref

TINY = bench_helpers.tiny("minicpm_sala")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "minicpm-sala-serve-long-documents"
FILE = BOOK.root + "/benchmark/configs/minicpm-sala-serve-pp2.json"
NEW_METRICS = (
    "extend.linear_share", "minicpm_sala.linear_roofline", "minicpm_sala.sparse_roofline",
    "minicpm_sala.selected_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: what each omission reads at this size, as a multiple of the limit it must pass
CAUGHT = {
    "no_decay": 50, "rope_in_sparse": 5, "no_rope_in_linear": 50, "no_output_gate": 50,
    "no_output_norm": 50, "no_depth_scale": 50, "dense_always": 5, "window_only": 5,
    "one_selection_for_both_kv_heads": 5, "mean_pooled_blocks": 5, "fp8_weights": 20}


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
    (queries from 32 on select), then 8 decoded tokens; then the same again, 80 tokens
    from the prefix cache with their compressed keys and the state after them."""
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
    one_period = {**CONFIG, "num_hidden_layers": 4}
    assert yardstick.logits_error(
        np.asarray(ref.program_logits(program, fed, one_period, 8)), want) > 50 * LIMIT


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """90 prompt tokens in chunks of 32 + 32 + 26, then 7 decode calls: 6 lightning
    layers and 2 sparse ones, one lane, 10 calls; queries 32 .. 96 select; the store
    copies a state for the repeat's prefix hit and for nothing else."""
    server, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("sparse_", "linear_", "state_"))}
    assert d["linear_tokens"] == 6 * 97 and d["linear_state_passes"] == 6 * 10
    assert d["sparse_queries"] == 2 * (97 - 32)
    # a query at t reads t + 1 keys densely, K/V head by K/V head
    assert d["sparse_keys_causal"] == 2 * 2 * sum(t + 1 for t in range(32, 97))
    # ... and sees the compressed keys that end at or before it: (t - 3) // 2 + 1
    assert d["sparse_keys_scored"] == 2 * 2 * sum((t - 3) // 2 + 1 for t in range(32, 97))
    # block 0, the window's two or three and the two best: 5 or 6 blocks of 8, cut at t
    assert 2 * 2 * 65 * 33 <= d["sparse_keys_attended"] <= 2 * 2 * 65 * 48
    # the first chunk's calls lie in the 64 bucket, later ones in 128
    assert d["sparse_slots_gathered"] == 2 * (64 + 64 + 128 + 7 * 128)
    assert 0 < d["sparse_slots_read"] < d["sparse_slots_gathered"]
    assert d["state_restores"] == 0 and d["state_bytes_moved"] == 0
    after_again = server.kv_stats()
    assert after_again["state_restores"] - after["state_restores"] == 1
    assert after_again["state_bytes_moved"] == server._engine.pool.state_bytes == 6 * 4 * 16 * 16 * 4


# -- the readers ----------------------------------------------------------------


def test_the_work_functions_by_hand():
    with open(FILE) as f:
        keys = json.load(f)
    assert arch.mixers(keys) == keys["mixer_types"] and arch.mixers(keys).count("minicpm4") == 4
    assert arch.linear_flops_per_token(keys) == 4 * 32 * 128 * 128 == 2_097_152
    # a decode call of 4 lanes: 12 layers x 4 tokens, 48 states read and written
    work = arch.linear_work(keys, {"linear_tokens": 48, "linear_state_passes": 48})
    assert work["flops"] == 2_097_152 * 48
    assert work["state_bytes"] == 48 * 2 * 2_097_152
    assert work["bytes"] == work["state_bytes"] + 48 * 32_768
    assert work["flops"] / 197e12 < work["bytes"] / 819e9          # memory-bound
    # a chunk of 512 tokens: one state pass a layer, 512 tokens' recurrence
    chunk = arch.linear_work(keys, {"linear_tokens": 12 * 512, "linear_state_passes": 12})
    assert chunk["bytes"] == 12 * 2 * 2_097_152 + 12 * 512 * 32_768
    # a chunk at a 24576 context: 512 queries x 4 layers, 1535 visible compressed keys,
    # 6272 keys attended a K/V head, every gathered slot's compressed key read
    counted = {
        "sparse_keys_scored": 4 * 512 * 2 * 1535, "sparse_keys_attended": 4 * 512 * 2 * 6272,
        "sparse_slots_read": 4 * 9000, "cache_tokens": 24576}
    sparse = arch.sparse_work(keys, counted)
    assert sparse["flops"] == 2 * 16 * 128 * counted["sparse_keys_scored"] + (
        4 * 16 * 128 * counted["sparse_keys_attended"])
    assert sparse["bytes"] == 512 * 4 * 24576 / 16 + 1024 * 4 * 9000
    assert arch.sparse_params(keys) == 52_428_800 and arch.linear_params(keys) == 83_886_080
    assert arch.matmul_params(keys) == (
        4 * 52_428_800 + 12 * 83_886_080 + 16 * 201_326_592 + 4096 * 73448)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "sparse_queries": 2_000_000, "sparse_keys_scored": 3_000_000_000,
            "sparse_keys_attended": 20_000_000_000, "sparse_keys_causal": 60_000_000_000,
            "sparse_slots_read": 40_000_000, "sparse_slots_gathered": 100_000_000,
            "linear_tokens": 6_000_000, "linear_state_passes": 40_000, "cache_tokens": 25_000_000,
            "phase_s": {"step": 40.0}, "phase_n": {"dispatch": 2500},
            "traced": {
                "sparse_queries": 40_000, "sparse_keys_scored": 60_000_000,
                "sparse_keys_attended": 400_000_000, "sparse_keys_causal": 1_200_000_000,
                "sparse_slots_read": 800_000, "cache_tokens": 500_000,
                "linear_tokens": 120_000, "linear_state_passes": 900, "steps": 60,
                "phase_n": {"dispatch": 70},
            },
        },
        "trace": {
            "busy_s": 1.25, "window_s": 1.5, "engine": {"steps": 60, "in_step_s": 1.4},
            "ops_by_scope": [
                ["extend.mlp", 0.6], ["extend.linear", 0.3], ["extend.linear.scan", 0.05],
                ["extend.attention", 0.1], ["extend.attention.index", 0.03],
                ["extend.attention.select", 0.02], ["extend.logits", 0.05], ["(no scope)", 0.1],
            ],
            "ops_by_kernel": [["fusion", 0.9], ["masked_attention", 0.06], ["copy-done", 0.1]],
        },
    }


def test_the_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.linear_share"](run) == pytest.approx(100 * 0.35 / 1.25)
    # the traced steps' own counts, unscaled, over the seconds under the recurrence's scope
    moved = 900 * 2 * 2_097_152 + 120_000 * 32_768
    done = 2_097_152 * 120_000
    assert moved / 819e9 > done / 197e12
    assert read["minicpm_sala.linear_roofline"](run) == pytest.approx(100 * moved / 819e9 / 0.05)
    assert 0 < read["minicpm_sala.linear_roofline"](run) < 100
    flops = 16 * 128 * (2 * 60_000_000 + 4 * 400_000_000)
    read_bytes = 512 * 4 * 500_000 / 16 + 1024 * 800_000
    assert flops / 197e12 > read_bytes / 819e9
    assert read["minicpm_sala.sparse_roofline"](run) == pytest.approx(
        100 * flops / 197e12 / (0.1 + 0.03 + 0.02))
    assert 0 < read["minicpm_sala.sparse_roofline"](run) < 100
    # the whole load's counters: a third of the causal pairs attended
    assert read["minicpm_sala.selected_share"](run) == pytest.approx(100 / 3)
    # Keye's two readers read this program as they stand, and open no file of Keye's
    assert BOOK.reader("extend.index_share")(run) == pytest.approx(100 * 0.05 / 1.25)
    assert BOOK.reader("engine.sparse_unread_share")(run) == pytest.approx(60.0)
    assert BOOK.reader("extend.attention_share")(run) == pytest.approx(100 * 0.1 / 1.25)
    for name in ("extend.index_share", "engine.sparse_unread_share"):
        with open(f"{BOOK.root}/benchmark/metrics/{name}.py") as f:
            assert "keye" not in f.read().replace("Keye", "").lower()
    # and the readers of another architecture's recurrence find nothing here (Keye's
    # roofline would read these names at Keye's widths: its list keeps it to Keye's cell)
    assert CELL not in next(
        m for m in BOOK.data["per_layer"] if m["name"] == "sparse_attention.roofline")["workloads"]
    for name in ("ssm.scan_roofline", "extend.ssm_share", "granite_4h_small.scan_roofline"):
        assert BOOK.reader(name)(run) is None, name
    # a run of a program without the counters, the record or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    untraced = {**run, "counters": {k: v for k, v in run["counters"].items() if k != "traced"}}
    no_scopes = {**run, "trace": {
        **run["trace"], "ops_by_scope": [["extend.mlp", 1.0]], "ops_by_kernel": [["fusion", 1.0]]}}
    assert all(read[n]({}) is None for n in NEW_METRICS)
    assert all(read[n](bare) is None for n in NEW_METRICS[1:])     # the share reads scopes alone
    for name in ("minicpm_sala.linear_roofline", "minicpm_sala.sparse_roofline"):
        assert read[name](untraced) is None and read[name](no_scopes) is None
    assert read["extend.linear_share"](no_scopes) is None
    # traced steps that held no query past dense_len: no share of the selection's roofline
    dense = {**run, "counters": {**run["counters"], "traced": {
        **run["counters"]["traced"], "sparse_keys_causal": 0, "sparse_keys_scored": 0}}}
    assert read["minicpm_sala.sparse_roofline"](dense) is None


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_three_keys_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    assert config["source"] == row["source_url"]
    assert config["model_type"] == row["config"]["model_type"] == "minicpm_sala"
    period = ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    cut = {"num_hidden_layers": 16, "mixer_types": period * 4}
    for key, value in row["config"].items():
        assert published[key] == value, key
        assert config[key] == cut.get(key, value), key
    assert set(config["reduced"]) == set(cut) | {"param_dtype"}
    assert (published["param_dtype"], config["param_dtype"]) == ("float32", "bfloat16")
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the published list is not periodic, and the file says so
    assert row["config"]["mixer_types"].count("minicpm4") == 8
    assert [i for i, m in enumerate(published["mixer_types"]) if m == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert "NOT periodic" in config["published_why"] and "not periodic" in config["reduced"][
        "mixer_types"]
    # what the harness hands the architecture says what the lists and the group say
    assert config["mixer_period"].split(",") == period
    assert config["published_num_hidden_layers"] == published["num_hidden_layers"] == 32
    assumed = config["assumed"]
    assert assumed["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "init_blocks": 1,
        "window_size": 2048, "topk": 64, "dense_len": 8192}
    for size in arch.SPARSE_SIZES:
        assert config["sparse_" + size] == assumed["sparse_config"][size], size
    assert "MiniCPM4" in assumed["sparse_config_why"] and "top-64" in assumed["sparse_config_why"]
    assert "2401.04658" in assumed["lightning_decay"] and "buffer" in assumed["lightning_decay"]
    assert "qk_norm applies to both mixers" in assumed["norms_and_gates"]
    departures = " ".join(config["departures"])
    for said in ("per QUERY", "exact float32 softmax", "embedding and the head both",
                 "random from --seed"):
        assert said in departures, said
    cfg = arch.program_config(manifest.published_keys(config))
    assert list(cfg.mixer_types) == config["mixer_types"] and (cfg.period, cfg.periods) == (4, 4)
    assert (cfg.embed_dim, cfg.mlp_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.linear_heads, cfg.linear_head_dim, cfg.vocab_size) == (
        4096, 16384, 32, 2, 128, 32, 128, 73448)
    assert (cfg.kernel_size, cfg.kernel_stride, cfg.select_block, cfg.init_blocks,
            cfg.window_size, cfg.topk, cfg.dense_len) == (32, 16, 64, 1, 2048, 64, 8192)
    assert (cfg.scale_emb, cfg.scale_depth, cfg.depth_layers, cfg.dim_model_base,
            cfg.norm_eps, cfg.rope_base) == (12.0, 1.4, 32, 256, 1e-6, 10000.0)
    assert cfg.residual_scale == pytest.approx(0.2475, abs=1e-4)
    assert cfg.state_dtype == np.float32
    assert cfg.cache_arrays == ((1, 256), (1, 256), (1, 256, 16)) and cfg.cache_layers == 4
    assert cfg.linear_chunk == config["engine"]["block_size"] == 256
    assert "4 block-sparse layers" in arch.describe(cfg) and "depth 16 of 32" in arch.describe(cfg)
    with pytest.raises(ValueError, match="not one with"):
        arch.program_config({**manifest.published_keys(config), "attn_use_rope": True})
    # 5.04 B parameters = 10.08 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 5_039_400_832
    assert "5,039,400,832 parameters = 10.08 GB" in config["deployment"]
    for part in ("253,763,840", "285,221,280", "601,686,016", "9,477,111,552", "59.6 %"):
        assert part in config["deployment"], part
    assert "25,165,824 B a sequence" in assumed["state_dtype"] and "4,224 B" in assumed["state_dtype"]
    assert config["reference"]["module"] == "minicpm_sala_reference"
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.2
    engine = config["engine"]
    weights = 2 * cfg.num_params()
    assert weights >= 0.25 * 16.91e9 * 2                        # the floor on weights alone
    resident = weights + engine["num_blocks"] * engine["block_size"] * 4224 + (
        engine["state_slots"] * 25_165_824)
    assert 12.0e9 < resident < 13.5e9
    assert engine["lane_buckets"] == [1, 2, 4] and engine["prefill_token_buckets"] == [512]
    assert engine["cache_buckets"] == [4096, 8192, 16384, 32768]
    stated = config["compiled_bytes_per_device"]
    assert stated["decode"]["shape"] == [4, 1, 32768] and stated["prefill"]["shape"] == [1, 512, 32768]
    assert 12.5e9 <= stated["peak_bytes_in_use"] <= 16.5e9


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "minicpm-sala-serve-pp2" and cell.traffic_name == "long-documents"
    assert len(cell.why) <= 200 and "half" in cell.why
    assert {m["name"] for m in cell.per_layer} == set(NEW_METRICS) | {
        "extend.attention_share", "extend.index_share", "engine.sparse_unread_share",
        "engine.step_ms", "engine.tokens_per_step", "device.idle_share.serve",
        "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s"}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    for name in NEW_METRICS:
        (entry,) = (m for m in BOOK.data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "request_latency_mean_s"
    # in the book once, whatever a later PR appends behind it; one cell takes four chips
    assert [w["name"] for w in BOOK.data["workloads"]].count(CELL) == 1
    assert [c["name"] for c in BOOK.data["configs"]].count(cell.config_name) == 1
    assert sum(w["chips"] == 4 for w in BOOK.data["workloads"]) == 1
    prompts, outputs = traffic["prompt_tokens"], traffic["output_tokens"]
    assert prompts == [9216, 12288, 10240, 16384, 8704, 24576, 11264, 14336]
    assert outputs == [128, 64, 192, 48, 96, 32, 256, 64]
    assert (sum(prompts) / 8, sum(outputs) / 8) == (13376.0, 110.0)
    assert min(prompts) > config["sparse_dense_len"] == 8192
    engine = config["engine"]
    assert max(p + o for p, o in zip(prompts, outputs)) == 24608 <= engine["cache_buckets"][-1]
    offsets = traffic["due_offsets"]
    assert offsets == [float(x) for x in np.random.default_rng(52).uniform(-0.3, 0.3, size=8)]
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0
    # the issue's rule: 0.8 of the knee, rounded down to whole cycles of the eight pairs in
    # the 51 s window and not under two cycles
    cycles = traffic["rate_rps"] * 51 / 8
    assert cycles == pytest.approx(round(cycles), abs=1e-3) and round(cycles) >= 2
    assert round(cycles) == max(2, int(0.8 * traffic["knee_rps"] * 51 / 8))
    assert str(traffic["knee_rps"]) in traffic["rate"] and "rung" in traffic["rate"]
    # the gate: 1.5 x dense_len, 47 blocks reused
    assert traffic["gate_prompt_tokens"] == 12288 == 3 * config["sparse_dense_len"] // 2
    assert traffic["gate_new_tokens"] == 16
    assert (12288 - 1) // 256 * 256 == 12032 and "12032" in traffic["gate"]
    assert (traffic["trace_from"], traffic["trace_seconds"]) == (0.93, 1.5)
