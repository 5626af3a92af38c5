"""granite-4.0-h-micro (``granitemoehybrid``): the serving path against the
benchmark's plain reference on seeded random weights at a small size on the CPU
(prefill in chunks through the chunked recurrence and the state store, decode,
the same prompt again from a state snapshot, against the reference's
token-by-token recurrence with no cache), what the comparison's limit catches,
the counters, ``scan_work`` by hand, the readers of the three metrics, and the
configuration's and the traffic's files. float32 throughout; the projections
are scaled up so that the logits are of order 1 and every mixer matters."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import granitemoehybrid as arch
from benchmark.reference import granitemoehybrid_reference as ref

TINY = bench_helpers.tiny("granitemoehybrid")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "granite-4h-micro-serve-chat-tool-turns"
FILE = BOOK.root + "/benchmark/configs/granite-4.0-h-micro-serve.json"
NEW_METRICS = ("extend.ssm_share", "ssm.scan_roofline", "engine.state_copy_ratio")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: what each omission reads at this size, as a multiple of the limit it must pass
CAUGHT = {
    "state_bf16": 2, "no_D": 50, "no_dt_bias": 50, "no_conv_bias": 50,
    "no_residual_multiplier": 50, "plain_attention_scale": 50, "fp8_weights": 50}


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    # the init's 0.02 would leave every logit near 0 and the attention uniform:
    # make the projections matter, and leave a Mamba layer's own parameters,
    # its convolution and the norms' scales as drawn
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 8.0 if path[-1].key in ("kernel", "wi", "wo", "embedding")
        and "conv" not in [getattr(k, "key", None) for k in path] else a,
        cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the engine, twice: a prompt of 60 tokens in chunks
    of 32, then 8 decoded tokens across the 64-token bucket; then the same
    again, 48 tokens from the prefix cache and the state after them from its
    snapshot (the middle of the second chunk)."""
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
    server, prompt, out, again, _, _ = served
    _, want = wanted
    assert out["logits"].shape == want.shape == (8, cfg.vocab_size)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, atol=3e-4, rtol=3e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    # the same prompt again: three blocks of 16 from the prefix cache, the state
    # after them from its snapshot, and the same bits
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"] and np.array_equal(again["logits"], out["logits"])
    # K and V of the two attention layers alone, a row of 2 x 16 each; a slot of
    # state a sequence over the six Mamba layers
    pool = server._engine.pool
    assert [a.shape for a in pool.arenas] == [(2, 64, 16, 1, 32)] * 2
    assert [s.shape for s in pool.states] == [(6, 12, 8, 16, 16), (6, 12, 3, 160)]


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    """The state kept in bfloat16, ``D``, ``dt_bias``, the convolution's bias or
    ``residual_multiplier`` left out, ``head_dim^-0.5`` for
    ``attention_multiplier``, weights a precision below: each is outside what a
    run allows (the state's rounding by the least: it is the smallest)."""
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    off = ref.program_logits(program, fed, CONFIG, 8, wrong=wrong)
    assert yardstick.logits_error(out["logits"], off) > CAUGHT[wrong] * LIMIT


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    shallow = {**CONFIG, "num_hidden_layers": MODEL["num_hidden_layers"] - 4}
    assert yardstick.logits_error(ref.program_logits(program, fed, shallow, 8), want) > 0.1
    assert ref.program_loss(program, np.asarray([fed[:20]]), CONFIG) == pytest.approx(
        float(ref.next_token_loss(ref.program_logits(program, fed[:20], CONFIG, 20), fed[:20])))


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls (the 8th
    token needs no call): 6 Mamba layers, one lane, 9 calls. Since PR 42 a call
    reads and writes a lane's state where it lies and ``extend`` writes the kept
    state itself: no state is copied, and ``state_bytes_moved`` counts a prefix
    hit's restore alone (one snapshot's bytes, the request after this one)."""
    server, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("ssm_", "state_"))}
    assert d["ssm_tokens"] == 6 * (60 + 7) and d["ssm_state_passes"] == 6 * 9
    assert d["state_restores"] == 0 and d["state_snapshots"] == 1
    state = server._engine.pool.state_bytes
    assert state == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert d["state_bytes_moved"] == 0
    after_again = server.kv_stats()
    assert after_again["state_restores"] - after["state_restores"] == 1
    assert after_again["state_bytes_moved"] - after["state_bytes_moved"] == state
    assert after_again["state_slots_in_use"] == after_again["state_snapshots"] == 1


# -- the readers ----------------------------------------------------------------


def test_scan_work_by_hand_for_one_call():
    with open(FILE) as f:
        keys = json.load(f)
    # a decode call of 8 lanes: 36 layers x 8 tokens, 36 x 8 states
    work = arch.scan_work(keys, {"ssm_tokens": 288, "ssm_state_passes": 288})
    assert arch.scan_flops_per_token(keys) == 4 * 64 * 64 * 128 == 2_097_152
    assert work["flops"] == 288 * 2_097_152
    assert work["state_bytes"] == 288 * 2 * 2_097_152
    assert work["bytes"] == work["state_bytes"] + 288 * 2 * (4096 + 256 + 64 + 4096)
    # a chunk of 512 tokens on one lane: the same state, 512 tokens' worth of rows
    chunk = arch.scan_work(keys, {"ssm_tokens": 36 * 512, "ssm_state_passes": 36})
    assert chunk["bytes"] == 36 * (2 * 2_097_152 + 512 * 17024)
    assert chunk["flops"] / 197e12 < chunk["bytes"] / 819e9        # bound by memory either way
    assert arch.scan_work(keys, {})["bytes"] == 0
    assert arch.mamba_params(keys) == 2048 * 8512 + 4096 * 2048
    assert arch.attention_params(keys) == 10_485_760
    assert arch.matmul_params(keys) == (
        36 * arch.mamba_params(keys) + 4 * 10_485_760 + 40 * 50_331_648 + 2048 * 100352)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "ssm_tokens": 9_000_000, "ssm_state_passes": 500_000,
            "state_bytes_moved": 2_306_867_200_000, "phase_s": {"step": 40.0},
            "traced": {"ssm_tokens": 400_000, "ssm_state_passes": 20_000, "steps": 80},
        },
        "trace": {
            "busy_s": 1.25, "window_s": 1.5, "engine": {"steps": 80, "in_step_s": 1.4},
            "ops_by_scope": [
                ["extend.ssm", 0.3], ["extend.ssm.scan", 0.2], ["extend.mlp", 0.5],
                ["extend.attention", 0.05], ["(no scope)", 0.2],
            ],
        },
    }


def test_the_three_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.ssm_share"](run) == pytest.approx(100 * 0.5 / 1.25)
    # the traced steps' own counts, unscaled, over the recurrence's 0.2 s
    moved = 20_000 * 2 * 2_097_152 + 400_000 * 17024
    assert moved / 819e9 > 400_000 * 2_097_152 / 197e12
    assert read["ssm.scan_roofline"](run) == pytest.approx(100 * moved / 819e9 / 0.2)
    assert 0 < read["ssm.scan_roofline"](run) < 100
    # the whole load's copies over what its recurrence had to move
    assert read["engine.state_copy_ratio"](run) == pytest.approx(
        2_306_867_200_000 / (500_000 * 2 * 2_097_152))
    in_place = {**run, "counters": {**run["counters"], "state_bytes_moved": 0}}
    assert read["engine.state_copy_ratio"](in_place) == 0
    # a run of a program without the counters, the record or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    assert read["ssm.scan_roofline"](bare) is None
    assert read["engine.state_copy_ratio"](bare) is None
    untraced = {**run, "counters": {k: v for k, v in run["counters"].items() if k != "traced"}}
    assert read["ssm.scan_roofline"](untraced) is None
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.mlp", 1.0]]}}
    assert read["extend.ssm_share"](no_scopes) is None
    assert read["ssm.scan_roofline"](no_scopes) is None
    assert all(read[n]({}) is None for n in NEW_METRICS)


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_nothing_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert config["source"] == row["source_url"] and config["model_type"] == "granitemoehybrid"
    for key, value in row["config"].items():
        assert config[key] == value and published[key] == value, key
    assert set(config["reduced"]) == {"param_dtype"}
    assert (published["param_dtype"], config["param_dtype"]) == ("float32", "bfloat16")
    assert (config["num_hidden_layers"], config["vocab_size"]) == (40, 100352)
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the harness hands an architecture the top-level scalars: the pattern stands there too
    assert arch.layer_pattern(config["layer_types"]) == {
        "layer_period": config["layer_period"],
        "attention_layer_offset": config["attention_layer_offset"]} == {
        "layer_period": 10, "attention_layer_offset": 5}
    with pytest.raises(ValueError, match="no period"):
        arch.layer_pattern(["mamba", "attention", "attention", "mamba", "mamba"])
    cfg = arch.program_config(manifest.published_keys(config))
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.embed_dim, cfg.mlp_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        2048, 8192, 32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk, cfg.conv_width) == (
        64, 64, 128, 256, 4)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling, cfg.norm_eps) == (12.0, 0.22, 0.015625, 8.0, 1e-5)
    assert cfg.state_dtype == np.float32 and cfg.cache_arrays == ((1, 512), (1, 512))
    # 3.19 B parameters = 6.38 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 3_191_396_096
    assert "3,191,396,096 parameters = 6.38 GB" in config["deployment"]
    for part in ("25,847,232", "50,331,648", "76,182,976", "60,821,504", "205,520,896"):
        assert part in config["deployment"], part
    assert "76,437,504 B a sequence" in config["assumed"]["state_dtype"]
    assert config["assumed"]["init"] and len(config["departures"]) >= 3
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.2
    engine = config["engine"]
    resident = 2 * cfg.num_params() + engine["num_blocks"] * engine["block_size"] * 8192 + (
        engine["state_slots"] * 76_437_504)
    assert 11.5e9 < resident < 12.5e9
    assert config["compiled_bytes_per_device"]["peak_bytes_in_use"] >= 13e9


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "granite-4.0-h-micro-serve"
    assert cell.traffic_name == "chat-tool-turns"
    assert len(cell.why) <= 200
    assert {m["name"] for m in cell.per_layer} == set(NEW_METRICS) | {
        "extend.attention_share", "engine.step_ms", "engine.tokens_per_step",
        "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s"}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    # the book grows a cell a model_config PR: this cell is in it, once, and one cell takes four chips
    assert [w["name"] for w in BOOK.data["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in BOOK.data["workloads"]) == 1
    prompts, outputs = traffic["prompt_tokens"], traffic["output_tokens"]
    assert prompts == [256, 1024, 128, 2048, 512, 4096, 384, 768, 192, 1536]
    assert outputs == [64, 128, 96, 48, 192, 32, 256, 80, 160, 64]
    assert (sum(prompts) / 10, sum(outputs) / 10) == (1094.4, 112.0)
    engine = config["engine"]
    assert max(p + o for p, o in zip(prompts, outputs)) <= engine["cache_buckets"][-1] == 8192
    assert engine["block_size"] == config["mamba_chunk_size"] == 256
    assert engine["prefill_chunk"] == 512 and engine["prefill_lanes"] == 1
    assert engine["lane_buckets"] == [1, 2, 4, 8]
    assert np.allclose(
        traffic["due_offsets"], np.random.default_rng(39).uniform(-0.3, 0.3, size=10))
    # whole cycles of the ten pairs in the 51 s window, the most that 0.8 of the knee allows
    cycles = traffic["rate_rps"] * 51 / 10
    assert cycles == pytest.approx(round(cycles), abs=1e-4)
    assert traffic["rate_rps"] <= 0.8 * traffic["knee_rps"] < (round(cycles) + 1) * 10 / 51
    assert str(traffic["knee_rps"]) in traffic["rate"]
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0 and traffic["trace_from"] == 0.93
    assert 1.2 <= traffic["trace_seconds"] <= 1.5
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
    with pytest.raises(ValueError, match="num_local_experts"):
        arch.program_config({**keys, "num_local_experts": 8})
    with pytest.raises(ValueError, match="mamba_n_groups"):
        arch.program_config({**keys, "mamba_n_groups": 8})
    with pytest.raises(ValueError, match="mamba_expand"):
        arch.program_config({**keys, "mamba_expand": 4})
