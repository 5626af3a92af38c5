"""Kimi-K2-Instruct (``kimi_k2``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in
chunks through the latent cache in the absorbed form, decode, the same prompt
again from the prefix cache, against the reference's expanded form with no
cache), what the comparison's limit catches, YaRN against the closed form, the
router's bias, the 32 shares of 12 experts, the counters, the readers of the
two metrics, and the configuration's file. float32 throughout; the projections
are scaled up so that the logits are of order 1 and the routing matters."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import kimi_k2 as arch
from benchmark.reference import kimi_k2_reference as ref

TINY = bench_helpers.tiny("kimi_k2")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "kimi-k2-serve-long-context"
NEW_METRICS = ("extend.latent_share", "mla.attend_roofline", "kimi_k2.experts_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    # the init's 0.02 would leave every logit near 0 and every score alike: make
    # the projections matter, and leave the norms' scales and the bias as drawn
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the engine, twice: a prompt of 60 tokens in chunks
    of 32, then 8 decoded tokens across the 64-token bucket; then the same
    again, 48 tokens from the prefix cache."""
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


def test_prefill_decode_and_the_prefix_cache_match_the_reference(weights, served, wanted):
    cfg, _ = weights
    server, prompt, out, again, _, _ = served
    _, want = wanted
    assert out["logits"].shape == want.shape == (8, cfg.vocab_size)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, atol=2e-4, rtol=2e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    # the same prompt again: three blocks of 16 from the prefix cache, latent
    # rows and nothing else, and the same bits
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"] and np.array_equal(again["logits"], out["logits"])
    # a cached token is one row: the latent, the rotary key behind it, zeros to 128 lanes
    assert [a.shape for a in server._engine.pool.arenas] == [(4, 64, 16, 1, 128)]


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    """``mscale^2`` left out of the scale, plain rotary, the bias weighing,
    ``routed_scaling_factor`` or the shared expert left out, the latent cached
    un-normed, weights a precision below: each is far outside what a run allows."""
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    off = ref.program_logits(program, fed, CONFIG, 8, wrong=wrong)
    assert yardstick.logits_error(out["logits"], off) > 50 * LIMIT


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    shallow = {**CONFIG, "num_hidden_layers": MODEL["num_hidden_layers"] - 1}
    assert yardstick.logits_error(ref.program_logits(program, fed, shallow, 8), want) > 0.1
    assert ref.program_loss(program, np.asarray([fed[:20]]), CONFIG) == pytest.approx(
        float(ref.next_token_loss(ref.program_logits(program, fed[:20], CONFIG, 20), fed[:20])))


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls (the 8th
    token needs no call): 4 layers of attention, 3 of them expert layers with
    4 of 16 experts held and 4 chosen a token."""
    _, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("moe_", "mla_"))}
    assert d["mla_queries"] == 4 * (60 + 7) and d["moe_tokens"] == 3 * (60 + 7)
    assert d["mla_pairs_absorbed"] == 4 * sum(range(1, 68))
    assert d["mla_pairs_expanded"] == d["mla_rows_expanded"] == 0
    # a quarter of the experts are held here: about a quarter of the pairs
    assert 0.1 * 4 * d["moe_tokens"] < d["moe_assignments"] < 0.5 * 4 * d["moe_tokens"]
    assert 0 < d["moe_experts_hit"] <= 4 * 3 * 9              # 4 held, 3 layers, 9 calls


# -- YaRN and the router -----------------------------------------------------------


def test_the_references_yarn_is_the_closed_form_and_the_programs():
    with open(BOOK.root + "/benchmark/configs/kimi-k2-instruct-serve-ep32.json") as f:
        config = json.load(f)
    freqs = ref.yarn_frequencies(64, float(config["rope_theta"]), config["rope_scaling"])
    own = 50000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freqs[:20], own[:20], rtol=1e-12)
    np.testing.assert_allclose(freqs[20:], own[20:] / 32, rtol=1e-12)
    cfg = arch.program_config(manifest.published_keys(config))
    np.testing.assert_allclose(cfg.rope_frequencies, freqs, rtol=1e-12)
    mscale = 0.1 * math.log(32) + 1
    assert ref.softmax_scale(config) == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert ref.softmax_scale(config) == pytest.approx(cfg.softmax_scale, rel=1e-12)
    assert ref.softmax_scale(config, with_mscale=False) == pytest.approx(192 ** -0.5)
    assert ref.rotation_factor(config) == 1.0                  # cos and sin are not scaled
    # where both correction dimensions coincide the ramp is the 0.001 guard's step
    # (an original context of 6 puts it at -0.14: floor and ceiling both clamp to 0)
    step = ref.yarn_frequencies(
        64, 50000.0, {**config["rope_scaling"], "original_max_position_embeddings": 6})
    share = (own - step) / (own - own / 32)
    assert share[0] == 0 and np.allclose(share[1:], 1.0)
    np.testing.assert_allclose(
        step, arch.program_config(manifest.published_keys(
            {**config, "rope_scaling_original_max_position_embeddings": 6})).rope_frequencies,
        rtol=1e-12)
    # without a group: the base's own
    np.testing.assert_allclose(ref.yarn_frequencies(64, 50000.0, None), own, rtol=1e-12)


def test_the_references_bias_chooses_and_does_not_weigh():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    n = jax.random.normal(keys[0], (300, 32))
    router = 0.3 * jax.random.normal(keys[1], (32, 16))
    bias = 0.05 * jax.random.normal(keys[2], (16,))
    with jax.default_matmul_precision("highest"):
        plain_w, plain_e = ref.route(n, router, jnp.zeros((16,)), 4, 2.827)
        w, e = ref.route(n, router, bias, 4, 2.827)
        weighing, _ = ref.route(n, router, bias, 4, 2.827, wrong="bias_weighs")
        unscaled, _ = ref.route(n, router, bias, 4, 2.827, wrong="no_routed_scale")
    moved = (np.sort(np.asarray(e), -1) != np.sort(np.asarray(plain_e), -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95
    by_expert = lambda w, e: np.take_along_axis(np.asarray(w), np.argsort(np.asarray(e), -1), -1)
    np.testing.assert_allclose(
        by_expert(w, e)[~moved], by_expert(plain_w, plain_e)[~moved], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(unscaled).sum(-1), 1.0, rtol=1e-6)
    assert np.abs(np.asarray(weighing) - np.asarray(w)).max() > 1e-3
    # the program's router is the reference's
    from ray_tpu.models import moe

    got_w, got_e = moe.sigmoid_bias_top_k(n, router, bias, 4, 2.827)
    assert np.array_equal(np.asarray(got_e), np.asarray(e))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(w), rtol=1e-6)


def test_the_32_shares_of_12_experts_add_up_to_the_uncut_layer():
    """Every share's part of the routed sum (384 experts, 12 a chip, the bias
    choosing) and the shared expert, counted once, is what the reference gives
    for the uncut layer."""
    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    n, d, f, routed, k, held = 40, 32, 16, 384, 8, 12
    x = jax.random.normal(keys[0], (n, d))
    router = 0.3 * jax.random.normal(keys[1], (d, routed))
    bias = 0.02 * jax.random.normal(keys[2], (routed,))
    wi = 0.3 * jax.random.normal(keys[3], (routed, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[4], (routed, f, d))
    shared_wi = 0.3 * jax.random.normal(keys[5], (d, 2 * f))
    shared_wo = 0.3 * jax.random.normal(keys[6], (f, d))
    weights, chosen = moe.sigmoid_bias_top_k(x, router, bias, k, 2.827)
    valid = jnp.ones((n,), bool)
    total, pairs = 0.0, 0
    for share in range(routed // held):
        lo = share * held
        part, counters = moe.held_experts_ffn(
            x, weights, chosen, valid, wi[lo:lo + held], wo[lo:lo + held], offset=lo)
        total, pairs = total + part, pairs + int(counters[1])
    assert pairs == n * k
    with jax.default_matmul_precision("highest"):
        total = total + ref.expert(x, shared_wi, shared_wo)
        # the uncut layer, by the reference: one "share" that holds all 384
        want = ref._experts(
            x, {"router": router, "bias": bias, "wi": wi, "wo": wo},
            {"wi": shared_wi, "wo": shared_wo},
            {"num_experts_per_tok": k, "routed_scaling_factor": 2.827}, None)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


# -- the readers ----------------------------------------------------------------


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "mla_queries": 2_000_000, "mla_pairs_absorbed": 8_000_000_000,
            "mla_pairs_expanded": 0, "mla_rows_expanded": 0,
            "cache_tokens": 10_000_000, "phase_s": {"step": 20.0},
            "moe_tokens": 3_000_000, "moe_assignments": 700_000, "moe_experts_hit": 10_000,
            "moe_load_max": 400_000, "phase_n": {"dispatch": 2_000},
            # the recorded steps' own counts: more of them chunks than an eighth of the load's
            "traced": {
                "mla_queries": 300_000, "mla_pairs_absorbed": 1_000_000_000,
                "mla_pairs_expanded": 0, "mla_rows_expanded": 0, "cache_tokens": 1_500_000,
                "moe_tokens": 400_000, "moe_assignments": 90_000, "moe_experts_hit": 1_500,
                "moe_load_max": 50_000, "phase_n": {"dispatch": 200}, "phase_s": {"step": 2.4},
            },
        },
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 50, "in_step_s": 2.5},
            "ops_by_scope": [
                ["extend.attention", 1.0], ["extend.attention.latent", 0.3],
                ["extend.moe.experts", 0.4], ["extend.moe.shared", 0.1], ["(no scope)", 0.2],
            ],
        },
    }


def test_the_experts_reader_reads_a_recorded_run():
    run, read = _recorded_run(), BOOK.reader("kimi_k2.experts_roofline")
    # the recorded steps' own pairs, tokens, hit experts and calls, in 0.4 + 0.1 s
    flops = 2 * 44_040_192 * (90_000 + 400_000)
    moved = 2 * 44_040_192 * (1_500 + 6 * 200)
    assert moved / 819e9 > flops / 197e12                   # the weights bind, as on the chip
    assert read(run) == pytest.approx(100 * moved / 819e9 / 0.5)
    assert 0 < read(run) < 100
    # a run of a program without the counters, the traced record or the scopes: nothing
    assert read({**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}) is None
    assert read({**run, "counters": {**run["counters"], "traced": None}}) is None
    assert read({**run, "trace": {**run["trace"], "ops_by_scope": [["extend.attention", 1.0]]}}) is None


def test_the_expert_layers_required_work():
    with open(BOOK.root + "/benchmark/configs/kimi-k2-instruct-serve-ep32.json") as f:
        keys = json.load(f)
    work = arch.experts_work(keys, {
        "moe_tokens": 12, "moe_assignments": 5, "moe_experts_hit": 3, "moe_load_max": 2,
        "phase_n": {"dispatch": 2}})
    # 5 pairs through a held expert and 12 (token, layer)s through the shared one; 3 held
    # experts' weights and the shared expert's for 2 calls x 6 expert layers (layer 0 has none)
    assert work["flops"] == 2 * 44_040_192 * (5 + 12)
    assert work["bytes"] == 2 * 44_040_192 * (3 + 2 * 6)


def test_the_two_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.latent_share"](run) == pytest.approx(15.0)
    # the recorded steps' own pairs and live slots, in 1.0 s
    flops = 2 * 64 * (576 + 512) * 1e9
    moved = 2 * 576 * 7 * 1.5e6
    at_peak = max(flops / 197e12, moved / 819e9)
    assert read["mla.attend_roofline"](run) == pytest.approx(100 * at_peak / 1.0)
    assert 0 < read["mla.attend_roofline"](run) < 100
    # pairs in the expanded form count their own operations
    some = {**run, "counters": {"traced": {
        **run["counters"]["traced"], "mla_pairs_expanded": 1_000_000_000}}}
    assert read["mla.attend_roofline"](some) == pytest.approx(
        100 * (flops + 2 * 64 * (192 + 128) * 1e9) / 197e12)
    # a run of a program without the counters or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    assert read["mla.attend_roofline"](bare) is None
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.moe.experts", 1.0]]}}
    assert read["kimi_k2.experts_roofline"](no_scopes) is not None
    assert read["extend.latent_share"](no_scopes) is None
    assert read["mla.attend_roofline"](no_scopes) is None
    assert all(read[n]({}) is None for n in NEW_METRICS)


def test_the_latent_attentions_required_work():
    with open(BOOK.root + "/benchmark/configs/kimi-k2-instruct-serve-ep32.json") as f:
        keys = json.load(f)
    work = arch.latent_work(keys, {
        "mla_pairs_absorbed": 10, "mla_pairs_expanded": 7, "cache_tokens": 3})
    assert work["flops"] == 2 * 64 * (576 + 512) * 10 + 2 * 64 * (192 + 128) * 7
    assert work["bytes"] == 1152 * 7 * 3
    assert arch.latent_work(keys, {"mla_pairs_absorbed": 1, "cache_tokens": 0})["flops"] == 139264
    assert arch.expert_params(keys) == 44_040_192 and arch.attention_params(keys) == 101_122_048
    # attention of 7 layers, layer 0's MLP, and router + shared + 8 experts of 6 layers, and the head
    assert arch.matmul_params(keys) == 7 * 101_122_048 + 3 * 7168 * 18432 + 6 * (
        7168 * 384 + 9 * 44_040_192) + 7168 * 20480
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_three_keys_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2-Instruct")
    assert config["source"] == row["source_url"] and config["model_type"] == "kimi_k2"
    cut = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
        if key != "model_type":
            assert published[key] == value, key
    assert set(config["reduced"]) == cut
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        7, 12, 20480)
    assert (config["router_experts"], config["expert_offset"]) == (384, 0)
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the harness hands an architecture the top-level scalars: YaRN's stand there too
    assert {group: config[flat] for flat, group in arch.YARN.items()} == config["rope_scaling"]
    cfg = arch.program_config(manifest.published_keys(config))
    assert (cfg.embed_dim, cfg.num_heads, cfg.q_rank, cfg.kv_rank) == (7168, 64, 1536, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.mlp_dim) == (128, 64, 128, 18432)
    assert (cfg.router_experts, cfg.num_experts, cfg.experts_per_token, cfg.expert_dim) == (
        384, 12, 8, 2048)
    assert (cfg.dense_layers, cfg.expert_layers, cfg.shared_experts) == (1, 6, 1)
    assert cfg.cache_arrays == ((1, 640),) and cfg.routed_scale == 2.827
    assert cfg.bias_std == config["e_score_correction_bias_std"] > 0
    # 4.850 B parameters = 9.70 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 4_849_591_552
    assert "4,849,591,552 parameters = 9.70 GB" in config["deployment"]
    assert "32 that share each layer" in config["deployment"]
    assert config["assumed"]["e_score_correction_bias"] and len(config["departures"]) >= 4
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.2


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS) | {
        "extend.moe_share", "extend.attention_share", "engine.step_ms", "engine.tokens_per_step",
        "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s"}
    assert not {"moe.experts_roofline", "engine.window_outside_share", "extend.index_share",
                "sparse_attention.roofline"} & {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    full = [3072, 8192, 4096, 16384, 6144, 24576, 5120, 12288]
    # the issue's cycle, or three quarters of it in whole chunks of 512
    assert traffic["prompt_tokens"] in (full, [p * 3 // 4 // 512 * 512 for p in full])
    assert traffic["output_tokens"] == [256, 96, 384, 64, 192, 48, 128, 96]
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (6144, 64)
    engine = config["engine"]
    longest = max(p + o for p, o in zip(traffic["prompt_tokens"], traffic["output_tokens"]))
    assert longest <= engine["cache_buckets"][-1] == 32768
    assert engine["block_size"] == 256 and engine["prefill_chunk"] == 512
    assert engine["prefill_lanes"] == 1 and engine["num_blocks"] * 256 == 163840
    assert np.allclose(
        traffic["due_offsets"], np.random.default_rng(34).uniform(-0.3, 0.3, size=8))
    cycles = traffic["rate_rps"] * 51 / 8
    # whole cycles of the eight pairs in the 51 s window, the most that 0.8 of the knee allows
    assert cycles == pytest.approx(round(cycles), abs=1e-4) and round(cycles) in (3, 4, 5)
    assert traffic["rate_rps"] <= 0.8 * traffic["knee_rps"] < (round(cycles) + 1) * 8 / 51
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0 and 1.2 <= traffic["trace_seconds"] <= 2.0


def test_a_block_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.program_config({**keys, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="topk_method"):
        arch.program_config({**keys, "topk_method": "greedy"})
    with pytest.raises(ValueError, match="n_group"):
        arch.program_config({**keys, "n_group": 8})
    with pytest.raises(ValueError, match="does not scale cos and sin"):
        arch.program_config({**keys, "rope_scaling_mscale": 0.707})
    with pytest.raises(ValueError, match="one key and one value a query head"):
        arch.program_config({**keys, "num_key_value_heads": 8})
