"""LongCat-Flash-Chat (``longcat_flash``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in chunks through
the cache of two rows a layer in the absorbed form, decode through the block table and
through the gather, the same prompt again from the prefix cache, against the reference's
expanded form with no cache), what the comparison's limit catches, the router, the
counters, the readers of the three metrics, and the configuration's file. float32
throughout; the projections are scaled up so that the logits are of order 1 and the
routing matters."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import longcat_flash as arch
from benchmark.reference import longcat_flash_reference as ref

TINY = bench_helpers.tiny("longcat_flash")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "longcat-flash-serve-agent-turns"
FILE = BOOK.root + "/benchmark/configs/longcat-flash-chat-serve-ep32.json"
NEW_METRICS = (
    "longcat_flash.experts_roofline", "longcat_flash.attend_roofline", "longcat_flash.zero_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    # the init's 0.02 would leave every logit near 0 and every score alike: make
    # the projections matter, and leave the norms' scales and the bias as drawn
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, cfg.init_params(3))
    return cfg, program


def _served(weights):
    """One request through the engine, twice: a prompt of 60 tokens in chunks of 32,
    then 8 decoded tokens across the 64-token bucket; then the same again, 48 tokens
    from the prefix cache."""
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
def served(weights):
    return _served(weights)


@pytest.fixture(scope="module")
def wanted(weights, served):
    _, program = weights
    _, prompt, out, _, _, _ = served
    fed = prompt + out["tokens"][:-1]
    return fed, np.asarray(ref.program_logits(program, fed, CONFIG, 8))


def test_prefill_decode_and_the_prefix_cache_match_the_reference(weights, served, wanted):
    cfg, _ = weights
    server, prompt, out, again, _, after = served
    _, want = wanted
    assert out["logits"].shape == want.shape == (8, cfg.vocab_size)
    assert float(np.abs(want).max()) > 0.3                  # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, atol=3e-4, rtol=3e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    # the same prompt again: three blocks of 16 from the prefix cache, two latent rows a
    # layer and nothing else, and the same bits
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"] and np.array_equal(again["logits"], out["logits"])
    # a cached token is one row a sub-block: six slabs for three layers
    assert [a.shape for a in server._engine.pool.arenas] == [(6, 64, 16, 1, 128)]
    # every decode call read the pool's pages through the block table
    assert after["calls"]["decode"]["paged"] == after["calls"]["decode"]["n"] == 7


def test_through_the_gather_the_same_request_gives_the_same_bits(weights, served, monkeypatch):
    """The engine told that nothing reads pages hands every call padded caches: off the
    chip bit for bit what the block table gives."""
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "reads_pages", lambda extend: False)
    _, _, out, again, _, after = _served(weights)
    assert after["calls"]["decode"]["paged"] == 0
    assert out["tokens"] == served[2]["tokens"] and np.array_equal(out["logits"], served[2]["logits"])
    assert np.array_equal(again["logits"], out["logits"])


# what each omission reads here, at the least: the system itself reads 3e-7
CAUGHT = dict.fromkeys(ref.WRONG + (ref.LOWER,), 50 * LIMIT)
CAUGHT.update(bias_weighs=10 * LIMIT)


@pytest.mark.parametrize("wrong", list(CAUGHT))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    """The zero-compute part left out, the shortcut landing early or reading ``h``, either
    ``mla_scale`` constant left out, the weights normalised, ``routed_scaling_factor``
    left out, the bias weighing, weights a precision below: each is far outside what a
    run allows. (The bias that weighs reads least: it moves a weight by the bias's own
    spread. On the chip the gate cannot see it: the configuration's ``reference.why``.)"""
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    off = ref.program_logits(program, fed, CONFIG, 8, wrong=wrong)
    assert yardstick.logits_error(out["logits"], off) > CAUGHT[wrong]


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    shallow = {**CONFIG, "num_layers": MODEL["num_layers"] - 1}
    assert yardstick.logits_error(ref.program_logits(program, fed, shallow, 8), want) > 0.1
    assert ref.program_loss(program, np.asarray([fed[:20]]), CONFIG) == pytest.approx(
        float(ref.next_token_loss(ref.program_logits(program, fed[:20], CONFIG, 20), fed[:20])))


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls (the 8th token needs
    no call): 3 layers of two attention sub-blocks and one expert layer, 4 of 16 routed
    experts held beside 8 zero-compute outputs of 24, 6 chosen a token."""
    _, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("moe_", "mla_"))}
    assert d["mla_queries"] == 6 * (60 + 7) and d["moe_tokens"] == 3 * (60 + 7)
    assert d["mla_pairs_absorbed"] == 6 * sum(range(1, 68))
    assert d["mla_pairs_expanded"] == d["mla_rows_expanded"] == 0
    pairs = 6 * d["moe_tokens"]
    # a third of the router's outputs are zero-compute, a sixth are held here
    assert 0.15 * pairs < d["moe_zero_assignments"] < 0.55 * pairs
    assert 0.05 * pairs < d["moe_assignments"] < 0.4 * pairs
    assert d["moe_assignments"] + d["moe_zero_assignments"] < pairs
    assert 0 < d["moe_experts_hit"] <= 4 * 3 * 9              # 4 held, 3 layers, 9 calls


def test_the_references_router_is_the_programs():
    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    n = jax.random.normal(keys[0], (300, 32))
    router = 0.3 * jax.random.normal(keys[1], (32, 24))
    bias = 0.02 * jax.random.normal(keys[2], (24,))
    with jax.default_matmul_precision("highest"):
        w, e = ref.route(n, router, bias, 6, 6.0)
        weighing, _ = ref.route(n, router, bias, 6, 6.0, wrong="bias_weighs")
        unscaled, _ = ref.route(n, router, bias, 6, 6.0, wrong="no_routed_scale")
        normalised, _ = ref.route(n, router, bias, 6, 6.0, wrong="norm_topk")
    sums = np.asarray(w).sum(-1)
    assert (sums < 6.0).all() and np.ptp(sums) > 0.1            # 6 p, not over their sum
    np.testing.assert_allclose(np.asarray(unscaled), np.asarray(w) / 6.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(normalised).sum(-1), 6.0, rtol=1e-5)
    assert np.abs(np.asarray(weighing) - np.asarray(w)).max() > 1e-2
    got_w, got_e = moe.softmax_bias_top_k(n, router, bias, 6, 6.0)
    assert np.array_equal(np.asarray(got_e), np.asarray(e))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(w), rtol=1e-6)


# -- the readers ----------------------------------------------------------------


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "moe_tokens": 3_000_000, "moe_assignments": 750_000, "moe_experts_hit": 40_000,
            "moe_zero_assignments": 12_000_000, "mla_pairs_absorbed": 8_000_000_000,
            "cache_tokens": 10_000_000, "phase_n": {"dispatch": 2_000},
            # the recorded steps' own counts
            "traced": {
                "mla_queries": 300_000, "mla_pairs_absorbed": 1_000_000_000,
                "mla_pairs_expanded": 500_000_000, "mla_rows_expanded": 40_000,
                "cache_tokens": 1_500_000, "moe_tokens": 400_000, "moe_assignments": 100_000,
                "moe_experts_hit": 3_000, "moe_load_max": 50_000, "moe_zero_assignments": 1_600_000,
                "phase_n": {"dispatch": 200}, "phase_s": {"step": 2.4},
            },
        },
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 50, "in_step_s": 2.5},
            "ops_by_scope": [
                ["extend.attention", 0.6], ["extend.attention.latent", 0.3], ["paging.gather", 0.1],
                ["extend.mlp", 0.5], ["extend.moe.experts", 0.4], ["extend.moe.zero", 0.01],
                ["extend.moe.route", 0.05], ["(no scope)", 0.04],
            ],
        },
    }


def test_the_three_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    # the whole load's pairs: 12 picks a token and layer
    assert read["longcat_flash.zero_share"](run) == pytest.approx(100 * 12e6 / (12 * 3e6))
    # the recorded steps' own pairs and hit experts, in 0.4 s: the weights bind
    flops, moved = 2 * 37_748_736 * 100_000, 2 * 37_748_736 * 3_000
    assert moved / 819e9 > flops / 197e12
    assert read["longcat_flash.experts_roofline"](run) == pytest.approx(100 * moved / 819e9 / 0.4)
    # the attend's pairs in both forms, the rows through W_kvb and the live rows of
    # eight slabs, over the attention's two scopes and the gather: 1.0 s
    flops = 2 * 64 * (576 + 512) * 1e9 + 2 * 64 * 320 * 5e8 + 2 * 512 * 64 * 256 * 40_000
    moved = 2 * 640 * 8 * 1.5e6
    assert read["longcat_flash.attend_roofline"](run) == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / 1.0)
    assert all(0 < read[n](run) < 100 for n in NEW_METRICS)
    # a run of a program without the counter (the parent's), the traced record or the scopes
    bare = {**run, "counters": {"steps": 5, "moe_tokens": 7, "phase_s": {"step": 1.0}}}
    assert all(read[n](bare) is None for n in NEW_METRICS)
    untraced = {**run, "counters": {**run["counters"], "traced": None}}
    assert read["longcat_flash.zero_share"](untraced) is not None
    assert read["longcat_flash.experts_roofline"](untraced) is None
    assert read["longcat_flash.attend_roofline"](untraced) is None
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.mlp", 1.0]]}}
    assert read["longcat_flash.experts_roofline"](no_scopes) is None
    assert read["longcat_flash.attend_roofline"](no_scopes) is None
    assert all(read[n]({}) is None for n in NEW_METRICS)


def test_the_required_work_of_the_held_experts_and_of_the_attend():
    with open(FILE) as f:
        keys = json.load(f)
    work = arch.experts_work(keys, {
        "moe_tokens": 12, "moe_assignments": 5, "moe_experts_hit": 3, "moe_load_max": 2,
        "moe_zero_assignments": 40})
    # 5 pairs through a held expert, 3 held experts' weights; a zero-compute pick is no work
    assert work == {"flops": 2.0 * 37_748_736 * 5, "bytes": 2.0 * 37_748_736 * 3}
    work = arch.latent_work(keys, {
        "mla_pairs_absorbed": 10, "mla_pairs_expanded": 7, "mla_rows_expanded": 2,
        "cache_tokens": 3})
    assert work["flops"] == (
        2 * 64 * (576 + 512) * 10 + 2 * 64 * (192 + 128) * 7 + 2 * 512 * 64 * 256 * 2)
    assert work["bytes"] == 1280 * 8 * 3                       # a row of 640, eight slabs
    assert arch.expert_params(keys) == 37_748_736 and arch.attention_params(keys) == 90_570_752
    # two sub-blocks' attention and MLP, the router and 12 experts of 4 layers, and the head
    assert arch.matmul_params(keys) == 4 * (
        2 * (90_570_752 + 3 * 6144 * 12288) + 6144 * 768 + 12 * 37_748_736) + 6144 * 16384
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_three_keys_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    assert config["source"] == row["source_url"] and config["model_type"] == "longcat_flash"
    cut = {"num_layers", "n_routed_experts", "vocab_size"}
    assert published == row["config"]
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
    assert set(config["reduced"]) == cut
    assert (config["num_layers"], config["n_routed_experts"], config["vocab_size"]) == (4, 16, 16384)
    assert config["router_experts"] == 768 == (
        published["n_routed_experts"] + published["zero_expert_num"])
    assert config["expert_offset"] == 0
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    cfg = arch.program_config(manifest.published_keys(config))
    assert (cfg.embed_dim, cfg.num_heads, cfg.q_rank, cfg.kv_rank) == (6144, 64, 1536, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.mlp_dim) == (128, 64, 128, 12288)
    assert (cfg.router_experts, cfg.zero_experts, cfg.routed_experts, cfg.num_experts) == (
        768, 256, 512, 16)
    assert (cfg.experts_per_token, cfg.expert_dim, cfg.routed_scale) == (12, 2048, 6.0)
    assert (cfg.q_scale, cfg.kv_scale) == (2.0, pytest.approx(3.4641, abs=1e-4))
    assert (cfg.cache_arrays, cfg.cache_layers, cfg.rope_base) == (((1, 640),), 8, 1e7)
    assert cfg.bias_std == config["e_score_correction_bias_std"] > 0
    # 5.173 B parameters = 10.35 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 5_172_749_312
    assert "5,172,749,312 parameters = 10.35 GB" in config["deployment"]
    assert "32 that share each layer" in config["deployment"]
    for stated in ("mla_scale", "norm_topk_prob", "hidden_act", "tie_word_embeddings",
                   "e_score_correction_bias", "init"):
        assert config["assumed"][stated], stated
    assert len(config["departures"]) >= 4
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.5
    stated = config["compiled_bytes_per_device"]
    assert stated["decode"]["shape"] == [16, 1, 8192] and stated["prefill"]["shape"] == [1, 512, 8192]


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert {m["name"] for m in cell.per_layer} == set(NEW_METRICS) | {
        "extend.moe_share", "extend.attention_share", "extend.latent_share", "engine.step_ms",
        "engine.tokens_per_step", "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s",
        "tpot_p95_s"}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    assert traffic["prompt_tokens"] == [1024, 3072, 512, 6144, 2048, 768, 4096, 1536, 7680, 2560]
    assert traffic["output_tokens"] == [256, 128, 384, 96, 192, 512, 160, 320, 64, 224]
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (6144, 64)
    engine = config["engine"]
    longest = max(p + o for p, o in zip(traffic["prompt_tokens"], traffic["output_tokens"]))
    assert longest == 7744 <= engine["cache_buckets"][-1] == 8192
    assert engine["block_size"] == 256 and engine["prefill_chunk"] == 512
    assert engine["prefill_lanes"] == 1 and engine["lane_buckets"] == [1, 2, 4, 8, 16]
    # every lane at the longest context at once fits the pool
    assert engine["num_blocks"] * 256 >= 16 * 8192
    assert np.allclose(
        traffic["due_offsets"], np.random.default_rng(67).uniform(-0.3, 0.3, size=10))
    cycles = traffic["rate_rps"] * 51 / 10
    # whole cycles of the ten pairs in the 51 s window, the most that 0.8 of the knee allows
    assert cycles == pytest.approx(round(cycles), abs=1e-4) and round(cycles) >= 2
    assert traffic["rate_rps"] <= 0.8 * traffic["knee_rps"] < (round(cycles) + 1) * 10 / 51
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0
    assert (traffic["trace_from"], traffic["trace_seconds"]) == (0.93, 1.5)


def test_a_layer_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.program_config({**keys, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        arch.program_config({**keys, "norm_topk_prob": True})
    with pytest.raises(ValueError, match="zero_expert_type"):
        arch.program_config({**keys, "zero_expert_type": "copy"})
    with pytest.raises(ValueError, match="mla_scale_kv_lora"):
        arch.program_config({**keys, "mla_scale_kv_lora": False})
    with pytest.raises(ValueError, match="not among the 512 routed"):
        arch.program_config({**keys, "expert_offset": 500})
