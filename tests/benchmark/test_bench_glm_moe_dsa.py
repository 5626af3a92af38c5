"""GLM-5 (``glm_moe_dsa``): the serving path against the benchmark's plain reference on
seeded random weights at a small size on the CPU (prefill in chunks through both
arenas under the selection mask, decode over gathered rows, the same prompt again from
the prefix cache, against the reference's expanded form with no cache and a plain
``top_k``), the selection the two agree on, what the comparison's limit catches, the
sixteen shares of 16 experts, the counters, the readers of the three metrics, and the
configuration's file. float32 throughout; the projections are scaled up so that the
logits are of order 1 and the routing and the selection matter, and the query latent's
norm has a drawn scale, so that an indexer that reads the latent before its norm
selects other rows (at a scale of ones a query's norm is one positive factor on all its
scores, and decides nothing: the gate on the chip cannot catch that omission)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import glm_moe_dsa as arch
from benchmark.reference import glm_moe_dsa_reference as ref

TINY = bench_helpers.tiny("glm_moe_dsa")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "glm-5-serve-document-questions"
FILE = BOOK.root + "/benchmark/configs/glm-5-serve-ep16.json"
NEW_METRICS = (
    "glm_moe_dsa.index_roofline", "glm_moe_dsa.attend_roofline", "glm_moe_dsa.experts_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    # the init's 0.02 would leave every logit near 0 and every score alike: make
    # the projections matter, and leave the norms' scales and the biases as drawn
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "bias") else a * 8.0, cfg.init_params(3))
    for part, key in ((program["first"], 1), (program["blocks"]["layers"], 2)):
        scale = part["attn"]["q_norm"]["scale"]
        part["attn"]["q_norm"]["scale"] = jax.random.uniform(
            jax.random.PRNGKey(key), scale.shape, scale.dtype, 0.2, 2.0)
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the engine, twice: a prompt of 60 tokens in chunks
    of 32 (``index_topk`` is 16), then 8 decoded tokens across the 64-token bucket;
    then the same again, 48 tokens from the prefix cache."""
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
    # the same prompt again: three blocks of 16 from the prefix cache, restored in both
    # arenas, and the same bits
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"] and np.array_equal(again["logits"], out["logits"])
    # a cached token is two rows: the latent row (the latent, the rotary key behind it,
    # zeros to 128 lanes) and the indexer's key; nothing a head
    assert [a.shape for a in server._engine.pool.arenas] == [
        (4, 64, 16, 1, 128), (4, 64, 16, 1, 16)]


def test_the_program_and_the_reference_select_the_same_rows(weights, wanted):
    """Keye's probe at these shapes: the prompt in chunks of 32 (the mask's form) and the
    decoded tokens one at a time (the rows' form) select, in every layer, what the
    reference's plain ``top_k`` over its own hidden states selects: in float32 the two sets
    are equal, 16 rows a query past the sixteenth."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "gate_probe", os.path.join(BOOK.root, "scripts", "gate_probe.py"))
    gate_probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate_probe)
    from ray_tpu.models import glm_moe_dsa

    cfg, program = weights
    fed, _ = wanted
    theirs = ref.program_selection(program, fed, CONFIG)
    ours = gate_probe.selection_of(glm_moe_dsa.make_probe_fn(cfg), cfg, program, fed, 32, 128)
    assert theirs.shape == ours.shape == (4, 67, 67)
    assert theirs.sum(-1).tolist() == [[min(t + 1, 16) for t in range(67)]] * 4
    assert (ours == theirs).all()


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    """Attending densely, half of ``index_topk``, the indexer without its ReLU, without
    ``w``, its key without the LayerNorm, its rotation left out, its queries from the
    un-normed latent; the latent cached un-normed, the softmax scale of the nope width
    alone, the shared expert or ``routed_scaling_factor`` left out, weights a precision
    below: each is far outside what a run allows."""
    _, program = weights
    _, _, out, _, _, _ = served
    fed, _ = wanted
    off = ref.program_logits(program, fed, CONFIG, 8, wrong=wrong)
    assert yardstick.logits_error(out["logits"], off) > 20 * LIMIT


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    shallow = {**CONFIG, "num_hidden_layers": MODEL["num_hidden_layers"] - 1}
    assert yardstick.logits_error(ref.program_logits(program, fed, shallow, 8), want) > 0.1
    assert ref.program_loss(program, np.asarray([fed[:20]]), CONFIG) == pytest.approx(
        float(ref.next_token_loss(ref.program_logits(program, fed[:20], CONFIG, 20), fed[:20])))


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls (the 8th token needs
    no call): 4 layers of attention, each behind an indexer that scores every pair a
    query sees and keeps 16; 3 of them expert layers with 4 of 16 experts held and 4
    chosen a token. The engine gathered the 64-slot bucket for the two chunks and the four
    decode calls up to position 63, and the 128-slot one for the three past it."""
    _, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("moe_", "mla_", "sparse_"))}
    assert d["mla_queries"] == d["sparse_queries"] == 4 * (60 + 7)
    assert d["moe_tokens"] == 3 * (60 + 7)
    assert d["sparse_keys_scored"] == 4 * sum(range(1, 68))
    attended = 4 * sum(min(t, 16) for t in range(1, 68))
    assert d["sparse_keys_attended"] == d["mla_pairs_absorbed"] == attended
    assert d["mla_pairs_absorbed"] <= 16 * d["mla_queries"]     # at most index_topk a query
    assert d["mla_pairs_expanded"] == d["mla_rows_expanded"] == 0
    # a decode call reads the 16 rows it selected; a chunk the rows some query of it selected
    assert 4 * (7 * 16 + 32 + 16) <= d["sparse_slots_read"] <= 4 * (7 * 16 + 32 + 60)
    assert d["sparse_slots_gathered"] == 4 * (6 * 64 + 3 * 128)
    assert 0.1 * 4 * d["moe_tokens"] < d["moe_assignments"] < 0.5 * 4 * d["moe_tokens"]
    assert 0 < d["moe_experts_hit"] <= 4 * 3 * 9              # 4 held, 3 layers, 9 calls


def test_the_sixteen_shares_of_16_experts_add_up_to_the_uncut_layer():
    """Every share's part of the routed sum (256 experts, 16 a chip, the bias
    choosing, weights over their sum times 2.5) and the shared expert, counted once,
    is what the reference gives for the uncut layer."""
    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    n, d, f, routed, k, held = 40, 32, 16, 256, 8, 16
    x = jax.random.normal(keys[0], (n, d))
    router = 0.3 * jax.random.normal(keys[1], (d, routed))
    bias = 0.02 * jax.random.normal(keys[2], (routed,))
    wi = 0.3 * jax.random.normal(keys[3], (routed, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[4], (routed, f, d))
    shared_wi = 0.3 * jax.random.normal(keys[5], (d, 2 * f))
    shared_wo = 0.3 * jax.random.normal(keys[6], (f, d))
    weights, chosen = moe.sigmoid_bias_top_k(x, router, bias, k, 2.5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
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
        # the uncut layer, by the reference: one "share" that holds all 256
        want = ref._experts(
            x, {"router": router, "bias": bias, "wi": wi, "wo": wo},
            {"wi": shared_wi, "wo": shared_wo},
            {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}, None)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


# -- the readers ----------------------------------------------------------------


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    traced = {
        "mla_queries": 300_000, "mla_pairs_absorbed": 200_000_000,
        "mla_pairs_expanded": 300_000_000, "mla_rows_expanded": 1_000_000,
        "sparse_queries": 300_000, "sparse_keys_scored": 2_000_000_000,
        "sparse_keys_attended": 1_000_000_000, "sparse_slots_read": 2_500_000,
        "sparse_slots_gathered": 9_000_000, "cache_tokens": 1_500_000,
        "moe_tokens": 400_000, "moe_assignments": 200_000, "moe_experts_hit": 2_000,
        "moe_load_max": 50_000, "phase_n": {"dispatch": 200}, "phase_s": {"step": 2.4},
    }
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            **{k: 8 * v for k, v in traced.items() if isinstance(v, int)},
            "phase_n": {"dispatch": 2_000}, "phase_s": {"step": 20.0}, "traced": traced,
        },
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 50, "in_step_s": 2.5},
            "ops_by_scope": [
                ["extend.attention", 0.6], ["extend.attention.latent", 0.2],
                ["extend.attention.index", 0.25], ["extend.attention.select", 0.15],
                ["extend.moe.experts", 0.4], ["extend.moe.shared", 0.1], ["(no scope)", 0.2],
            ],
        },
    }


def test_the_three_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    # the indexer: the recorded steps' own scored pairs and live slots, in 0.25 + 0.15 s
    flops = 2 * 32 * 128 * 2e9
    moved = 2 * 128 * 6 * 1.5e6
    assert flops / 197e12 > moved / 819e9                   # the products bind
    assert read["glm_moe_dsa.index_roofline"](run) == pytest.approx(100 * flops / 197e12 / 0.4)
    # the attend: both forms' attended pairs, W_kvb over the expanded rows, the selected
    # slots' rows, in 0.6 + 0.2 s
    flops = 2 * 64 * (576 + 512) * 2e8 + 2 * 64 * (256 + 256) * 3e8 + 2 * 512 * 64 * 448 * 1e6
    moved = 1280 * 2.5e6
    assert flops / 197e12 > moved / 819e9
    assert read["glm_moe_dsa.attend_roofline"](run) == pytest.approx(100 * flops / 197e12 / 0.8)
    # the experts: pairs, tokens, hit experts and calls, in 0.4 + 0.1 s
    flops = 2 * 37_748_736 * (200_000 + 400_000)
    moved = 2 * 37_748_736 * (2_000 + 5 * 200)
    assert moved / 819e9 > flops / 197e12                   # the weights bind
    assert read["glm_moe_dsa.experts_roofline"](run) == pytest.approx(100 * moved / 819e9 / 0.5)
    assert all(0 < read[n](run) < 100 for n in NEW_METRICS)
    # the accepted readers this cell is listed under read the same run
    assert BOOK.reader("extend.index_share")(run) == pytest.approx(20.0)
    assert BOOK.reader("extend.latent_share")(run) == pytest.approx(10.0)
    assert BOOK.reader("engine.sparse_unread_share")(run) == pytest.approx(100 * (1 - 2.5 / 9))
    # a run of a program without the counters, the traced record or the scopes (the
    # parent's): nothing, and no reader raises
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    untraced = {**run, "counters": {**run["counters"], "traced": None}}
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.mlp", 1.0]]}}
    for name in NEW_METRICS:
        assert read[name](bare) is None and read[name](untraced) is None
        assert read[name](no_scopes) is None and read[name]({}) is None
    kimi = {**run, "counters": {"traced": {
        k: v for k, v in run["counters"]["traced"].items() if not k.startswith("sparse_")}}}
    assert read["glm_moe_dsa.index_roofline"](kimi) is None
    assert read["glm_moe_dsa.attend_roofline"](kimi) is None


def test_the_required_work():
    with open(FILE) as f:
        keys = json.load(f)
    work = arch.experts_work(keys, {
        "moe_tokens": 12, "moe_assignments": 5, "moe_experts_hit": 3, "moe_load_max": 2,
        "phase_n": {"dispatch": 2}})
    # 5 pairs through a held expert and 12 (token, layer)s through the shared one; 3 held
    # experts' weights and the shared expert's for 2 calls x 5 expert layers (layer 0 has none)
    assert work["flops"] == 2 * 37_748_736 * (5 + 12)
    assert work["bytes"] == 2 * 37_748_736 * (3 + 2 * 5)
    work = arch.index_work(keys, {"sparse_keys_scored": 10, "cache_tokens": 3})
    assert work == {"flops": 8192.0 * 10, "bytes": 256.0 * 6 * 3}
    work = arch.attend_work(keys, {
        "mla_pairs_absorbed": 10, "mla_pairs_expanded": 7, "mla_rows_expanded": 2,
        "sparse_slots_read": 3})
    assert work["flops"] == 2 * 64 * (576 + 512) * 10 + 2 * 64 * (256 + 256) * 7 + (
        2 * 512 * 64 * 448 * 2)
    assert work["bytes"] == 1280 * 3 and arch.cached_row(keys) == 640
    assert arch.expert_params(keys) == 37_748_736
    assert arch.attention_params(keys) == 165_019_648 + 9_371_648
    # attention and indexer of 6 layers, layer 0's MLP, and router + shared + 8 experts of 5
    # layers, and the head
    assert arch.matmul_params(keys) == 6 * 174_391_296 + 3 * 6144 * 12288 + 5 * (
        6144 * 256 + 9 * 37_748_736) + 6144 * 19360
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_five_keys_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert config["source"] == row["source_url"] and config["model_type"] == "glm_moe_dsa"
    cut = {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
        assert published[key] == value, key
    assert set(config["reduced"]) == cut
    assert [config[k] for k in sorted(cut)] == [1, 16, 6, 0, 19360]
    assert (config["router_experts"], config["expert_offset"]) == (256, 0)
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the harness hands an architecture the top-level scalars: the base stands there too
    assert config["rope_theta"] == config["rope_parameters"]["rope_theta"] == 1000000
    assert config["rope_parameters"]["rope_type"] == "default"
    cfg = arch.program_config(manifest.published_keys(config))
    assert (cfg.embed_dim, cfg.num_heads, cfg.q_rank, cfg.kv_rank) == (6144, 64, 2048, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.mlp_dim) == (192, 64, 256, 12288)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_rope_dim, cfg.topk) == (32, 128, 64, 2048)
    assert (cfg.router_experts, cfg.num_experts, cfg.experts_per_token, cfg.expert_dim) == (
        256, 16, 8, 2048)
    assert (cfg.dense_layers, cfg.expert_layers, cfg.shared_experts) == (1, 5, 1)
    assert cfg.cache_arrays == ((1, 640), (1, 128)) and cfg.routed_scale == 2.5
    assert cfg.rope_base == 1e6 and cfg.norm_eps == 1e-5 and cfg.index_norm_eps == 1e-6
    assert cfg.bias_std == config["e_score_correction_bias_std"] > 0
    assert cfg.index_bias_std == config["index_k_norm_bias_std"] > 0
    # 4.727 B parameters = 9.45 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == 4_727_340_800
    assert "4,727,340,800 parameters = 9.45 GB" in config["deployment"]
    assert "one chip of 240 laid out ep16 x pp15" in config["deployment"]
    # a cached token: 6 layers x (640 + 128) values x 2 B
    assert 2 * cfg.num_layers * sum(h * d for h, d in cfg.cache_arrays) == 9216
    for key in ("indexer", "index_k_norm_bias", "index_init", "rope_theta"):
        assert config["assumed"][key], key
    assert len(config["departures"]) >= 6
    assert config["reference"]["why"] and 0 < config["reference"]["max_logits_error"] < 0.2
    assert config["reference"]["module"] == "glm_moe_dsa_reference"


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert BOOK.cell_names().count(CELL) == 1                   # in the book, once
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "glm-5-serve-ep16" and cell.traffic_name == "document-questions"
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS) | {
        "extend.moe_share", "extend.attention_share", "extend.index_share",
        "extend.latent_share", "engine.sparse_unread_share", "engine.step_ms",
        "engine.tokens_per_step", "device.idle_share.serve", "loadgen.late_p95_ms",
        "ttft_p95_s", "tpot_p95_s"}
    assert {m["name"] for m in cell.end_to_end} == {"request_latency_mean_s", "setup_s"}
    for name in NEW_METRICS:
        (entry,) = (m for m in BOOK.data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "request_latency_mean_s"
    assert traffic["prompt_tokens"] == [
        4096, 10240, 6144, 20480, 8192, 14336, 5120, 24576, 7168, 12288]
    assert traffic["output_tokens"] == [128, 96, 256, 64, 192, 128, 384, 64, 160, 96]
    topk = config["index_topk"]
    assert all(2 * topk <= p <= 12 * topk for p in traffic["prompt_tokens"])
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (3 * topk, 64)
    engine = config["engine"]
    longest = max(p + o for p, o in zip(traffic["prompt_tokens"], traffic["output_tokens"]))
    assert longest <= engine["cache_buckets"][-1] == 32768
    assert engine["block_size"] == 256 and engine["prefill_chunk"] == 512
    assert engine["prefill_lanes"] == 1 and engine["num_blocks"] * 256 == 163840
    assert np.allclose(
        traffic["due_offsets"], np.random.default_rng(65).uniform(-0.3, 0.3, size=10))
    cycles = traffic["rate_rps"] * 51 / 10
    # whole cycles of the ten pairs in the 51 s window, never under two, the most that
    # 0.8 of the knee allows
    assert cycles == pytest.approx(round(cycles), abs=1e-4) and round(cycles) >= 2
    assert int(traffic["rate_rps"] * 51) == 10 * round(cycles) >= 20
    assert traffic["rate_rps"] <= 0.8 * traffic["knee_rps"] or round(cycles) == 2
    assert 0.8 * traffic["knee_rps"] < (round(cycles) + 1) * 10 / 51
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0 and 1.2 <= traffic["trace_seconds"] <= 2.0


def test_a_block_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.program_config({**keys, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="topk_method"):
        arch.program_config({**keys, "topk_method": "greedy"})
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        arch.program_config({**keys, "num_nextn_predict_layers": 1})
    with pytest.raises(ValueError, match="indexer_rope_interleave"):
        arch.program_config({**keys, "indexer_rope_interleave": False})
    with pytest.raises(ValueError, match="one key and one value a query head"):
        arch.program_config({**keys, "num_key_value_heads": 8})
    with pytest.raises(ValueError, match="qk_head_dim"):
        arch.program_config({**keys, "qk_head_dim": 192})
