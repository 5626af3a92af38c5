"""Qwen3-Next (``qwen3_next``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in chunks
through the pool's two arenas and the state store, decode that reads a slot's state, the
same prompt again from the prefix cache with the state's snapshot), what the
comparison's limit catches, ``delta_work`` and ``experts_work`` by hand, the readers of
the three new metrics on a hand-made run, and the configuration's and the traffic's
files against the catalog's row and the issue. float32 throughout; the projections are
scaled up so that the logits are of order 1."""

import json

import jax
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import qwen3_next as arch
from benchmark.reference import qwen3_next_reference as ref

TINY = bench_helpers.tiny("qwen3_next")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "qwen3-next-serve-concurrent-turns"
FILE = BOOK.root + "/benchmark/configs/qwen3-next-80b-a3b-serve-ep4.json"
NEW_METRICS = ("extend.delta_share", "qwen3_next.delta_roofline", "qwen3_next.experts_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: what each omission reads at this size, as a multiple of the limit it must pass
#: (``no_qk_norm`` diverges: unnormalised keys make the rule's write overshoot)
CAUGHT = {
    "no_decay": 100, "beta_one": 100, "no_delta": 100, "no_qk_norm": 100, "no_conv_silu": 100,
    "no_z_gate": 100, "no_output_gate": 50, "rotate_all": 20, "norm_not_centred": 100,
    "no_shared_gate": 100, "bf16_state": 20, "fp8_weights": 50}


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 6.0 if path[-1].key in (
            "kernel", "wi", "wo", "embedding", "router", "gate") else a,
        cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the server, twice: a prompt of 90 tokens in chunks of 32
    (four sub-chunks a chunk), then 8 decoded tokens; then the same again, 80 tokens
    from the prefix cache with the state's snapshot at that boundary."""
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
    # not (error <= limit): a rule that diverges reads nan
    assert not yardstick.logits_error(out["logits"], other) <= CAUGHT[wrong] * LIMIT, wrong


# -- the gate's second number: the state itself ------------------------------------

STATE_LIMIT = TINY["reference"]["max_state_error"]
#: the omissions that change the first delta layer's state, with what each reads
#: against the served one at this size as a multiple of the limit it must pass
STATE_CAUGHT = {
    "no_decay": 1000, "beta_one": 1000, "no_delta": 1000, "no_qk_norm": 1000,
    "no_conv_silu": 1000, "norm_not_centred": 1000, "bf16_state": 100, "fp8_weights": 1000}


def test_the_served_state_is_the_references_and_in_the_dtype_the_file_states(
        weights, served, wanted):
    """``program_logits`` under a file with ``max_state_error`` found the engine that
    serves these weights, read the snapshot after the prompt's 80 cached tokens and
    the slot the sequence left after its 97, and passed both (the logits are finite)."""
    _, program = weights
    server, _, _, _, _, _ = served
    fed, want = wanted
    assert np.isfinite(want).all()
    held = ref.served_states(program, fed, 8)
    assert sorted(held) == [80, 97]
    assert {h.dtype for h in held.values()} == {np.dtype(MODEL["state_dtype"])}
    exact = ref.first_delta_states(program, fed, CONFIG, (80, 97))
    for n, state in zip((80, 97), exact):
        assert held[n].shape == state.shape == (8, 16, 16)
        assert ref.state_error(held[n], state) < STATE_LIMIT / 10, n


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_state_limit_catches_what_changes_the_state(weights, served, wanted, wrong):
    """A rounded state reads a hundred times the limit, whatever else changes the
    first delta layer more; what lies outside the delta mixer leaves its state alone."""
    _, program = weights
    fed, _ = wanted
    held = ref.served_states(program, fed, 8)
    other = ref.first_delta_states(program, fed, CONFIG, (80, 97), wrong)
    for n, state in zip((80, 97), other):
        if wrong in STATE_CAUGHT:
            assert not ref.state_error(held[n], state) <= STATE_CAUGHT[wrong] * STATE_LIMIT
        else:
            assert ref.state_error(held[n], state) < STATE_LIMIT / 10


@pytest.mark.parametrize("how", ["rounded", "dtype"])
def test_a_served_state_that_is_off_makes_the_logits_nan(weights, served, wanted, how):
    """The harness compares logits alone: a state past the limit, or kept in another
    dtype than the file states, reaches it as logits no limit passes."""
    import jax.numpy as jnp

    _, program = weights
    server, _, _, _, _, _ = served
    fed, want = wanted
    pool = server._engine.pool
    kept = pool.states
    try:
        if how == "rounded":
            pool.states = (kept[0].astype(jnp.bfloat16).astype(kept[0].dtype),) + kept[1:]
        else:
            pool.states = (kept[0].astype(jnp.float16),) + kept[1:]     # exact here, but not float32
        assert np.isnan(np.asarray(ref.program_logits(program, fed, CONFIG, 8))).all()
        # an omission's reading is a reading: its logits stay as they are
        assert np.isfinite(
            np.asarray(ref.program_logits(program, fed, CONFIG, 8, "no_z_gate"))).all()
    finally:
        pool.states = kept
    np.testing.assert_array_equal(np.asarray(ref.program_logits(program, fed, CONFIG, 8)), want)


def test_the_state_limit_needs_the_engine_that_serves_the_weights(weights, wanted):
    _, program = weights
    fed, _ = wanted
    with pytest.raises(RuntimeError, match="0 engines in this process serve these weights"):
        ref.program_logits(dict(program), fed, CONFIG, 8)
    with pytest.raises(RuntimeError, match="not the sequence of 96 tokens"):
        ref.program_logits(program, fed[:-1], CONFIG, 8)
    without = {**MODEL, "reference": {"module": "qwen3_next_reference"}}
    assert np.isfinite(np.asarray(ref.program_logits(dict(program), fed, without, 8))).all()


def test_a_shallower_reference_is_another_model(weights, wanted):
    _, program = weights
    fed, want = wanted
    one_period = {**CONFIG, "num_hidden_layers": 4}
    assert yardstick.logits_error(
        np.asarray(ref.program_logits(program, fed, one_period, 8)), want) > 50 * LIMIT


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """90 prompt tokens in chunks of 32 + 32 + 26, then 7 decode calls: 6 delta layers
    and 2 full ones, 8 expert layers, one lane; the store copies the state for the
    repeat's prefix hit and for nothing else; nothing is gathered for a delta layer."""
    server, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(
        ("delta_", "moe_", "state_", "cache_", "window_"))}
    assert d["delta_tokens"] == 6 * 97
    assert d["delta_state_passes"] == 6 * 10                # a lane, a layer and a call
    assert d["moe_tokens"] == 8 * 97
    assert 0 < d["moe_assignments"] <= 8 * 97 * 3
    assert d["window_slots"] == 0
    # the first two chunks' calls lie in the 64 bucket, later ones in 128: lanes x cache
    assert d["cache_slots"] == 64 + 64 + 128 + 7 * 128
    assert d["state_restores"] == 0 and d["state_bytes_moved"] == 0
    after_again = server.kv_stats()
    assert after_again["state_restores"] - after["state_restores"] == 1
    pool = server._engine.pool
    assert after_again["state_bytes_moved"] == pool.state_bytes == 6 * (8 * 16 * 16 + 3 * 256) * 4
    assert pool.layers == 2 and [a.shape[0] for a in pool.arenas] == [2, 2]
    assert [s.shape for s in pool.states] == [(6, 12, 8, 16, 16), (6, 12, 3, 256)]


# -- the readers ----------------------------------------------------------------


def test_the_work_functions_by_hand():
    with open(FILE) as f:
        keys = json.load(f)
    assert arch.rotary_features(keys) == 64 and arch.delta_layers(keys) == 6
    assert arch.expert_params(keys) == 3 * 2048 * 512 == 3_145_728 == arch.shared_params(keys)
    assert arch.delta_mixer_params(keys) == 25_165_824 + 131_072 + 8_388_608
    assert arch.attention_params(keys) == 16_777_216 + 2 * 1_048_576 + 8_388_608
    assert arch.matmul_params(keys) == (
        6 * 33_685_504 + 2 * 27_262_976
        + 8 * (1_048_576 + 3_145_728 + 2_048 + 10 * 3_145_728) + 2048 * 37984)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096
    # the rule: 7 operations a state element of 32 heads of 128 x 128
    assert arch.delta_flops_per_token(keys) == 7 * 32 * 128 * 128 == 3_670_016
    # a decode call of 16 lanes: six states read and written a lane, a token's rows beside
    step = arch.delta_work(keys, {"delta_tokens": 6 * 16, "delta_state_passes": 6 * 16})
    assert step["state_bytes"] == 2 * 2_097_152 * 96
    assert step["bytes"] == step["state_bytes"] + 96 * ((2 * 2048 + 2 * 4096) * 2 + 8 * 32)
    assert step["flops"] == 3_670_016 * 96
    assert step["flops"] / 197e12 < step["bytes"] / 819e9          # a decode call reads
    # a chunk of 1,024 tokens on one lane: the state once a layer, 1,024 tokens' rows;
    # the definition's operations are still fewer than the bytes' time: the rule is
    # read-bound in either form
    chunk = arch.delta_work(keys, {"delta_tokens": 6 * 1024, "delta_state_passes": 6})
    assert chunk["state_bytes"] == 2 * 2_097_152 * 6
    assert chunk["flops"] / 197e12 < chunk["bytes"] / 819e9
    assert arch.delta_work(keys, {}) == {"flops": 0.0, "bytes": 0.0, "state_bytes": 0.0}
    # the experts: 2 operations a parameter and pair, an expert's weights a hit; the
    # shared expert a token and layer, its weights a call and layer
    experts = arch.experts_work(keys, {
        "moe_assignments": 20_000, "moe_tokens": 8 * 1024, "moe_experts_hit": 1024,
        "phase_n": {"dispatch": 1}})
    assert experts == {
        "flops": 2.0 * 3_145_728 * (20_000 + 8192), "bytes": 2.0 * 3_145_728 * (1024 + 8)}


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    traced = {
        "delta_tokens": 60_000, "delta_state_passes": 6_000, "cache_tokens": 3_000_000,
        "moe_tokens": 80_000, "moe_assignments": 200_000, "moe_experts_hit": 30_000,
        "moe_load_max": 4_000, "steps": 100, "phase_n": {"dispatch": 105},
        "calls": {
            "decode": {"n": 100, "lanes_used": 1_000}, "prefill": {"n": 5, "tokens": 5_000}},
    }
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            **{k: 40 * v for k, v in traced.items() if isinstance(v, int)},
            "phase_s": {"step": 40.0}, "phase_n": {"dispatch": 4200}, "traced": traced},
        "trace": {
            "busy_s": 1.0, "window_s": 1.25, "engine": {"steps": 100, "in_step_s": 1.2},
            "ops_by_scope": [
                ["extend.moe.experts", 0.4], ["extend.delta", 0.2], ["extend.delta.scan", 0.15],
                ["extend.attention", 0.1], ["extend.moe.shared", 0.05], ["extend.moe.route", 0.04],
                ["extend.logits", 0.03], ["(no scope)", 0.03],
            ],
            "ops_by_kernel": [["fusion", 0.6], ["gmm", 0.35], ["masked_attention", 0.02]],
        },
    }


def test_the_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.delta_share"](run) == pytest.approx(100 * 0.35 / 1.0)
    # the traced steps' own counts, unscaled, over the seconds under the rule's scope
    moved = 2 * 2_097_152 * 6_000 + 60_000 * ((2 * 2048 + 2 * 4096) * 2 + 8 * 32)
    assert moved / 819e9 > 3_670_016 * 60_000 / 197e12
    assert read["qwen3_next.delta_roofline"](run) == pytest.approx(100 * moved / 819e9 / 0.15)
    assert 0 < read["qwen3_next.delta_roofline"](run) < 100
    weights = 2 * 3_145_728 * (30_000 + 8 * 105)
    assert weights / 819e9 > 2 * 3_145_728 * 280_000 / 197e12
    assert read["qwen3_next.experts_roofline"](run) == pytest.approx(
        100 * weights / 819e9 / (0.4 + 0.05))
    assert 0 < read["qwen3_next.experts_roofline"](run) < 100
    # the accepted readers this cell is listed under read it as they stand
    assert BOOK.reader("extend.attention_share")(run) == pytest.approx(100 * 0.1 / 1.0)
    assert BOOK.reader("extend.moe_share")(run) == pytest.approx(100 * 0.49 / 1.0)
    # and those of other architectures' layers find nothing here
    for name in ("extend.linear_share", "extend.ssm_share", "extend.index_share",
                 "extend.latent_share", "extend.window_share", "minicpm_sala.linear_roofline",
                 "ssm.scan_roofline"):
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
    assert read["extend.delta_share"](no_scopes) is None
    # another program's traced steps (granite small's: a recurrence and experts, no delta
    # rule): the rule's readers find nothing, whatever its expert counters say
    other = {**run, "trace": {**run["trace"], "ops_by_scope": [
        ["extend.moe.experts", 0.5], ["extend.ssm.scan", 0.3]]}}
    other["counters"] = {**run["counters"], "traced": {
        k: v for k, v in run["counters"]["traced"].items() if not k.startswith("delta_")}}
    assert read["extend.delta_share"](other) is None
    assert read["qwen3_next.delta_roofline"](other) is None


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_its_cut():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    assert config["model_type"] == row["config"]["model_type"] == "qwen3_next"
    cut = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 37984}
    for key, value in row["config"].items():
        assert published[key] == value, key
        assert config[key] == cut.get(key, value), key
    assert set(config["reduced"]) == set(cut) | {"param_dtype"}
    assert (published["param_dtype"], config["param_dtype"]) == ("float32", "bfloat16")
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    assert config["router_experts"] == published["num_experts"] == 512
    assert config["published_num_hidden_layers"] == published["num_hidden_layers"] == 48
    assert config["vocab_size"] * 4 == published["vocab_size"]
    assert (config["num_experts"], config["expert_offset"]) == (128, 0)
    assert config["num_hidden_layers"] == 2 * published["full_attention_interval"]
    assumed = config["assumed"]
    for said in ("(1 + g)", "pre-norm", "repeat_interleave", "rsqrt(sum x^2 + 1e-6)",
                 "sigmoid(w_s . n)"):
        assert said in assumed["block"], said
    assert "uniform in (0, 16)" in assumed["delta_init"] and "(0.001, 0.1)" in assumed["delta_init"]
    assert "0.1" in assumed["norm_scales"] and config["norm_scale_std"] == 0.1
    assert "12,877,824 B a sequence" in assumed["state_store"]
    departures = " ".join(config["departures"])
    for said in ("multi-token-prediction", "of the model's 262144", "expert exchange",
                 "random from --seed", "column order is ours"):
        assert said in departures, said
    cfg = arch.program_config(manifest.published_keys(config))
    assert (cfg.period, cfg.periods, cfg.delta_layers, cfg.cache_layers) == (4, 2, 6, 2)
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.rotary_dim,
            cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_width, cfg.expert_dim, cfg.shared_dim, cfg.router_experts,
            cfg.num_experts, cfg.experts_per_token, cfg.vocab_size) == (
        2048, 16, 2, 256, 64, 16, 32, 128, 128, 4, 512, 512, 512, 128, 10, 37984)
    assert (cfg.rope_base, cfg.norm_eps, cfg.delta_chunk) == (1e7, 1e-6, 64)
    assert cfg.cache_arrays == ((1, 512), (1, 512))
    assert [shape for _, shape, _ in cfg.state_arrays] == [(32, 128, 128), (3, 8192)]
    assert cfg.state_chunk == 64 and config["engine"]["block_size"] % 64 == 0
    assert "6 delta layers" in arch.describe(cfg) and "128 held of 512" in arch.describe(cfg)
    with pytest.raises(ValueError, match="not one with"):
        arch.program_config({**manifest.published_keys(config), "norm_topk_prob": False})
    # 3.67 B parameters = 7.33 GB in bfloat16: the issue's count and the file's arithmetic
    assert cfg.num_params() == 3_667_251_328
    assert "3,667,251,328 parameters = 7.33 GB" in config["deployment"]
    for part in ("33,718,464", "27,263,488", "440,572,096", "434,117,120", "77,791,232",
                 "ep4 x pp6"):
        assert part in config["deployment"], part
    assert config["reference"]["module"] == "qwen3_next_reference"
    why = config["reference"]["why"]
    for named in ref.WRONG + (ref.LOWER,):
        assert named in why, named
    engine = config["engine"]
    weights = 2 * cfg.num_params()
    assert weights >= 0.25 * 16.91e9                            # the floor on weights alone
    snapshot = sum(
        layers * int(np.prod(shape)) * np.dtype(dtype).itemsize
        for layers, shape, dtype in cfg.state_arrays)
    assert snapshot == 12_877_824
    resident = weights + engine["num_blocks"] * engine["block_size"] * 4096 + (
        engine["state_slots"] * snapshot)
    assert resident >= 0.60 * 16_909_336_064                    # weights + pool + slots
    assert engine["lane_buckets"][-1] == 16 and engine["prefill_chunk"] in engine[
        "prefill_token_buckets"]
    assert engine["cache_buckets"][-1] >= 16384
    stated = config["compiled_bytes_per_device"]
    assert stated["decode"]["shape"] == [16, 1, engine["cache_buckets"][-1]]
    assert stated["prefill"]["shape"] == [1, engine["prefill_chunk"], engine["cache_buckets"][-1]]
    assert 0.60 * 16_909_336_064 <= stated["built_peak_bytes"] <= stated["peak_bytes_in_use"]
    assert stated["peak_bytes_in_use"] <= 16_909_336_064


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert cell.config_name == "qwen3-next-80b-a3b-serve-ep4"
    assert cell.traffic_name == "concurrent-turns"
    assert len(cell.why) <= 200 and "host" in cell.why and "idle" in cell.why
    # held with >=: a later benchmark PR may list extend.unscoped_share for this cell
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS) | {
        "extend.attention_share", "extend.moe_share", "engine.step_ms", "engine.tokens_per_step",
        "device.idle_share.serve", "loadgen.late_p95_ms", "ttft_p95_s", "tpot_p95_s"}
    assert {"request_latency_mean_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    for name in NEW_METRICS:
        (entry,) = (m for m in BOOK.data["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"] and entry["moves"] == "request_latency_mean_s"
    # in the book once, whatever a later PR appends behind it
    assert [w["name"] for w in BOOK.data["workloads"]].count(CELL) == 1
    assert [c["name"] for c in BOOK.data["configs"]].count(cell.config_name) == 1
    bench_helpers.check_cell(BOOK, CELL)
    prompts, outputs = traffic["prompt_tokens"], traffic["output_tokens"]
    assert prompts == [1024, 4096, 2048, 12288, 3072, 8192, 1536, 15360, 6144, 2560]
    assert outputs == [256, 384, 512, 128, 320, 192, 768, 160, 448, 352]
    assert (sum(prompts) / 10, sum(outputs) / 10) == (5632.0, 352.0)
    engine = config["engine"]
    longest = max(p + o for p, o in zip(prompts, outputs))
    assert longest == 15520 <= 16384 and 16384 in engine["cache_buckets"]
    offsets = traffic["due_offsets"]
    assert offsets == [float(x) for x in np.random.default_rng(58).uniform(-0.3, 0.3, size=10)]
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0
    # the issue's rule: 0.8 of the knee, rounded down to whole cycles of the ten pairs in
    # the 51 s window and not under four cycles
    cycles = traffic["rate_rps"] * 51 / 10
    assert cycles == pytest.approx(round(cycles), abs=1e-3) and round(cycles) >= 4
    assert round(cycles) == max(4, int(0.8 * traffic["knee_rps"] * 51 / 10))
    assert int(traffic["rate_rps"] * 51) == 10 * round(cycles) >= 40
    assert str(traffic["knee_rps"]) in traffic["rate"] and "rung" in traffic["rate"]
    # the gate: 6144 + 64, 23 blocks reused with the state's snapshot
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (6144, 64)
    assert (6144 - 1) // 256 * 256 == 5888 and "5888" in traffic["gate"]
    assert "12,877,824" in traffic["gate"]
    assert traffic["trace_seconds"] <= 1.5 and traffic["trace_from"] >= 0.9     # short and late
