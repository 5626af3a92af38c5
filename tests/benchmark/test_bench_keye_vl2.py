"""Keye-VL-2.0's language model (``keye_vl2``): the serving path against the
benchmark's plain reference on seeded random weights at a small size on the CPU
(prefill in chunks, decode through the paged cache, the same prompt again from
the prefix cache, at contexts of three times ``topk`` and more), the selected
sets against the reference's, what the comparison's limit catches, the expert
layer's shares under the softmax router, the counters, the readers of the three
metrics, and the configuration's file. float32 throughout; the projections are
scaled up so that the logits are of order 1 and the selection matters."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import keye_vl2 as arch
from benchmark.reference import keye_vl2_reference as ref

TINY = bench_helpers.tiny("keye_vl2")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "keye-vl2-serve-long-context"
NEW_METRICS = ("extend.index_share", "sparse_attention.roofline", "engine.sparse_unread_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(manifest.published_keys(MODEL))
    # the init's 0.02 would leave every logit near 0 and every key alike: make
    # the projections matter, and leave the norms' scales at 1
    program = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key == "scale" else a * 8.0, cfg.init_params(3))
    return cfg, program


@pytest.fixture(scope="module")
def served(weights):
    """One request through the engine, twice: a prompt of 60 tokens (topk is
    16) in chunks of 32, then 8 decoded tokens across the 64-token bucket;
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
    # the same prompt again: three blocks of 16 from the prefix cache, the
    # indexer's keys with them, and the same bits
    assert (out["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 48)
    assert again["tokens"] == out["tokens"] and np.array_equal(again["logits"], out["logits"])
    # a cached token is K, V and an indexer key
    assert [a.shape for a in server._engine.pool.arenas] == [
        (3, 64, 16, 2, 16), (3, 64, 16, 2, 16), (3, 64, 16, 1, 8)]


def test_the_selected_sets_are_the_references(weights, wanted):
    """Every query of every layer selects the reference's keys: the prefill
    form over the whole sequence, and the decode form for the last query."""
    from ray_tpu.models import keye_vl2

    cfg, program = weights
    fed, _ = wanted
    want = ref.program_selection(program, fed, CONFIG)          # [layers, seq, seq]
    seq = len(fed)
    assert want.shape == (3, seq, seq) and want[:, -1].sum(-1).tolist() == [16] * 3
    assert (want.sum(-1) == np.minimum(np.arange(seq) + 1, 16)).all()
    probe = keye_vl2.make_probe_fn(cfg)

    def caches(n):
        return [jnp.zeros((3, 1, n) + tuple(each), jnp.float32) for each in cfg.cache_arrays]

    tokens = jnp.asarray([fed + [-1]], jnp.int32)               # 68 rows, one of them padding
    *_, counters, selected = probe(program, tokens, jnp.zeros((1,), jnp.int32), *caches(128))
    assert selected.shape == (3, 1, seq + 1, 128)
    assert np.array_equal(np.asarray(selected)[:, 0, :seq, :seq], want)
    assert not np.asarray(selected)[:, 0, seq].any()            # padding selects no key
    assert not np.asarray(selected)[:, 0, :, seq:].any()
    named = dict(zip(cfg.counters, np.asarray(counters).tolist()))
    assert named["sparse_queries"] == named["moe_tokens"] == 3 * seq
    assert named["sparse_keys_scored"] == 3 * seq * (seq + 1) // 2
    assert named["sparse_keys_attended"] == int(want.sum())
    assert named["sparse_slots_read"] == int(want.any(1).sum())
    # the decode form: the last query over a cache the others left behind
    _, _, k, v, i, _, _ = probe(
        program, tokens[:, :seq - 1], jnp.zeros((1,), jnp.int32), *caches(128))
    held = [jnp.pad(x, ((0, 0), (0, 0), (0, 128 - x.shape[2]), (0, 0), (0, 0))) for x in (k, v, i)]
    *_, counters, one = probe(
        program, tokens[:, seq - 1:seq], jnp.full((1,), seq - 1, jnp.int32), *held)
    assert np.array_equal(np.asarray(one)[:, 0, 0, :seq], want[:, -1])
    assert dict(zip(cfg.counters, np.asarray(counters).tolist()))["sparse_slots_read"] == 3 * 16


@pytest.mark.parametrize("wrong", ref.WRONG + (ref.LOWER,))
def test_the_limit_catches_each_omission(weights, served, wanted, wrong):
    """Attending densely, half of ``topk``, the indexer without its ReLU or
    without its weights, the router's weights not renormalised, weights a
    precision below: each is far outside what a run allows."""
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
    token needs no call), 3 layers, topk 16, 4 of 16 experts a token."""
    _, _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("moe_", "sparse_"))}
    assert d["moe_tokens"] == d["sparse_queries"] == 3 * (60 + 7)
    assert d["moe_assignments"] == 4 * d["moe_tokens"]           # every expert is held here
    assert d["sparse_keys_scored"] == 3 * sum(range(1, 68))
    assert d["sparse_keys_attended"] == 3 * sum(min(16, t) for t in range(1, 68))
    # every call gathers one lane: prefill at lengths 0 and 32 in the 64 bucket,
    # decode at lengths 60..66, of which 60..63 (with the new token) fit the 64
    # bucket and 64..66 need the 128
    assert d["sparse_slots_gathered"] == 3 * (64 + 64 + 4 * 64 + 3 * 128)
    # a decode call reads 16 slots a layer, a chunk between 16 and all it sees
    assert 3 * (7 * 16 + 2 * 16) < d["sparse_slots_read"] <= 3 * (7 * 16 + 32 + 60)


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_parts_add_up_to_the_whole_layer_under_the_softmax_router(shares):
    """Every share's part of the routed sum (16 experts over 1, 2 or 8 chips)
    is what the uncut reference gives for the layer."""
    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    n, d, f, routed, k = 24, 32, 16, 16, 4
    x = jax.random.normal(keys[0], (n, d))
    router = jax.random.normal(keys[1], (d, routed))
    wi = 0.3 * jax.random.normal(keys[2], (routed, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[3], (routed, f, d))
    weights, chosen = moe.softmax_top_k(x, router, k)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    valid, held = jnp.ones((n,), bool), routed // shares
    total, pairs = 0.0, 0
    for share in range(shares):
        lo = share * held
        part, counters = moe.held_experts_ffn(
            x, weights, chosen, valid, wi[lo:lo + held], wo[lo:lo + held], offset=lo)
        total, pairs = total + part, pairs + int(counters[1])
    assert pairs == n * k
    program = {"router": router[None], "wi": wi[None], "wo": wo[None]}
    with jax.default_matmul_precision("highest"):
        top, picked = ref._route(x, program, 0, k, True, False)
        want = sum(
            jnp.where(picked == e, top, 0.0).sum(-1)[:, None] * ref._expert(x, program, 0, e, False)
            for e in range(routed))
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-5)


# -- the readers ----------------------------------------------------------------


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "sparse_keys_scored": 8_000_000_000, "sparse_keys_attended": 1_000_000_000,
            "sparse_slots_read": 30_000_000, "sparse_slots_gathered": 120_000_000,
            "cache_tokens": 10_000_000, "phase_s": {"step": 20.0},
            # the recorded steps' own counts: more of them chunks than an eighth of the load's
            "traced": {
                "sparse_keys_scored": 1_200_000_000, "sparse_keys_attended": 100_000_000,
                "sparse_slots_read": 4_000_000, "sparse_slots_gathered": 15_000_000,
                "cache_tokens": 1_500_000, "phase_s": {"step": 2.4},
            },
        },
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 50, "in_step_s": 2.5},
            "ops_by_scope": [
                ["extend.attention", 0.5], ["extend.attention.select", 0.3],
                ["extend.attention.index", 0.2], ["extend.moe.experts", 0.4], ["(no scope)", 0.6],
            ],
        },
    }


def test_the_three_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.index_share"](run) == pytest.approx(25.0)
    assert read["engine.sparse_unread_share"](run) == pytest.approx(75.0)
    # the recorded steps' own pairs, keys and slots, in 1.0 s
    flops = 2 * 16 * 64 * 1.2e9 + 4 * 32 * 128 * 1e8
    moved = 2 * (64 * 6 * 1.5e6 + 2 * 4 * 128 * 4e6)
    at_peak = max(flops / 197e12, moved / 819e9)
    assert read["sparse_attention.roofline"](run) == pytest.approx(100 * at_peak / 1.0)
    assert 0 < read["sparse_attention.roofline"](run) < 100
    # a run of a program without the counters or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    assert read["sparse_attention.roofline"](bare) is None
    assert read["engine.sparse_unread_share"](bare) is None
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.attention", 1.0]]}}
    assert read["extend.index_share"](no_scopes) is None
    assert read["sparse_attention.roofline"](no_scopes) is None
    assert all(read[n]({}) is None for n in NEW_METRICS)


def test_the_sparse_layers_required_work():
    with open(BOOK.root + "/benchmark/configs/keye-vl2-30b-a3b-serve.json") as f:
        keys = json.load(f)
    work = arch.sparse_work(keys, {
        "sparse_keys_scored": 10, "sparse_keys_attended": 7, "sparse_slots_read": 5,
        "cache_tokens": 3})
    assert work["flops"] == 2 * 16 * 64 * 10 + 4 * 32 * 128 * 7
    assert work["bytes"] == 2 * (64 * 6 * 3 + 2 * 4 * 128 * 5)
    assert arch.expert_params(keys) == 4_718_592
    # q, k, v, o + the indexer + the router + 8 experts a layer, and the head
    assert arch.matmul_params(keys) == 6 * (18_874_368 + 2_260_992 + 262_144 + 8 * 4_718_592) + (
        2048 * 151936)
    assert arch.train_step_flops(keys, 1, 4096) > 6 * arch.matmul_params(keys) * 4096


# -- the configuration -------------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_with_the_depth_cut_alone():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert config["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in ("num_hidden_layers", "model_type"):
            assert config[key] == value, key
        if key != "model_type":
            assert published[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert 4 <= config["num_hidden_layers"] <= 6 and published["num_hidden_layers"] == 48
    for key in arch.WIDTHS:
        assert config[key] == row["config"][key], key
    # the harness hands an architecture the top-level scalars: the indexer's stand there too
    assert {k: config[k] for k in ("indexer_head_dim", "indexer_num_heads",
                                   "indexer_num_kv_heads", "topk")} == {
        k: v for k, v in config["sa_config"].items() if not k.endswith("chunk_size")}
    cfg = arch.program_config(manifest.published_keys(config))
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_dim) == (128, 8, 768)
    assert (cfg.index_heads, cfg.index_dim, cfg.topk) == (16, 64, 2048)
    assert cfg.cache_arrays == ((4, 128), (4, 128), (1, 64))
    # 4.375 B parameters = 8.75 GB in bfloat16 at depth 6: the file's own arithmetic
    import dataclasses
    assert dataclasses.replace(cfg, num_layers=6).num_params() == pytest.approx(4.375e9, rel=1e-3)
    assert "8.75 GB" in config["deployment"] and "eight pipeline stages" in config["deployment"]
    assert config["assumed"] and len(config["departures"]) >= 2 and config["reference"]["why"]


def test_the_cell_is_the_issues_traffic():
    cell = BOOK.cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and traffic["generator"] == "serve_open_loop"
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS) | {
        "extend.moe_share", "extend.attention_share", "engine.step_ms", "device.idle_share.serve"}
    assert not {"moe.experts_roofline", "engine.window_outside_share"} & {
        m["name"] for m in cell.per_layer}
    assert traffic["output_tokens"] == [192, 64, 256, 48, 128, 32, 96, 64]
    full = [3072, 8192, 4096, 16384, 6144, 24576, 5120, 12288]
    assert traffic["prompt_tokens"] in (full, [p // 2 for p in full])
    assert all(p > config["topk"] for p in traffic["prompt_tokens"]) or traffic[
        "prompt_tokens"][0] == 1536
    assert (traffic["gate_prompt_tokens"], traffic["gate_new_tokens"]) == (6144, 16)
    engine = config["engine"]
    longest = max(p + o for p, o in zip(traffic["prompt_tokens"], traffic["output_tokens"]))
    assert longest <= engine["cache_buckets"][-1] <= 32768
    assert engine["block_size"] == 256 and engine["prefill_chunk"] == 512
    assert engine["prefill_lanes"] == 1 and engine["num_blocks"] * 256 >= 98304
    assert all(abs(o) <= 0.3 for o in traffic["due_offsets"])
    assert np.allclose(
        traffic["due_offsets"], np.random.default_rng(32).uniform(-0.3, 0.3, size=8))
    cycles = traffic["rate_rps"] * 51 / 8
    assert cycles == pytest.approx(round(cycles), abs=1e-4) and traffic["rate_rps"] * 51 >= 23.9
    assert 0.7 <= traffic["rate_rps"] / traffic["knee_rps"] <= 0.9
    assert (traffic["lead_in_requests"], traffic["lead_out_requests"]) == (4, 4)
    assert traffic["drain_limit_s"] == 60.0 and 1.2 <= traffic["trace_seconds"] <= 2.0


def test_a_block_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.program_config({**keys, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        arch.program_config({**keys, "indexer_num_kv_heads": 2})
