"""Command A+ (``cohere2_moe``): the serving path against the benchmark's plain
reference on seeded random weights at a small size on the CPU (prefill in
chunks, then decode through the paged cache, past the tiny window and across a
cache bucket), the expert layer's shares against the uncut layer, what the
comparison's limit catches, the counters the engine keeps, the readers of the
four metrics, and the configuration's file. float32 throughout; the weights are
scaled up so that the logits are of order 1."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers
from benchmark import manifest, yardstick
from benchmark.models import cohere2_moe as arch
from benchmark.reference import cohere2_moe_reference as ref

TINY = bench_helpers.tiny("cohere2_moe")
MODEL = TINY["model"]
LIMIT = TINY["reference"]["max_logits_error"]
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
BOOK = manifest.Manifest(bench_helpers.REPO)
CELL = "cmd-a-plus-serve-mixed-lengths"
NEW_METRICS = (
    "extend.moe_share", "extend.attention_share", "moe.experts_roofline",
    "engine.window_outside_share",
)


@pytest.fixture(scope="module")
def weights():
    cfg = arch.program_config(MODEL)
    program = jax.tree.map(
        # the init's 0.02 would leave every logit near 0: make the weights matter
        lambda a: a * 8.0 if a.ndim > 1 and a.shape[-1] != 1 else a, arch.seeded_params(cfg, 3),
    )
    program["blocks"]["layers"]["ln"]["scale"] = program["blocks"]["layers"]["ln"]["scale"] / 8.0
    return cfg, program, ref.from_program_params(program, MODEL)


@pytest.fixture(scope="module")
def served(weights):
    """One request through the engine: a prompt of 60 tokens (past the window
    of 24) in chunks of 32, then 8 decoded tokens across the 64-token bucket."""
    from ray_tpu.serve import llm

    cfg, program, _ = weights
    server = llm.LLMServer(cfg, params=program, **ENGINE)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, cfg.vocab_size, size=60)]
    before = server.kv_stats()
    out = server({"prompt": prompt, "max_new_tokens": 8, "return_logits": True})
    return server, prompt, out, before, server.kv_stats()


def test_prefill_in_chunks_then_decode_through_the_paged_cache_matches_the_full_forward(
    weights, served
):
    cfg, _, reference = weights
    _, prompt, out, _, _ = served
    fed = prompt + out["tokens"][:-1]
    want = ref.forward(reference, fed, MODEL)[len(prompt) - 1:]
    assert out["logits"].shape == want.shape == (8, cfg.vocab_size)
    assert float(jnp.abs(want).max()) > 0.3                 # not all but zero
    assert yardstick.logits_error(out["logits"], want) < LIMIT
    np.testing.assert_allclose(out["logits"], want, atol=1e-4, rtol=1e-4)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]
    # the pool stores K/V heads, not query heads
    assert served[0]._engine.pool.k_data.shape == (6, 64, 16, 2, 16)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_limit_catches_each_omission(weights, served, wrong):
    """Sliding layers without their window, full layers with a rotation, the
    shared experts summed: each is far outside what a run allows."""
    _, _, reference = weights
    _, prompt, out, _, _ = served
    fed = prompt + out["tokens"][:-1]
    off = ref.forward(reference, fed, MODEL, wrong=wrong)[len(prompt) - 1:]
    assert yardstick.logits_error(out["logits"], off) > 100 * LIMIT


def test_the_reference_a_piece_at_a_time_is_the_reference(weights):
    """What the runs on the chip call: the program's own weights, one K/V head,
    one block of queries and one expert at a time."""
    cfg, program, reference = weights
    tokens = [int(t) for t in np.random.default_rng(2).integers(0, cfg.vocab_size, size=45)]
    config = {**MODEL, "reference": TINY["reference"]}
    want = ref.forward(reference, tokens, MODEL)
    assert yardstick.logits_error(ref.program_logits(program, tokens, config, 3), want[-3:]) < 1e-5
    assert ref.program_loss(program, np.asarray([tokens]), config) == pytest.approx(
        float(ref.next_token_loss(want, tokens)), abs=1e-5)
    shallow = {**config, "num_hidden_layers": MODEL["num_hidden_layers"] - 1}
    assert yardstick.logits_error(ref.program_logits(program, tokens, shallow, 3), want[-3:]) > 0.1
    # each omission, and weights a precision below, as the runs on the chip measure them
    for wrong in ref.WRONG:
        assert yardstick.logits_error(
            ref.program_logits(program, tokens, config, 3, wrong=wrong),
            ref.forward(reference, tokens, MODEL, wrong=wrong)[-3:]) < 1e-5
    lower = ref.program_logits(program, tokens, config, 3, wrong=ref.LOWER)
    assert yardstick.logits_error(lower, want[-3:]) > 10 * LIMIT


def test_cached_and_uncached_asks_are_bitwise_equal(served):
    """The gate's shape: 40 tokens end in (1 lane, 8 tokens) whether the first
    32 are prefilled (32 + 8) or reused from the prefix cache."""
    server = served[0]
    ask = {
        "prompt": [int(t) for t in np.random.default_rng(6).integers(0, 256, size=40)],
        "max_new_tokens": 3, "return_logits": True,
    }
    first, again = server(ask), server(ask)
    assert (first["prefix_cached_tokens"], again["prefix_cached_tokens"]) == (0, 32)
    assert first["tokens"] == again["tokens"]
    assert np.array_equal(first["logits"], again["logits"])


def test_the_counters_count_what_a_hand_worked_request_says(served):
    """60 prompt tokens in chunks of 32 + 28, then 7 decode calls (the 8th
    token needs no call), 6 expert layers, 4 sliding layers, window 24."""
    _, _, _, before, after = served
    d = {k: after[k] - before[k] for k in after if k.startswith(("moe_", "window_"))}
    assert d["moe_tokens"] == 6 * (60 + 7)
    # 4 choices of 16 experts a token, 4 held: between none and all held here
    assert 0 < d["moe_assignments"] < 4 * d["moe_tokens"]
    calls = 2 + 7
    assert 0 < d["moe_experts_hit"] <= 4 * 6 * calls
    assert d["moe_experts_hit"] <= d["moe_assignments"]
    assert d["moe_load_max"] <= d["moe_assignments"] <= 4 * d["moe_load_max"]
    # every call gathers one lane: prefill at lengths 0 and 32 in the 64 bucket,
    # decode at lengths 60..66, of which 60..63 (with the new token) fit the 64
    # bucket and 64..66 need the 128
    slots = 64 + 64 + 4 * 64 + 3 * 128
    outside = (32 - 23) + sum(length - 23 for length in range(60, 67))
    assert (d["window_slots"], d["window_slots_outside"]) == (4 * slots, 4 * outside)


# -- the expert layer ----------------------------------------------------------


def _layer(seed, n=24, d=32, f=16, routed=16, k=4):
    from ray_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (n, d))
    router = jax.random.normal(keys[1], (d, routed))
    wi = 0.3 * jax.random.normal(keys[2], (routed, d, 2 * f))
    wo = 0.3 * jax.random.normal(keys[3], (routed, f, d))
    return moe, x, router, wi, wo, k


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_routed_parts_add_up_to_the_whole_layer(shares):
    """Every chip's part of the routed sum, with the shared experts counted
    once, is what the uncut reference gives for the layer."""
    moe, x, router, wi, wo, k = _layer(0)
    f, held = wo.shape[1], wi.shape[0] // shares
    weights, chosen = moe.sigmoid_top_k(x, router, k)
    valid = jnp.ones((x.shape[0],), bool)
    total, pairs = 0.0, 0
    for share in range(shares):
        lo = share * held
        part, counters = moe.held_experts_ffn(
            x, weights, chosen, valid, wi[lo:lo + held], wo[lo:lo + held], offset=lo)
        total, pairs = total + part, pairs + int(counters[1])
    assert pairs == x.shape[0] * k                      # every choice computed somewhere, once
    experts = [(wi[e, :, :f], wi[e, :, f:], wo[e]) for e in range(wi.shape[0])]
    layer = {"router": router, "experts": experts, "shared": experts[:2]}
    model = {"num_experts_per_tok": k, "expert_offset": 0}
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(x, layer, model)
        shared = sum(ref.expert(x, *w) for w in experts[:2]) / 2
    np.testing.assert_allclose(total + shared, want, atol=1e-5, rtol=1e-5)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """A capacity would drop here; this layer computes every pair, and padding
    computes nothing."""
    moe, x, router, wi, wo, k = _layer(1)
    x = jnp.abs(x)                                       # every token scores alike in sign
    router = router.at[:, 4:8].set(jnp.abs(router[:, 4:8]) + 1.0).at[:, :4].set(-1.0)
    weights, chosen = moe.sigmoid_top_k(x, router, k)
    assert set(np.unique(chosen).tolist()) == {4, 5, 6, 7}
    valid = jnp.arange(x.shape[0]) < 20                  # four padded tokens
    got, counters = moe.held_experts_ffn(
        x, weights, chosen, valid, wi[4:8], wo[4:8], offset=4)
    assert counters.tolist() == [20, 20 * k, 4, 20]
    f = wo.shape[1]
    dense = jnp.zeros_like(x)
    for e in range(4, 8):
        weight = jnp.where(chosen == e, weights, 0.0).sum(-1)
        dense = dense + weight[:, None] * ref.expert(x, wi[e, :, :f], wi[e, :, f:], wo[e])
    np.testing.assert_allclose(got[:20], dense[:20], atol=1e-5, rtol=1e-5)
    assert not np.asarray(got[20:]).any()


def test_a_layer_of_a_stack_is_computed_in_place_as_if_sliced_out():
    moe, x, router, wi, wo, k = _layer(2)
    weights, chosen = moe.sigmoid_top_k(x, router, k)
    valid = jnp.ones((x.shape[0],), bool)
    stack_wi, stack_wo = (w.reshape((4, 4) + w.shape[1:]) for w in (wi, wo))
    for layer in (0, 2, 3):
        alone = moe.held_experts_ffn(x, weights, chosen, valid, stack_wi[layer], stack_wo[layer], 8)
        stacked = jax.jit(moe.held_experts_ffn, static_argnums=(6,))(
            x, weights, chosen, valid, stack_wi, stack_wo, 8, jnp.int32(layer))
        np.testing.assert_allclose(stacked[0], alone[0], atol=1e-6)
        assert stacked[1].tolist() == alone[1].tolist()


def test_the_chips_grouped_matmul_is_the_ragged_dot(monkeypatch):
    """The megablox kernel the TPU runs, interpreted here, against XLA's
    ragged dot, with empty groups and rows that belong to none."""
    from ray_tpu.models import moe

    monkeypatch.setattr(moe, "GMM_TILING", (8, 128, 128))
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    rows = jax.random.normal(keys[0], (40, 256))
    w = jax.random.normal(keys[1], (6, 256, 128))
    sizes = jnp.asarray([3, 0, 10, 0, 7, 5], jnp.int32)
    want = moe.grouped_matmul(rows, w, sizes)
    got = moe.grouped_matmul(rows, w, sizes, interpret=True)
    assert got.shape == want.shape == (40, 128)
    np.testing.assert_allclose(got[:25], want[:25], atol=1e-3, rtol=1e-4)


# -- the readers ----------------------------------------------------------------


def _recorded_run():
    """A traced run as the generator hands it over, with round numbers."""
    return {
        "kind": "serve", "device": {"kind": "TPU v5 lite"},
        "counters": {
            "moe_tokens": 40_000, "moe_assignments": 40_000, "moe_experts_hit": 6_000,
            "window_slots": 8_000, "window_slots_outside": 1_000,
            "phase_n": {"dispatch": 500}, "phase_s": {"step": 20.0},
            # the recorded steps' own counts: more of them chunks than an eighth of the load's
            "traced": {
                "moe_tokens": 8_000, "moe_assignments": 9_000, "moe_experts_hit": 900,
                "window_slots": 1_200, "window_slots_outside": 100,
                "phase_n": {"dispatch": 60}, "phase_s": {"step": 2.4},
            },
        },
        "trace": {
            "busy_s": 2.0, "window_s": 6.0, "engine": {"steps": 50, "in_step_s": 2.5},
            "ops_by_scope": [
                ["extend.moe.experts", 0.6], ["extend.attention", 0.5], ["extend.moe.shared", 0.3],
                ["extend.moe.route", 0.1], ["extend.logits", 0.1], ["(no scope)", 0.4],
            ],
        },
    }


def test_the_four_readers_read_a_recorded_run():
    run = _recorded_run()
    read = {name: BOOK.reader(name) for name in NEW_METRICS}
    assert read["extend.moe_share"](run) == pytest.approx(50.0)
    assert read["extend.attention_share"](run) == pytest.approx(25.0)
    assert read["engine.window_outside_share"](run) == pytest.approx(12.5)
    # the recorded steps' own pairs, tokens, hit experts and calls, in 0.9 s
    expert = 3 * 4096 * 4096
    flops = 2 * expert * (9_000 + 4 * 8_000)
    moved = 2 * expert * (900 + 4 * 4 * 60)
    at_peak = max(flops / 197e12, moved / 819e9)
    assert read["moe.experts_roofline"](run) == pytest.approx(100 * at_peak / 0.9)
    assert 0 < read["moe.experts_roofline"](run) < 100
    # a run of a program without the counters or the scopes (the parent's): nothing
    bare = {**run, "counters": {"steps": 5, "phase_s": {"step": 1.0}}}
    assert read["moe.experts_roofline"](bare) is None
    assert read["engine.window_outside_share"](bare) is None
    no_scopes = {**run, "trace": {**run["trace"], "ops_by_scope": [["extend.mlp", 1.0]]}}
    assert all(read[n](no_scopes) is None for n in NEW_METRICS[:3])


# -- the configuration -------------------------------------------------------------


def test_the_configuration_states_the_share_and_is_the_published_block():
    cell = BOOK.cell(CELL)
    config, published = cell.config, cell.config["published"]
    assert cell.chips == 1 and cell.traffic["generator"] == "serve_open_loop"
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS)
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    # the router's width is the published count of experts, never cut
    assert config["router_experts"] == published["num_experts"] == 128
    assert config["expert_offset"] + config["num_experts"] <= config["router_experts"]
    cfg = arch.program_config(manifest.published_keys(config))
    # the list the source gives is the pattern the program derives from layer_switch
    pattern = ["sliding_attention" if s else "full_attention" for s in cfg.sliding_layers]
    assert config["layer_types"][:cfg.num_layers] == pattern
    import dataclasses
    whole = dataclasses.replace(cfg, num_layers=published["num_hidden_layers"]).sliding_layers
    assert published["layer_types"] == [
        "sliding_attention" if s else "full_attention" for s in whole]
    # 4.73 B parameters = 9.47 GB in bfloat16: the file's own arithmetic
    assert cfg.num_params() == pytest.approx(4.733e9, rel=1e-3)
    assert "9.47 GB" in config["deployment"] and "eight chips" in config["deployment"]
    assert cfg.kv_heads == 8 and cfg.num_heads // cfg.kv_heads == 16
    # the traffic the issue names, and a gate past the window
    traffic = cell.traffic
    assert traffic["prompt_tokens"] == [192, 5120, 96, 768, 384, 7936, 1536, 128, 2560, 256]
    assert traffic["output_tokens"] == [48, 32, 96, 24, 128, 16, 64, 40, 32, 80]
    assert traffic["gate_prompt_tokens"] > config["sliding_window"]
    longest = max(p + o for p, o in zip(traffic["prompt_tokens"], traffic["output_tokens"]))
    assert longest <= config["engine"]["cache_buckets"][-1]
    assert all(abs(o) <= 0.3 for o in traffic["due_offsets"])
    assert (traffic["rate_rps"] * 51 / 10) == pytest.approx(round(traffic["rate_rps"] * 51 / 10))


def test_a_block_the_program_does_not_have_is_refused():
    keys = manifest.published_keys(BOOK.cell(CELL).config)
    with pytest.raises(ValueError, match="softmax"):
        arch.program_config({**keys, "expert_selection_fn": "softmax"})
    with pytest.raises(ValueError, match="not among the 128"):
        arch.program_config({**keys, "expert_offset": 120})


def test_the_expert_layers_required_work():
    keys = {"hidden_size": 4096, "intermediate_size": 4096, "num_shared_experts": 4,
            "num_hidden_layers": 4, "param_dtype": "bfloat16"}
    assert arch.expert_params(keys) == 50_331_648
    work = arch.experts_work(keys, {
        "moe_assignments": 10, "moe_tokens": 8, "moe_experts_hit": 7, "phase_n": {"dispatch": 2},
    })
    assert work["flops"] == 6 * 4096 * 4096 * (10 + 4 * 8)
    assert work["bytes"] == 2 * 50_331_648 * (7 + 4 * 4 * 2)
