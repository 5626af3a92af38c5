"""Command A+ (``model_type`` ``cohere2_moe``): from the published
``config.json`` keys to the program's ``Cohere2MoeConfig``, seeded weights made
on the device in one jitted call, and the operations and bytes the expert layer
of one chip's share requires."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "num_experts_per_tok", "num_shared_experts", "sliding_window",
    "layer_switch",
)


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run:
    ``num_experts`` is the experts **held here**, from ``expert_offset`` on;
    ``router_experts`` (the benchmark's key) the experts the router scores,
    which is the published ``num_experts`` and nothing else (the nested
    ``published`` group does not reach this function:
    ``tests/benchmark/test_bench_cohere2_moe.py`` holds the file to it);
    ``compute_dtype`` / ``param_dtype`` are the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.cohere2_moe import Cohere2MoeConfig

    wanted = {
        "hidden_act": "silu", "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
        "position_embedding_type": "rope_gptj", "rotary_pct": 1,
        "order_of_interleaved_layers": "local_attn_first", "use_parallel_block": True,
        "use_gated_activation": True, "use_qk_norm": False, "attention_bias": False,
        "tie_word_embeddings": True, "first_k_dense_replace": 0,
        "shared_expert_combination_strategy": "average",
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one cohere2_moe block, and not one with {differ}")
    return Cohere2MoeConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        embed_dim=keys["hidden_size"], num_heads=keys["num_attention_heads"],
        kv_heads=keys["num_key_value_heads"], head_dim=keys["head_dim"],
        expert_dim=keys["intermediate_size"], router_experts=keys["router_experts"],
        num_experts=keys["num_experts"], expert_offset=keys["expert_offset"],
        experts_per_token=keys["num_experts_per_tok"],
        shared_experts=keys["num_shared_experts"], sliding_window=keys["sliding_window"],
        layer_switch=keys["layer_switch"], rope_base=float(keys["rope_theta"]),
        norm_eps=keys["layer_norm_eps"], logit_scale=float(keys["logit_scale"]),
        max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they
    are served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_heads} heads over {cfg.kv_heads} K/V x "
        f"{cfg.head_dim} / experts {cfg.num_experts} held of {cfg.router_experts} from "
        f"{cfg.expert_offset}, {cfg.experts_per_token} a token, {cfg.shared_experts} shared, "
        f"width {cfg.expert_dim} / window {cfg.sliding_window}, every {cfg.layer_switch}th "
        f"layer full / vocab {cfg.vocab_size} / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.2f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed or shared expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["intermediate_size"]


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: q, k, v,
    o, the router, the shared experts and ``num_experts_per_tok`` routed
    experts of every layer (were each of its choices held here), and the tied
    output head. The input embedding is a gather."""
    d = keys["hidden_size"]
    attention = 2 * d * keys["head_dim"] * (
        keys["num_attention_heads"] + keys["num_key_value_heads"])
    experts = (keys["num_shared_experts"] + keys["num_experts_per_tok"]) * expert_params(keys)
    per_layer = attention + d * keys["router_experts"] + experts
    return keys["num_hidden_layers"] * per_layer + d * keys["vocab_size"]


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (8 bytes a parameter: four layers of
    the share are 25 GB); the harness's contract lists the entry point. The
    count is ``matmul_params``' upper bound plus causal attention as a full
    layer sees it, windows not taken off."""
    tokens = batch * seq
    heads_x_dim = keys["num_attention_heads"] * keys["head_dim"]
    attention = 12.0 * keys["num_hidden_layers"] * batch * heads_x_dim * seq * (seq + 1) / 2.0
    return 6.0 * matmul_params(keys) * tokens + attention


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the expert layers of the counted device calls had to do, from the
    engine's counters (deltas of ``kv_stats``): ``flops`` = 2 per parameter of
    an expert for every token-expert pair computed here and for every token
    through every shared expert; ``bytes`` = the weights that had to be read
    once a call, i.e. an expert's for every (call, layer, held expert with a
    token) and the shared experts' for every (call, layer). Activations are
    not counted, so both are lower bounds of what must move."""
    per_expert = expert_params(keys)
    shared = keys["num_shared_experts"]
    calls = counters["phase_n"]["dispatch"]
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    return {
        "flops": 2.0 * per_expert * (
            counters["moe_assignments"] + shared * counters["moe_tokens"]),
        "bytes": float(itemsize * per_expert * (
            counters["moe_experts_hit"] + shared * keys["num_hidden_layers"] * calls)),
    }
