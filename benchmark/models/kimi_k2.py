"""Kimi-K2-Instruct (``model_type`` ``kimi_k2``): from the published
``config.json`` keys to the program's ``KimiK2Config``, seeded weights made on
the device in one jitted call, and the operations and bytes the latent
attention and the expert layers require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
)

# ``rope_scaling``'s keys as they stand flat at the top level of a file (the
# harness hands an architecture the top-level scalars only)
YARN = {
    "rope_scaling_type": "type", "rope_scaling_factor": "factor",
    "rope_scaling_original_max_position_embeddings": "original_max_position_embeddings",
    "rope_scaling_beta_fast": "beta_fast", "rope_scaling_beta_slow": "beta_slow",
    "rope_scaling_mscale": "mscale", "rope_scaling_mscale_all_dim": "mscale_all_dim",
}


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run:
    ``n_routed_experts`` is the experts **held here**, from ``expert_offset`` on;
    ``router_experts`` (the benchmark's key) the experts the router scores, which
    is the published ``n_routed_experts`` and nothing else; ``rope_scaling``'s
    keys flat (``YARN``; ``tests/benchmark/test_bench_kimi_k2.py`` holds them
    equal to the group's); ``e_score_correction_bias_std`` the spread of the
    seeded bias; ``compute_dtype`` / ``param_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.kimi_k2 import KimiK2Config

    wanted = {
        "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "attention_bias": False, "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "rope_scaling_type": "yarn",
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one kimi_k2 block, and not one with {differ}")
    if keys["rope_scaling_mscale"] != keys["rope_scaling_mscale_all_dim"]:
        raise ValueError("the program does not scale cos and sin: mscale and mscale_all_dim differ")
    if keys["num_key_value_heads"] != keys["num_attention_heads"]:
        raise ValueError("latent attention has one key and one value a query head")
    return KimiK2Config(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        dense_layers=keys["first_k_dense_replace"], embed_dim=keys["hidden_size"],
        num_heads=keys["num_attention_heads"], q_rank=keys["q_lora_rank"],
        kv_rank=keys["kv_lora_rank"], rope_dim=keys["qk_rope_head_dim"],
        nope_dim=keys["qk_nope_head_dim"], v_dim=keys["v_head_dim"],
        mlp_dim=keys["intermediate_size"], expert_dim=keys["moe_intermediate_size"],
        router_experts=keys["router_experts"], num_experts=keys["n_routed_experts"],
        expert_offset=keys["expert_offset"], experts_per_token=keys["num_experts_per_tok"],
        shared_experts=keys["n_shared_experts"],
        routed_scale=float(keys["routed_scaling_factor"]),
        bias_std=float(keys["e_score_correction_bias_std"]),
        rope_base=float(keys["rope_theta"]), rope_factor=float(keys["rope_scaling_factor"]),
        rope_original_len=keys["rope_scaling_original_max_position_embeddings"],
        rope_beta_fast=float(keys["rope_scaling_beta_fast"]),
        rope_beta_slow=float(keys["rope_scaling_beta_slow"]),
        rope_mscale_all_dim=float(keys["rope_scaling_mscale_all_dim"]),
        norm_eps=keys["rms_norm_eps"], max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they
    are served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_heads} heads of {cfg.nope_dim} + {cfg.rope_dim} "
        f"over one latent of {cfg.kv_rank} + {cfg.rope_dim}, queries through {cfg.q_rank} / "
        f"{cfg.dense_layers} dense layer of {cfg.mlp_dim} / experts {cfg.num_experts} held of "
        f"{cfg.router_experts} from {cfg.expert_offset}, {cfg.experts_per_token} a token, "
        f"{cfg.shared_experts} shared, width {cfg.expert_dim} / vocab {cfg.vocab_size} / depth "
        f"{cfg.num_layers} / params {cfg.param_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert, or of one shared expert's worth: gate,
    up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def attention_params(keys: Dict[str, Any]) -> int:
    """The matrices of one layer's attention: both down-projections, ``W_qb``,
    ``W_kvb`` and ``W_o``."""
    d, heads = keys["hidden_size"], keys["num_attention_heads"]
    return (
        d * keys["q_lora_rank"]
        + keys["q_lora_rank"] * heads * (keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"])
        + d * (keys["kv_lora_rank"] + keys["qk_rope_head_dim"])
        + keys["kv_lora_rank"] * heads * (keys["qk_nope_head_dim"] + keys["v_head_dim"])
        + heads * keys["v_head_dim"] * d)


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: the
    attention of every layer, the dense layers' MLP, the router, the shared
    expert and ``num_experts_per_tok`` routed experts of every expert layer
    (fewer where the chosen are held elsewhere), and the untied output head. The
    input embedding is a gather."""
    d, dense = keys["hidden_size"], keys["first_k_dense_replace"]
    routed = (
        d * keys["router_experts"]
        + (keys["num_experts_per_tok"] + keys["n_shared_experts"]) * expert_params(keys))
    return (
        keys["num_hidden_layers"] * attention_params(keys)
        + dense * 3 * d * keys["intermediate_size"]
        + (keys["num_hidden_layers"] - dense) * routed + d * keys["vocab_size"])


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (the repo's train step has no expert
    layer); the harness's contract lists the entry point. The count is
    ``matmul_params`` plus causal attention in the expanded form."""
    tokens = batch * seq
    per_pair = 2.0 * keys["num_attention_heads"] * (
        keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"] + keys["v_head_dim"])
    attention = 3.0 * keys["num_hidden_layers"] * batch * per_pair * seq * (seq + 1) / 2.0
    return 6.0 * matmul_params(keys) * tokens + attention


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the expert layers of the counted device calls had to do, from the
    engine's counters (deltas of ``kv_stats``), as ``cohere2_moe.experts_work``
    counts it: ``flops`` = 2 per parameter of an expert for every token-expert
    pair computed here and for every token through the shared expert; ``bytes``
    = an expert's weights for every (call, layer, held expert with a token) and
    the shared expert's for every (call, expert layer). Activations are not
    counted, so both are lower bounds of what must move."""
    per_expert, shared = expert_params(keys), keys["n_shared_experts"]
    expert_layers = keys["num_hidden_layers"] - keys["first_k_dense_replace"]
    calls = counters["phase_n"]["dispatch"]
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    return {
        "flops": 2.0 * per_expert * (
            counters["moe_assignments"] + shared * counters["moe_tokens"]),
        "bytes": float(itemsize * per_expert * (
            counters["moe_experts_hit"] + shared * expert_layers * calls)),
    }


def latent_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the attend over the cached latents of the counted device calls had
    to do, from the engine's counters (deltas of ``kv_stats``): ``flops`` = for
    every live causal query-key pair and query head, 2 a feature of the row
    scored and of the latent summed in the absorbed form (``kv_lora_rank +
    qk_rope_head_dim`` and ``kv_lora_rank``), and of a head's own key and value
    in the expanded form (``qk_nope_head_dim + qk_rope_head_dim`` and
    ``v_head_dim``); ``bytes`` = the rows of the live slots, read once a call and
    layer (``cache_tokens``, the live slots the engine gathered, times the
    layers). The projections, the absorption, ``W_kvb`` over expanded rows,
    activations and writes are not counted, so both are lower bounds of what
    must move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["compute_dtype"]]
    heads, rank, rope = (
        keys["num_attention_heads"], keys["kv_lora_rank"], keys["qk_rope_head_dim"])
    absorbed = 2.0 * heads * (rank + rope + rank)
    expanded = 2.0 * heads * (keys["qk_nope_head_dim"] + rope + keys["v_head_dim"])
    return {
        "flops": (
            absorbed * counters.get("mla_pairs_absorbed", 0)
            + expanded * counters.get("mla_pairs_expanded", 0)),
        "bytes": float(
            itemsize * (rank + rope) * keys["num_hidden_layers"] * counters["cache_tokens"]),
    }
