"""GLM-5 (``model_type`` ``glm_moe_dsa``): from the published ``config.json`` keys to
the program's ``GlmMoeDsaConfig``, seeded weights made on the device in one jitted
call, and the operations and bytes the indexer, the attend over the selected latent
rows and the expert layers require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "index_head_dim", "index_n_heads",
    "index_topk", "num_experts_per_tok", "n_shared_experts",
)


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run:
    ``n_routed_experts`` is the experts **held here**, from ``expert_offset`` on;
    ``router_experts`` (the benchmark's key) the experts the router scores, which is
    the published ``n_routed_experts`` and nothing else; ``rope_theta`` is
    ``rope_parameters``'s, flat (``tests/benchmark/test_bench_glm_moe_dsa.py`` holds it
    equal to the group's); ``index_rope_head_dim`` and ``index_norm_eps`` are the
    published inference code's, which the source's keys do not state;
    ``e_score_correction_bias_std`` and ``index_k_norm_bias_std`` the spreads of the
    seeded draws; ``compute_dtype`` / ``param_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.glm_moe_dsa import GlmMoeDsaConfig

    wanted = {
        "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "attention_bias": False, "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "rope_interleave": True, "indexer_rope_interleave": True,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one glm_moe_dsa block, and not one with {differ}")
    if keys["num_key_value_heads"] != keys["num_attention_heads"]:
        raise ValueError("latent attention has one key and one value a query head")
    if keys["qk_head_dim"] != keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not the two parts of a head's key")
    if keys["head_dim"] != keys["qk_rope_head_dim"]:
        raise ValueError("head_dim is the width the rotation turns: qk_rope_head_dim")
    return GlmMoeDsaConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        dense_layers=keys["first_k_dense_replace"], embed_dim=keys["hidden_size"],
        num_heads=keys["num_attention_heads"], q_rank=keys["q_lora_rank"],
        kv_rank=keys["kv_lora_rank"], rope_dim=keys["qk_rope_head_dim"],
        nope_dim=keys["qk_nope_head_dim"], v_dim=keys["v_head_dim"],
        index_heads=keys["index_n_heads"], index_dim=keys["index_head_dim"],
        index_rope_dim=keys["index_rope_head_dim"], topk=keys["index_topk"],
        mlp_dim=keys["intermediate_size"], expert_dim=keys["moe_intermediate_size"],
        router_experts=keys["router_experts"], num_experts=keys["n_routed_experts"],
        expert_offset=keys["expert_offset"], experts_per_token=keys["num_experts_per_tok"],
        shared_experts=keys["n_shared_experts"],
        routed_scale=float(keys["routed_scaling_factor"]),
        bias_std=float(keys["e_score_correction_bias_std"]),
        index_bias_std=float(keys["index_k_norm_bias_std"]),
        rope_base=float(keys["rope_theta"]), norm_eps=keys["rms_norm_eps"],
        index_norm_eps=keys["index_norm_eps"], max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they
    are served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_heads} heads of {cfg.nope_dim} + {cfg.rope_dim}, "
        f"value {cfg.v_dim}, over one latent of {cfg.kv_rank} + {cfg.rope_dim}, queries through "
        f"{cfg.q_rank} / indexer {cfg.index_heads} x {cfg.index_dim} on the query latent, "
        f"{cfg.topk} rows a query / {cfg.dense_layers} dense layer of {cfg.mlp_dim} / experts "
        f"{cfg.num_experts} held of {cfg.router_experts} from {cfg.expert_offset}, "
        f"{cfg.experts_per_token} a token, {cfg.shared_experts} shared, width {cfg.expert_dim} / "
        f"vocab {cfg.vocab_size} / depth {cfg.num_layers} / params {cfg.param_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert, or of one shared expert's worth: gate,
    up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def attention_params(keys: Dict[str, Any]) -> int:
    """The matrices of one layer's attention and indexer: both down-projections,
    ``W_qb``, ``W_kvb``, ``W_o`` and the indexer's three."""
    d, heads = keys["hidden_size"], keys["num_attention_heads"]
    return (
        d * keys["q_lora_rank"]
        + keys["q_lora_rank"] * heads * keys["qk_head_dim"]
        + d * (keys["kv_lora_rank"] + keys["qk_rope_head_dim"])
        + keys["kv_lora_rank"] * heads * (keys["qk_nope_head_dim"] + keys["v_head_dim"])
        + heads * keys["v_head_dim"] * d
        + keys["q_lora_rank"] * keys["index_n_heads"] * keys["index_head_dim"]
        + d * (keys["index_head_dim"] + keys["index_n_heads"]))


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: the
    attention and the indexer of every layer, the dense layers' MLP, the router, the
    shared expert and ``num_experts_per_tok`` routed experts of every expert layer
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
    """The benchmark trains no such model (at 16 bytes a parameter no cut within the
    floors fits a chip); the harness's contract lists the entry point. The count is
    ``matmul_params`` plus, for every query, the indexer's scores over what is before
    it and attention in the expanded form over at most ``index_topk`` rows."""
    tokens = batch * seq
    before = seq * (seq + 1) / 2.0
    attended = sum(min(keys["index_topk"], t + 1) for t in range(seq))
    per_pair = 2.0 * keys["num_attention_heads"] * (keys["qk_head_dim"] + keys["v_head_dim"])
    sparse = 3.0 * keys["num_hidden_layers"] * batch * (
        2.0 * keys["index_n_heads"] * keys["index_head_dim"] * before + per_pair * attended)
    return 6.0 * matmul_params(keys) * tokens + sparse


def _itemsize(keys: Dict[str, Any], which: str) -> int:
    return {"bfloat16": 2, "float32": 4}[keys[which]]


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the expert layers of the counted device calls had to do, from the
    engine's counters (deltas of ``kv_stats``), as ``kimi_k2.experts_work`` counts it:
    ``flops`` = 2 per parameter of an expert for every token-expert pair computed here
    and for every token through the shared expert; ``bytes`` = an expert's weights for
    every (call, layer, held expert with a token) and the shared expert's for every
    (call, expert layer). Activations are not counted, so both are lower bounds of
    what must move."""
    per_expert, shared = expert_params(keys), keys["n_shared_experts"]
    expert_layers = keys["num_hidden_layers"] - keys["first_k_dense_replace"]
    calls = counters["phase_n"]["dispatch"]
    return {
        "flops": 2.0 * per_expert * (
            counters["moe_assignments"] + shared * counters["moe_tokens"]),
        "bytes": float(_itemsize(keys, "param_dtype") * per_expert * (
            counters["moe_experts_hit"] + shared * expert_layers * calls)),
    }


def index_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the indexer of the counted device calls had to do: ``flops`` = 2 a feature
    of every indexer head for every live causal query-key pair scored
    (``sparse_keys_scored``: 2 x 32 x 128 a pair); ``bytes`` = the indexer key of
    every live slot, read once a call and layer (``cache_tokens``, the live slots the
    engine gathered, times the layers). The three projections, the norm, the rotation,
    the weighting by ``w``, the selection itself and the writes are not counted, so
    both are lower bounds of what must move."""
    return {
        "flops": 2.0 * keys["index_n_heads"] * keys["index_head_dim"]
        * counters["sparse_keys_scored"],
        "bytes": float(
            _itemsize(keys, "compute_dtype") * keys["index_head_dim"]
            * keys["num_hidden_layers"] * counters["cache_tokens"]),
    }


def cached_row(keys: Dict[str, Any]) -> int:
    """Values of a cached latent row as the pool holds it: the latent and the rotary
    key, up to whole 128-lane tiles (``GlmMoeDsaConfig.row_dim``)."""
    return -(-(keys["kv_lora_rank"] + keys["qk_rope_head_dim"]) // 128) * 128


def attend_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the attend over the selected latent rows of the counted device calls had
    to do: ``flops`` = for every pair **attended** (at most ``index_topk`` a query)
    and query head, 2 a feature of the row scored and of the latent summed in the
    absorbed form (``kv_lora_rank + qk_rope_head_dim`` and ``kv_lora_rank``:
    ``mla_pairs_absorbed``) and of a head's own key and value in the expanded form
    (``qk_head_dim`` and ``v_head_dim``: ``mla_pairs_expanded``), and in the expanded
    form ``W_kvb`` over every live slot, which the selection does not spare
    (``mla_rows_expanded`` x 2 x ``kv_lora_rank`` x heads x (``qk_nope_head_dim +
    v_head_dim``)); ``bytes`` = the cached rows of the slots some query of the call
    selected, as they lie (``sparse_slots_read`` x :func:`cached_row`). The pairs a
    chunk's kernel scores and masks away, the projections, the absorption,
    activations and writes are not counted: lower bounds of what must move, so a
    kernel that skips the tiles no query selected reads higher, and not over 100 %."""
    heads, rank, rope = (
        keys["num_attention_heads"], keys["kv_lora_rank"], keys["qk_rope_head_dim"])
    absorbed = 2.0 * heads * (rank + rope + rank)
    expanded = 2.0 * heads * (keys["qk_head_dim"] + keys["v_head_dim"])
    through_kvb = 2.0 * rank * heads * (keys["qk_nope_head_dim"] + keys["v_head_dim"])
    return {
        "flops": (
            absorbed * counters.get("mla_pairs_absorbed", 0)
            + expanded * counters.get("mla_pairs_expanded", 0)
            + through_kvb * counters.get("mla_rows_expanded", 0)),
        "bytes": float(
            _itemsize(keys, "compute_dtype") * cached_row(keys)
            * counters.get("sparse_slots_read", 0)),
    }
