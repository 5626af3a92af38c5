"""LongCat-Flash-Chat (``model_type`` ``longcat_flash``): from the published
``config.json`` keys to the program's ``LongcatFlashConfig``, seeded weights made on the
device in one jitted call, and the operations and bytes the held experts and the attend
over the cached latent rows require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "moe_topk", "zero_expert_num",
)

#: attention sub-blocks (and dense MLPs) a layer
SUB_BLOCKS = 2


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run: ``n_routed_experts``
    is the routed experts **held here**, from ``expert_offset`` on; ``router_experts``
    (the benchmark's key) the outputs of the router, which is the published
    ``n_routed_experts + zero_expert_num`` and nothing else; ``hidden_act``,
    ``norm_topk_prob`` and ``tie_word_embeddings`` stand where a file states what the
    source leaves out; ``e_score_correction_bias_std`` is the spread of the seeded bias;
    ``compute_dtype`` / ``param_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import LongcatFlashConfig

    wanted = {
        "attention_method": "MLA", "zero_expert_type": "identity", "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "attention_bias": False, "hidden_act": "silu",
        "norm_topk_prob": False, "tie_word_embeddings": False,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one longcat_flash layer, and not one with {differ}")
    return LongcatFlashConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_layers"],
        embed_dim=keys["hidden_size"], num_heads=keys["num_attention_heads"],
        q_rank=keys["q_lora_rank"], kv_rank=keys["kv_lora_rank"],
        rope_dim=keys["qk_rope_head_dim"], nope_dim=keys["qk_nope_head_dim"],
        v_dim=keys["v_head_dim"], mlp_dim=keys["ffn_hidden_size"],
        expert_dim=keys["expert_ffn_hidden_size"], router_experts=keys["router_experts"],
        zero_experts=keys["zero_expert_num"], num_experts=keys["n_routed_experts"],
        expert_offset=keys["expert_offset"], experts_per_token=keys["moe_topk"],
        routed_scale=float(keys["routed_scaling_factor"]),
        bias_std=float(keys["e_score_correction_bias_std"]),
        rope_base=float(keys["rope_theta"]), norm_eps=keys["rms_norm_eps"],
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
        f"hidden {cfg.embed_dim} / a layer of 2 sub-blocks: {cfg.num_heads} heads of "
        f"{cfg.nope_dim} + {cfg.rope_dim} over one latent of {cfg.kv_rank} + {cfg.rope_dim}, "
        f"queries through {cfg.q_rank}, an MLP of {cfg.mlp_dim} each / a shortcut of experts "
        f"{cfg.num_experts} held of {cfg.routed_experts} from {cfg.expert_offset} and "
        f"{cfg.zero_experts} zero-compute, {cfg.experts_per_token} a token of "
        f"{cfg.router_experts}, width {cfg.expert_dim} / vocab {cfg.vocab_size} / depth "
        f"{cfg.num_layers} / params {cfg.param_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["expert_ffn_hidden_size"]


def attention_params(keys: Dict[str, Any]) -> int:
    """The matrices of one sub-block's attention: both down-projections, ``W_qb``,
    ``W_kvb`` and ``W_o``."""
    d, heads = keys["hidden_size"], keys["num_attention_heads"]
    return (
        d * keys["q_lora_rank"]
        + keys["q_lora_rank"] * heads * (keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"])
        + d * (keys["kv_lora_rank"] + keys["qk_rope_head_dim"])
        + keys["kv_lora_rank"] * heads * (keys["qk_nope_head_dim"] + keys["v_head_dim"])
        + heads * keys["v_head_dim"] * d)


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: both sub-blocks'
    attention and dense MLP, the router and ``moe_topk`` routed experts of every layer
    (fewer where the chosen are held elsewhere or are zero-compute experts, which have
    none), and the untied output head. The input embedding is a gather."""
    d = keys["hidden_size"]
    layer = (
        SUB_BLOCKS * (attention_params(keys) + 3 * d * keys["ffn_hidden_size"])
        + d * keys["router_experts"] + keys["moe_topk"] * expert_params(keys))
    return keys["num_layers"] * layer + d * keys["vocab_size"]


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (at 16 bytes a parameter four layers without
    a single expert are 40.9 GB); the harness's contract lists the entry point. The count
    is ``matmul_params`` plus causal attention in the expanded form, two a layer."""
    tokens = batch * seq
    per_pair = 2.0 * keys["num_attention_heads"] * (
        keys["qk_nope_head_dim"] + keys["qk_rope_head_dim"] + keys["v_head_dim"])
    attention = 3.0 * SUB_BLOCKS * keys["num_layers"] * batch * per_pair * seq * (seq + 1) / 2.0
    return 6.0 * matmul_params(keys) * tokens + attention


def _itemsize(keys: Dict[str, Any], which: str) -> int:
    return {"bfloat16": 2, "float32": 4}[keys[which]]


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the held experts of the counted device calls had to do, from the engine's
    counters (deltas of ``kv_stats``), as ``kimi_k2.experts_work`` counts it without a
    shared expert: ``flops`` = 2 per parameter of an expert for every token-expert pair
    computed here (``moe_assignments``: a pair on a zero-compute expert is none);
    ``bytes`` = an expert's weights for every (call, layer, held expert with a token)
    (``moe_experts_hit``). Activations, the sort and the combine are not counted, so both
    are lower bounds of what must move."""
    per_expert = expert_params(keys)
    return {
        "flops": 2.0 * per_expert * counters["moe_assignments"],
        "bytes": float(_itemsize(keys, "param_dtype") * per_expert * counters["moe_experts_hit"]),
    }


def cached_row(keys: Dict[str, Any]) -> int:
    """Values of a cached latent row as the pool holds it: the latent and the rotary
    key, up to whole 128-lane tiles (``LongcatFlashConfig.row_dim``)."""
    return -(-(keys["kv_lora_rank"] + keys["qk_rope_head_dim"]) // 128) * 128


def latent_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the attend over the cached latent rows of the counted device calls had to
    do, **whatever implements it**: ``flops`` = for every live causal query-key pair and
    query head, 2 a feature of the row scored and of the latent summed in the absorbed
    form (``kv_lora_rank + qk_rope_head_dim`` and ``kv_lora_rank``:
    ``mla_pairs_absorbed``), and of a head's own key and value in the expanded form
    (``qk_nope_head_dim + qk_rope_head_dim`` and ``v_head_dim``: ``mla_pairs_expanded``),
    and in the expanded form ``W_kvb`` over every live slot (``mla_rows_expanded`` x 2 x
    ``kv_lora_rank`` x heads x (``qk_nope_head_dim + v_head_dim``)): the counters are
    summed over both sub-blocks of every layer by ``extend``. ``bytes`` = the rows of the
    live slots as the pool holds them (:func:`cached_row`), read once a call and
    sub-block: ``cache_tokens`` (the live slots of the calls' lanes, gathered or read
    through the block table) times the ``SUB_BLOCKS x num_layers`` slabs. The
    projections, the absorption, activations and writes are not counted, so both are
    lower bounds of what must move."""
    heads, rank, rope = (
        keys["num_attention_heads"], keys["kv_lora_rank"], keys["qk_rope_head_dim"])
    absorbed = 2.0 * heads * (rank + rope + rank)
    expanded = 2.0 * heads * (keys["qk_nope_head_dim"] + rope + keys["v_head_dim"])
    through_kvb = 2.0 * rank * heads * (keys["qk_nope_head_dim"] + keys["v_head_dim"])
    return {
        "flops": (
            absorbed * counters.get("mla_pairs_absorbed", 0)
            + expanded * counters.get("mla_pairs_expanded", 0)
            + through_kvb * counters.get("mla_rows_expanded", 0)),
        "bytes": float(
            _itemsize(keys, "compute_dtype") * cached_row(keys) * SUB_BLOCKS
            * keys["num_layers"] * counters.get("cache_tokens", 0)),
    }
