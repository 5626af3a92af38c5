"""Keye-VL-2.0-30B-A3B's language model (``model_type`` ``keye_vl2``): from the
published ``config.json`` keys to the program's ``KeyeVL2Config``, seeded weights
made on the device in one jitted call, and the operations and bytes the
indexer and the attention over the selected keys require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut (``sa_config`` is a nested
# group, copied whole: every key of it is a width of the indexer)
WIDTHS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok", "sa_config",
)


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run, the
    indexer's flat under ``indexer_head_dim``, ``indexer_num_heads``,
    ``indexer_num_kv_heads`` and ``topk`` (in the source they are a nested group,
    ``sa_config``, which ``manifest.published_keys`` leaves behind: the file
    holds both, and ``tests/benchmark/test_bench_keye_vl2.py`` holds them equal),
    and the benchmark's ``compute_dtype`` / ``param_dtype``."""
    import jax.numpy as jnp

    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    wanted = {
        "hidden_act": "silu", "norm_topk_prob": True, "attention_bias": False,
        "tie_word_embeddings": False, "decoder_sparse_step": 1, "use_sliding_window": False,
        "indexer_num_kv_heads": 1,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one KeyeVL2 block, and not one with {differ}")
    return KeyeVL2Config(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        embed_dim=keys["hidden_size"], num_heads=keys["num_attention_heads"],
        kv_heads=keys["num_key_value_heads"], head_dim=keys["head_dim"],
        expert_dim=keys["moe_intermediate_size"], num_experts=keys["num_experts"],
        experts_per_token=keys["num_experts_per_tok"],
        index_heads=keys["indexer_num_heads"], index_dim=keys["indexer_head_dim"],
        topk=keys["topk"], rope_base=float(keys["rope_theta"]), norm_eps=keys["rms_norm_eps"],
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
        f"{cfg.head_dim} / indexer {cfg.index_heads} x {cfg.index_dim} over one key head, "
        f"{cfg.topk} keys a query / experts {cfg.num_experts}, {cfg.experts_per_token} a token, "
        f"width {cfg.expert_dim} / vocab {cfg.vocab_size} / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.2f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with: q, k, v, o, the indexer's three
    projections, the router and ``num_experts_per_tok`` experts of every layer,
    and the untied output head. The input embedding is a gather."""
    d = keys["hidden_size"]
    attention = 2 * d * keys["head_dim"] * (
        keys["num_attention_heads"] + keys["num_key_value_heads"])
    indexer = d * (
        keys["indexer_num_heads"] * keys["indexer_head_dim"] + keys["indexer_head_dim"]
        + keys["indexer_num_heads"])
    experts = keys["num_experts_per_tok"] * expert_params(keys)
    per_layer = attention + indexer + d * keys["num_experts"] + experts
    return keys["num_hidden_layers"] * per_layer + d * keys["vocab_size"]


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (the repo's train step has no expert
    layer); the harness's contract lists the entry point. The count is
    ``matmul_params`` plus, for every query, the indexer's scores over what is
    before it and attention over at most ``topk`` keys."""
    tokens = batch * seq
    before = seq * (seq + 1) / 2.0
    attended = sum(min(keys["topk"], t + 1) for t in range(seq))
    heads_x_dim = keys["num_attention_heads"] * keys["head_dim"]
    sparse = 3.0 * keys["num_hidden_layers"] * batch * (
        2.0 * keys["indexer_num_heads"] * keys["indexer_head_dim"] * before
        + 4.0 * heads_x_dim * attended)
    return 6.0 * matmul_params(keys) * tokens + sparse


def sparse_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the indexer and the attention over the selected keys of the counted
    device calls had to do, from the engine's counters (deltas of ``kv_stats``):
    ``flops`` = 2 per feature of every indexer head for every live causal
    query-key pair scored, and 4 per feature of every query head (scores and
    the weighted sum) for every key attended; ``bytes`` = what has to be read
    of the caches once a call and layer: the indexer key of every live slot
    (the pairs scored are at least the live slots of a call, one query a slot,
    so they are counted from ``cache_tokens``, the live slots the engine
    gathered, times the layers) and the K and V rows of every slot that some
    query of the call selected (``sparse_slots_read``). Activations, the
    projections and the writes are not counted, so both are lower bounds of
    what must move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["compute_dtype"]]
    kv_row = 2 * keys["num_key_value_heads"] * keys["head_dim"]
    index_row = keys["indexer_num_kv_heads"] * keys["indexer_head_dim"]
    return {
        "flops": (
            2.0 * keys["indexer_num_heads"] * keys["indexer_head_dim"]
            * counters["sparse_keys_scored"]
            + 4.0 * keys["num_attention_heads"] * keys["head_dim"]
            * counters["sparse_keys_attended"]),
        "bytes": float(itemsize * (
            index_row * keys["num_hidden_layers"] * counters["cache_tokens"]
            + kv_row * counters["sparse_slots_read"])),
    }
