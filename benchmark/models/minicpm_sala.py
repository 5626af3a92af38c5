"""MiniCPM-SALA (``model_type`` ``minicpm_sala``): from the published
``config.json`` keys to the program's ``MiniCPMSALAConfig``, seeded weights made on
the device in one jitted call, and the operations and bytes the two mixers'
distinctive parts require (the linear recurrence; the selection and the attention
over the selected keys)."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "lightning_nh", "lightning_nkv", "lightning_head_dim", "dim_model_base",
)

#: the sizes of MiniCPM4's ``sparse_config``, which a file carries at its top level as
#: ``sparse_<size>`` (the harness hands an architecture the top-level scalars only)
SPARSE_SIZES = (
    "kernel_size", "kernel_stride", "block_size", "init_blocks", "window_size", "topk",
    "dense_len")

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def mixers(keys: Dict[str, Any]):
    """The mixer of every layer that runs: ``mixer_period`` (a period's mixers,
    comma-separated: the top-level scalar that says what ``mixer_types`` says)
    repeated over ``num_hidden_layers``."""
    period = keys["mixer_period"].split(",")
    if keys["num_hidden_layers"] % len(period):
        raise ValueError(f"{keys['num_hidden_layers']} layers are no whole periods of {period}")
    return period * (keys["num_hidden_layers"] // len(period))


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run, the layers'
    mixers as ``mixer_period``, the published depth (what the residual scale is of)
    as ``published_num_hidden_layers``, MiniCPM4's ``sparse_config`` as ``sparse_<size>``,
    the recurrence's sub-chunk as ``lightning_chunk``, and the benchmark's
    ``compute_dtype``, ``param_dtype`` and ``state_dtype``."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    wanted = {
        "hidden_act": "silu", "attention_bias": False, "attn_use_rope": False,
        "lightning_use_rope": True, "qk_norm": True, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True, "tie_word_embeddings": False,
        "lightning_scale": "1/sqrt(d)",
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one minicpm_sala block, and not one with {differ}")
    if keys["lightning_nkv"] != keys["lightning_nh"]:
        raise ValueError("the program's linear attention has a key and a value head a query head")
    return MiniCPMSALAConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        mixer_types=tuple(mixers(keys)), embed_dim=keys["hidden_size"],
        mlp_dim=keys["intermediate_size"], num_heads=keys["num_attention_heads"],
        kv_heads=keys["num_key_value_heads"], head_dim=keys["head_dim"],
        linear_heads=keys["lightning_nh"], linear_head_dim=keys["lightning_head_dim"],
        linear_chunk=keys["lightning_chunk"], kernel_size=keys["sparse_kernel_size"],
        kernel_stride=keys["sparse_kernel_stride"], select_block=keys["sparse_block_size"],
        init_blocks=keys["sparse_init_blocks"], window_size=keys["sparse_window_size"],
        topk=keys["sparse_topk"], dense_len=keys["sparse_dense_len"],
        scale_emb=float(keys["scale_emb"]), scale_depth=float(keys["scale_depth"]),
        depth_layers=keys["published_num_hidden_layers"],
        dim_model_base=keys["dim_model_base"], rope_base=float(keys["rope_theta"]),
        norm_eps=keys["rms_norm_eps"], max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
        state_dtype=jnp.dtype(keys.get("state_dtype", "float32")).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they are
    served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.sparse_layers} block-sparse layers ({cfg.num_heads} heads "
        f"over {cfg.kv_heads} K/V of {cfg.head_dim}, no positions; compressed keys of "
        f"{cfg.kernel_size} every {cfg.kernel_stride}, top {cfg.topk} blocks of {cfg.select_block} "
        f"beside {cfg.init_blocks} first and a window of {cfg.window_size}, dense up to "
        f"{cfg.dense_len}) and {cfg.linear_layers} lightning layers ({cfg.linear_heads} heads of "
        f"{cfg.linear_head_dim} x {cfg.linear_head_dim} state, sub-chunks of {cfg.linear_chunk}) in "
        f"periods of {cfg.period} / MLP {cfg.mlp_dim} / vocab {cfg.vocab_size} untied / depth "
        f"{cfg.num_layers} of {cfg.depth_layers} / params {cfg.param_dtype.__name__}, state "
        f"{cfg.state_dtype.__name__} / {cfg.num_params() / 1e9:.3f}B params"
    )


def sparse_params(keys: Dict[str, Any]) -> int:
    """The matrices of one block-sparse mixer: q, k, v, the gate and o."""
    return keys["hidden_size"] * keys["head_dim"] * (
        3 * keys["num_attention_heads"] + 2 * keys["num_key_value_heads"])


def linear_params(keys: Dict[str, Any]) -> int:
    """The matrices of one lightning mixer: q, k, v, the gate and o."""
    return 5 * keys["hidden_size"] * keys["lightning_nh"] * keys["lightning_head_dim"]


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with: every mixer's matrices, every layer's
    MLP and the untied head. The input embedding is a gather."""
    of = mixers(keys)
    return (
        of.count(SPARSE) * sparse_params(keys) + of.count(LINEAR) * linear_params(keys)
        + len(of) * 3 * keys["hidden_size"] * keys["intermediate_size"]
        + keys["hidden_size"] * keys["vocab_size"])


def linear_flops_per_token(keys: Dict[str, Any]) -> float:
    """What the recurrence itself takes a token and lightning layer, whatever
    computes it: the update ``S = l S + k^T v`` and the read-out ``q S``, 2 operations
    a state element each."""
    return 4.0 * keys["lightning_nh"] * keys["lightning_head_dim"] ** 2


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (the repo's train step has no backward
    of the selection or of the chunked recurrence); the harness's contract lists the
    entry point. The count is ``matmul_params``, the recurrence, and attention over
    what a query reads: every key before ``sparse_dense_len``, at most the first
    blocks, the window and the chosen blocks past it."""
    of = mixers(keys)
    tokens = batch * seq
    most = keys["sparse_block_size"] * (
        keys["sparse_init_blocks"] + keys["sparse_topk"]) + keys["sparse_window_size"] + (
        keys["sparse_block_size"])
    attended = sum(
        t + 1 if t < keys["sparse_dense_len"] else min(t + 1, most) for t in range(seq))
    pairs = 4.0 * keys["num_attention_heads"] * keys["head_dim"] * batch * attended
    return 3.0 * (
        2.0 * matmul_params(keys) * tokens + of.count(SPARSE) * pairs
        + of.count(LINEAR) * linear_flops_per_token(keys) * tokens)


def linear_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the linear recurrence of the counted device calls had to do, from the
    engine's counters: ``flops`` = :func:`linear_flops_per_token` for every token and
    lightning layer (``linear_tokens``; the chunked form does more and is credited no
    more); ``bytes`` = a layer's state read and written once a lane, layer and call
    (``linear_state_passes``), in the type it is kept in, and a token's ``q``, ``k``,
    ``v`` in and ``o`` out in the compute type (``linear_tokens``). The projections,
    the norms, the rotation and the gate are not the recurrence's."""
    itemsize = {"bfloat16": 2, "float32": 4}
    inner = keys["lightning_nh"] * keys["lightning_head_dim"]
    state_bytes = inner * keys["lightning_head_dim"] * itemsize[keys.get("state_dtype", "float32")]
    token_bytes = 4 * inner * itemsize[keys["compute_dtype"]]
    return {
        "flops": linear_flops_per_token(keys) * counters.get("linear_tokens", 0),
        "bytes": float(
            2 * state_bytes * counters.get("linear_state_passes", 0)
            + token_bytes * counters.get("linear_tokens", 0)),
        "state_bytes": float(2 * state_bytes * counters.get("linear_state_passes", 0)),
    }


def sparse_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the selection and the attention over the selected keys of the counted
    device calls had to do, from the engine's counters: ``flops`` = 2 a feature of the
    query heads of a K/V head for every (query, visible compressed key, K/V head)
    scored (``sparse_keys_scored``), and 4 (scores and the weighted sum) for every key
    attended, K/V head by K/V head (``sparse_keys_attended``); ``bytes`` = what has to be
    read of the caches once a call and layer: the compressed keys of the live slots
    (one row of all K/V heads for every ``sparse_kernel_stride`` tokens of
    ``cache_tokens``, times the sparse layers) and the K and V rows of every slot that
    some query of the call read (``sparse_slots_read``). Activations, the projections,
    the gate and the writes are not counted, so both are lower bounds of what must
    move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["compute_dtype"]]
    group = keys["num_attention_heads"] // keys["num_key_value_heads"]
    row = keys["num_key_value_heads"] * keys["head_dim"]
    layers = mixers(keys).count(SPARSE)
    return {
        "flops": group * keys["head_dim"] * (
            2.0 * counters.get("sparse_keys_scored", 0)
            + 4.0 * counters.get("sparse_keys_attended", 0)),
        "bytes": float(itemsize * row * (
            layers * counters.get("cache_tokens", 0) / keys["sparse_kernel_stride"]
            + 2 * counters.get("sparse_slots_read", 0))),
    }
