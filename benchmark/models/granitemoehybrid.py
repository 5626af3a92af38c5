"""granite-4.0-h (``model_type`` ``granitemoehybrid``): from the published
``config.json`` keys to the program's ``GraniteMoeHybridConfig``, seeded
weights made on the device in one jitted call, and the operations and bytes the
Mamba-2 recurrence requires."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "shared_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
    "mamba_expand", "mamba_n_groups", "mamba_chunk_size", "num_experts_per_tok",
)


def layer_pattern(layer_types) -> Dict[str, int]:
    """``layer_period`` and ``attention_layer_offset`` of a ``layer_types`` list:
    the shortest period with one attention layer that the whole list repeats.
    The harness hands an architecture the top-level scalars only, so a file
    carries the two beside the list (``tests/benchmark`` holds them equal)."""
    for period in range(1, len(layer_types) + 1):
        one = list(layer_types[:period])
        if len(layer_types) % period == 0 and one.count("attention") == 1 and (
                one * (len(layer_types) // period) == list(layer_types)):
            return {"layer_period": period, "attention_layer_offset": one.index("attention")}
    raise ValueError(f"no period with one attention layer in {layer_types}")


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run, the layer
    pattern as ``layer_period`` / ``attention_layer_offset`` (:func:`layer_pattern`
    of the published ``layer_types``), and the benchmark's ``compute_dtype``,
    ``param_dtype`` and ``state_dtype``."""
    import jax.numpy as jnp

    from ray_tpu.models.granitemoehybrid import GraniteMoeHybridConfig

    wanted = {
        "hidden_act": "silu", "attention_bias": False, "position_embedding_type": "nope",
        "normalization_function": "rmsnorm", "tie_word_embeddings": True,
        "num_local_experts": 0, "num_experts_per_tok": 0, "mamba_n_groups": 1,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one granitemoehybrid block, and not one with {differ}")
    if keys["mamba_n_heads"] * keys["mamba_d_head"] != keys["mamba_expand"] * keys["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    if keys["shared_intermediate_size"] != keys["intermediate_size"]:
        raise ValueError("the program's MLP has one width")
    return GraniteMoeHybridConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        period=keys["layer_period"], attention_at=keys["attention_layer_offset"],
        embed_dim=keys["hidden_size"], mlp_dim=keys["shared_intermediate_size"],
        num_heads=keys["num_attention_heads"], kv_heads=keys["num_key_value_heads"],
        head_dim=keys["hidden_size"] // keys["num_attention_heads"],
        ssm_heads=keys["mamba_n_heads"], ssm_head_dim=keys["mamba_d_head"],
        ssm_state=keys["mamba_d_state"], ssm_chunk=keys["mamba_chunk_size"],
        conv_width=keys["mamba_d_conv"],
        embedding_multiplier=float(keys["embedding_multiplier"]),
        residual_multiplier=float(keys["residual_multiplier"]),
        attention_multiplier=float(keys["attention_multiplier"]),
        logits_scaling=float(keys["logits_scaling"]), norm_eps=keys["rms_norm_eps"],
        max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
        state_dtype=jnp.dtype(keys.get("state_dtype", "float32")).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they
    are served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.ssm_layers} Mamba-2 layers ({cfg.ssm_heads} heads of "
        f"{cfg.ssm_head_dim} x {cfg.ssm_state} state, conv {cfg.conv_width}, sub-chunks of "
        f"{cfg.ssm_chunk}) and {cfg.periods} attention layers ({cfg.num_heads} heads over "
        f"{cfg.kv_heads} K/V of {cfg.head_dim}, no positions) in periods of {cfg.period} / MLP "
        f"{cfg.mlp_dim} / vocab {cfg.vocab_size} tied / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__}, state {cfg.state_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def mamba_params(keys: Dict[str, Any]) -> int:
    """The matrices of one Mamba-2 mixer: ``W_in`` and ``W_out``."""
    inner = keys["mamba_n_heads"] * keys["mamba_d_head"]
    return keys["hidden_size"] * (
        2 * inner + 2 * keys["mamba_n_groups"] * keys["mamba_d_state"] + keys["mamba_n_heads"]
    ) + inner * keys["hidden_size"]


def attention_params(keys: Dict[str, Any]) -> int:
    head = keys["hidden_size"] // keys["num_attention_heads"]
    return keys["hidden_size"] * head * 2 * (
        keys["num_attention_heads"] + keys["num_key_value_heads"])


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with: every mixer's matrices, every
    layer's MLP and the tied head. The input embedding is a gather."""
    layers = keys["num_hidden_layers"]
    attention = layers // keys["layer_period"]
    return (
        (layers - attention) * mamba_params(keys) + attention * attention_params(keys)
        + layers * 3 * keys["hidden_size"] * keys["shared_intermediate_size"]
        + keys["hidden_size"] * keys["vocab_size"])


def scan_flops_per_token(keys: Dict[str, Any]) -> float:
    """What the recurrence itself takes a token and Mamba layer, whatever
    computes it: the update ``S = exp(D A) S + D x (x) B`` and the read-out ``S
    C``, 2 operations a state element each."""
    return 4.0 * keys["mamba_n_heads"] * keys["mamba_d_head"] * keys["mamba_d_state"]


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (the repo's train step has no scan
    and no backward of one); the harness's contract lists the entry point. The
    count is ``matmul_params``, the recurrence and causal attention."""
    tokens = batch * seq
    layers = keys["num_hidden_layers"]
    attention = layers // keys["layer_period"]
    pairs = 4.0 * keys["hidden_size"] * batch * seq * (seq + 1) / 2.0
    return 3.0 * (
        2.0 * matmul_params(keys) * tokens + attention * pairs
        + (layers - attention) * scan_flops_per_token(keys) * tokens)


def scan_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the Mamba-2 recurrence of the counted device calls had to do, from
    the engine's counters: ``flops`` = :func:`scan_flops_per_token` for every
    token and Mamba layer (``ssm_tokens``; the chunked form does more and is
    credited no more); ``bytes`` = a layer's state read and written once a lane,
    layer and call (``ssm_state_passes``), in the type it is kept in, and a
    token's ``x``, ``B``, ``C``, ``D_t`` in and ``y`` out in the compute type
    (``ssm_tokens``). The convolution, the projections and the gate are not the
    recurrence's."""
    itemsize = {"bfloat16": 2, "float32": 4}
    heads, state = keys["mamba_n_heads"], keys["mamba_d_state"]
    inner = heads * keys["mamba_d_head"]
    state_bytes = inner * state * itemsize[keys.get("state_dtype", "float32")]
    token_bytes = itemsize[keys["compute_dtype"]] * (
        inner + 2 * keys["mamba_n_groups"] * state + heads + inner)
    return {
        "flops": scan_flops_per_token(keys) * counters.get("ssm_tokens", 0),
        "bytes": float(
            2 * state_bytes * counters.get("ssm_state_passes", 0)
            + token_bytes * counters.get("ssm_tokens", 0)),
        "state_bytes": float(2 * state_bytes * counters.get("ssm_state_passes", 0)),
    }
