"""Qwen3-Next (``model_type`` ``qwen3_next``): from the published ``config.json`` keys to
the program's ``Qwen3NextConfig``, seeded weights made on the device in one jitted call,
and the operations and bytes that the gated delta rule and the expert layers require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "linear_key_head_dim", "linear_value_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "linear_conv_kernel_dim", "num_experts_per_tok",
    "partial_rotary_factor", "full_attention_interval",
)


def rotary_features(keys: Dict[str, Any]) -> int:
    return int(keys["partial_rotary_factor"] * keys["head_dim"])


def delta_layers(keys: Dict[str, Any]) -> int:
    """The layers that run the delta rule: all but one in ``full_attention_interval``."""
    period = keys["full_attention_interval"]
    return keys["num_hidden_layers"] // period * (period - 1)


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run: ``num_experts`` is
    the experts **held here**, from ``expert_offset`` on; ``router_experts`` (the
    benchmark's key) the experts the router scores, which is the published
    ``num_experts`` and nothing else; ``delta_chunk`` the program's sub-chunk of the
    chunked rule and ``norm_scale_std`` the spread of the seeded norm scales (both the
    benchmark's); ``compute_dtype`` / ``param_dtype`` / ``state_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.qwen3_next import Qwen3NextConfig

    wanted = {
        "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1,
        "tie_word_embeddings": False, "use_sliding_window": False, "rope_scaling": None,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one qwen3_next block, and not one with {differ}")
    return Qwen3NextConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        full_interval=keys["full_attention_interval"], embed_dim=keys["hidden_size"],
        num_heads=keys["num_attention_heads"], kv_heads=keys["num_key_value_heads"],
        head_dim=keys["head_dim"], rotary_dim=rotary_features(keys),
        rope_base=float(keys["rope_theta"]), delta_key_heads=keys["linear_num_key_heads"],
        delta_value_heads=keys["linear_num_value_heads"],
        delta_key_dim=keys["linear_key_head_dim"], delta_value_dim=keys["linear_value_head_dim"],
        delta_chunk=keys["delta_chunk"], conv_width=keys["linear_conv_kernel_dim"],
        expert_dim=keys["moe_intermediate_size"],
        shared_dim=keys["shared_expert_intermediate_size"],
        router_experts=keys["router_experts"], num_experts=keys["num_experts"],
        expert_offset=keys["expert_offset"], experts_per_token=keys["num_experts_per_tok"],
        norm_std=float(keys["norm_scale_std"]),
        qk_norm_mean=float(keys.get("qk_norm_scale_mean", 0.0)), norm_eps=keys["rms_norm_eps"],
        max_seq_len=keys["max_position_embeddings"],
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
        f"hidden {cfg.embed_dim} / {cfg.delta_layers} delta layers ({cfg.delta_key_heads} key and "
        f"{cfg.delta_value_heads} value heads of {cfg.delta_key_dim} x {cfg.delta_value_dim}, conv "
        f"{cfg.conv_width}, sub-chunks of {cfg.delta_chunk}, state {cfg.state_dtype.__name__} kept a "
        f"sequence) and {cfg.cache_layers} full ({cfg.num_heads} heads over {cfg.kv_heads} K/V of "
        f"{cfg.head_dim}, {cfg.rotary_dim} rotated, gated, paged) in periods of {cfg.period} / "
        f"experts {cfg.num_experts} held of {cfg.router_experts} from {cfg.expert_offset}, "
        f"{cfg.experts_per_token} a token, width {cfg.expert_dim}, beside a gated shared one of "
        f"{cfg.shared_dim} / vocab {cfg.vocab_size} untied / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def shared_params(keys: Dict[str, Any]) -> int:
    """Parameters of the shared expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["shared_expert_intermediate_size"]


def delta_mixer_params(keys: Dict[str, Any]) -> int:
    """The matrices of one delta mixer: q, k, v, z, b, a and the output."""
    d = keys["hidden_size"]
    key_inner = keys["linear_num_key_heads"] * keys["linear_key_head_dim"]
    value_inner = keys["linear_num_value_heads"] * keys["linear_value_head_dim"]
    return d * (2 * key_inner + 2 * value_inner + 2 * keys["linear_num_value_heads"]) + (
        value_inner * d)


def attention_params(keys: Dict[str, Any]) -> int:
    """The matrices of one full layer's attention: q with its gate, k, v and o."""
    d, hd = keys["hidden_size"], keys["head_dim"]
    return d * hd * (3 * keys["num_attention_heads"] + 2 * keys["num_key_value_heads"])


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: every mixer's
    matrices, every layer's router, shared expert with its gate and
    ``num_experts_per_tok`` routed experts (fewer where the chosen are held elsewhere),
    and the untied output head. The input embedding is a gather."""
    d, layers = keys["hidden_size"], keys["num_hidden_layers"]
    ffn = (
        d * keys["router_experts"] + shared_params(keys) + d
        + keys["num_experts_per_tok"] * expert_params(keys))
    return (
        delta_layers(keys) * delta_mixer_params(keys)
        + (layers - delta_layers(keys)) * attention_params(keys) + layers * ffn
        + d * keys["vocab_size"])


def delta_flops_per_token(keys: Dict[str, Any]) -> float:
    """What the rule itself takes a token and delta layer, whatever computes it: a state
    element a value head is decayed (1), read for the key (2), written (2) and read for
    the query (2): the definition's 7 operations."""
    return 7.0 * keys["linear_num_value_heads"] * keys["linear_key_head_dim"] * (
        keys["linear_value_head_dim"])


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (the repo's train step has no backward of the
    chunked rule); the harness's contract lists the entry point. The count is
    ``matmul_params``, the rule, and causal attention in the full layers."""
    tokens = batch * seq
    full = keys["num_hidden_layers"] - delta_layers(keys)
    pairs = 4.0 * keys["num_attention_heads"] * keys["head_dim"] * batch * seq * (seq + 1) / 2.0
    return 3.0 * (
        2.0 * matmul_params(keys) * tokens + full * pairs
        + delta_layers(keys) * delta_flops_per_token(keys) * tokens)


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the expert layers of the counted device calls had to do, from the engine's
    counters, as ``kimi_k2.experts_work`` counts it: ``flops`` = 2 per parameter of an
    expert for every token-expert pair computed here and 2 per parameter of the shared
    expert for every token and layer (``moe_tokens`` is summed over the layers);
    ``bytes`` = an expert's weights for every (call, layer, held expert with a token) and
    the shared expert's for every (call, layer). Activations are not counted, so both
    are lower bounds of what must move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    calls = (counters.get("phase_n") or {}).get("dispatch", 0)
    return {
        "flops": 2.0 * (
            expert_params(keys) * counters["moe_assignments"]
            + shared_params(keys) * counters["moe_tokens"]),
        "bytes": float(itemsize * (
            expert_params(keys) * counters["moe_experts_hit"]
            + shared_params(keys) * keys["num_hidden_layers"] * calls)),
    }


def delta_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the gated delta rule of the counted device calls had to do, from the engine's
    counters: ``flops`` = :func:`delta_flops_per_token` for every token and delta layer
    (``delta_tokens``; the chunked form does more, the triangular inverse among it, and
    is credited no more); ``bytes`` = a layer's state read and written once a lane, layer
    and call (``delta_state_passes``), in the type it is kept in, and a token's ``q``,
    ``k`` (a row a key head), ``v`` in and ``o`` out in the compute type with its
    ``alpha`` and ``beta`` in float32 (``delta_tokens``). The projections, the
    convolution, the norms and the gate are not the rule's."""
    itemsize = {"bfloat16": 2, "float32": 4}
    heads = keys["linear_num_value_heads"]
    key_inner = keys["linear_num_key_heads"] * keys["linear_key_head_dim"]
    value_inner = heads * keys["linear_value_head_dim"]
    state_bytes = value_inner * keys["linear_key_head_dim"] * itemsize[
        keys.get("state_dtype", "float32")]
    token_bytes = (2 * key_inner + 2 * value_inner) * itemsize[keys["compute_dtype"]] + 8 * heads
    return {
        "flops": delta_flops_per_token(keys) * counters.get("delta_tokens", 0),
        "bytes": float(
            2 * state_bytes * counters.get("delta_state_passes", 0)
            + token_bytes * counters.get("delta_tokens", 0)),
        "state_bytes": float(2 * state_bytes * counters.get("delta_state_passes", 0)),
    }
