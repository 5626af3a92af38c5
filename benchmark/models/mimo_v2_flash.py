"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``): from the published
``config.json`` keys to the program's ``MiMoV2FlashConfig``, seeded weights made on
the device in one jitted call, and the operations and bytes that the two kinds of
attend and the expert layers require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "v_head_dim", "swa_num_attention_heads",
    "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim", "num_experts_per_tok",
    "sliding_window", "sliding_window_size", "partial_rotary_factor",
)


def pattern(keys: Dict[str, Any]):
    """Per layer that runs, 1 where it slides: ``layer_pattern`` (comma-separated: the
    top-level scalar that says what ``hybrid_layer_pattern`` says)."""
    flags = [int(x) for x in keys["layer_pattern"].split(",")]
    if len(flags) != keys["num_hidden_layers"]:
        raise ValueError(f"{keys['num_hidden_layers']} layers, and a pattern of {len(flags)}")
    return flags


def rotary_features(keys: Dict[str, Any]) -> int:
    return int(keys["partial_rotary_factor"] * keys["head_dim"])


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run: ``n_routed_experts``
    is the experts **held here**, from ``expert_offset`` on; ``router_experts`` (the
    benchmark's key) the experts the router scores, which is the published
    ``n_routed_experts`` and nothing else; ``layer_pattern`` the layers' kinds
    (:func:`pattern`); ``attention_sink_bias_std`` and ``e_score_correction_bias_std``
    the spreads of the seeded sinks and of the seeded router bias; ``compute_dtype`` /
    ``param_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.mimo_v2_flash import MiMoV2FlashConfig

    wanted = {
        "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "attention_bias": False,
        "tie_word_embeddings": False, "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "n_shared_experts": None,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one mimo_v2_flash block, and not one with {differ}")
    same = {
        "swa_num_attention_heads": "num_attention_heads", "swa_head_dim": "head_dim",
        "swa_v_head_dim": "v_head_dim", "sliding_window_size": "sliding_window"}
    unlike = {k: keys[k] for k, other in same.items() if keys[k] != keys[other]}
    if unlike:
        raise ValueError(f"the program's two kinds of layer differ in K/V heads alone: {unlike}")
    return MiMoV2FlashConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"],
        sliding_layers=tuple(pattern(keys)), embed_dim=keys["hidden_size"],
        num_heads=keys["num_attention_heads"], head_dim=keys["head_dim"],
        v_dim=keys["v_head_dim"], kv_heads=keys["num_key_value_heads"],
        sliding_kv_heads=keys["swa_num_key_value_heads"], rotary_dim=rotary_features(keys),
        sliding_window=keys["sliding_window"], rope_base=float(keys["rope_theta"]),
        sliding_rope_base=float(keys["swa_rope_theta"]),
        value_scale=float(keys["attention_value_scale"]),
        sink_std=float(keys["attention_sink_bias_std"]), mlp_dim=keys["intermediate_size"],
        expert_dim=keys["moe_intermediate_size"], router_experts=keys["router_experts"],
        num_experts=keys["n_routed_experts"], expert_offset=keys["expert_offset"],
        experts_per_token=keys["num_experts_per_tok"],
        routed_scale=float(keys["routed_scaling_factor"] or 1.0),
        bias_std=float(keys["e_score_correction_bias_std"]),
        norm_eps=keys["layernorm_epsilon"], max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they are
    served in (the program's own init)."""
    return cfg.init_params(seed)


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_heads} heads, q and k {cfg.head_dim} "
        f"({cfg.rotary_dim} rotated), v {cfg.v_dim} / {cfg.window_layers} sliding layers "
        f"({cfg.sliding_kv_heads} K/V heads, a window of {cfg.sliding_window} kept as state, a "
        f"sink a head) and {cfg.cache_layers} full ({cfg.kv_heads} K/V heads, paged) in periods of "
        f"{cfg.period} behind layer 0 / MLP {cfg.mlp_dim} / experts {cfg.num_experts} held of "
        f"{cfg.router_experts} from {cfg.expert_offset}, {cfg.experts_per_token} a token, width "
        f"{cfg.expert_dim} / vocab {cfg.vocab_size} untied / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def attention_params(keys: Dict[str, Any], sliding: bool) -> int:
    """The matrices of one layer's attention: q, k, v and o."""
    d, heads = keys["hidden_size"], keys["num_attention_heads"]
    kv = keys["swa_num_key_value_heads" if sliding else "num_key_value_heads"]
    per_head = keys["head_dim"] + keys["v_head_dim"]
    return d * heads * per_head + d * kv * per_head


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: the attention
    of every layer, layer 0's MLP, the router and ``num_experts_per_tok`` routed experts
    of every other layer (fewer where the chosen are held elsewhere), and the untied
    output head. The input embedding is a gather."""
    d, flags = keys["hidden_size"], pattern(keys)
    routed = d * keys["router_experts"] + keys["num_experts_per_tok"] * expert_params(keys)
    return (
        sum(attention_params(keys, bool(s)) for s in flags)
        + 3 * d * keys["intermediate_size"] + (len(flags) - 1) * routed
        + d * keys["vocab_size"])


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """The benchmark trains no such model (at this repo's bytes a parameter nothing of
    it fits a chip's floors); the harness's contract lists the entry point. The count
    is ``matmul_params`` plus attention over what a query sees: every key up to its own
    in a full layer, at most ``sliding_window`` in a sliding one."""
    flags = pattern(keys)
    per_pair = 2.0 * keys["num_attention_heads"] * (keys["head_dim"] + keys["v_head_dim"])
    causal = seq * (seq + 1) / 2.0
    banded = sum(min(t + 1, keys["sliding_window"]) for t in range(seq))
    pairs = batch * (flags.count(0) * causal + flags.count(1) * banded)
    return 6.0 * matmul_params(keys) * batch * seq + 3.0 * per_pair * pairs


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the expert layers of the counted device calls had to do, from the engine's
    counters, as ``kimi_k2.experts_work`` counts it: ``flops`` = 2 per parameter of an
    expert for every token-expert pair computed here; ``bytes`` = an expert's weights
    for every (call, layer, held expert with a token). There is no shared expert.
    Activations are not counted, so both are lower bounds of what must move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    return {
        "flops": 2.0 * expert_params(keys) * counters["moe_assignments"],
        "bytes": float(itemsize * expert_params(keys) * counters["moe_experts_hit"]),
    }


def attend_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What both kinds of attend of the counted device calls had to do, from the
    engine's counters: ``flops`` = 2 a feature of a key and of a value for every query
    head and query-key pair inside the mask (``full_keys``, ``window_keys``: summed over
    the layers of each kind); ``bytes`` = the K and V rows that have to be read once a
    call, layer and K/V head: of a full layer the live slots the engine gathered
    (``cache_tokens``), of a sliding layer a decode lane's ``sliding_window`` rows
    (``calls.decode.lanes_used``) and a chunk's own rows (``calls.prefill.tokens``; the
    slot's rows beside them are not counted). The projections, the rotation, the
    writes and activations are not counted, so both are lower bounds of what must
    move."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["compute_dtype"]]
    per_head = keys["head_dim"] + keys["v_head_dim"]
    flags = pattern(keys)
    calls = counters.get("calls") or {}
    window_rows = (
        keys["sliding_window"] * (calls.get("decode") or {}).get("lanes_used", 0)
        + (calls.get("prefill") or {}).get("tokens", 0))
    return {
        "flops": 2.0 * per_head * keys["num_attention_heads"] * (
            counters.get("full_keys", 0) + counters.get("window_keys", 0)),
        "bytes": float(itemsize * per_head * (
            flags.count(0) * keys["num_key_value_heads"] * counters.get("cache_tokens", 0)
            + flags.count(1) * keys["swa_num_key_value_heads"] * window_rows)),
    }
