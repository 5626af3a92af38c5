"""LFM2-24B-A2B (``model_type`` ``lfm2_moe``): from the published ``config.json``
keys to the program's ``Lfm2MoeConfig``, seeded weights made on the device in
one jitted call, and the operations and bytes a train step and its expert
layers require."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut. The router's width is the
# benchmark's key ``router_experts`` and no published key (the published
# ``num_experts`` counts the experts held here and may be a chip's share):
# ``tests/benchmark/test_bench_lfm2_moe.py`` holds it to the published count
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "num_experts_per_tok", "conv_L_cache",
)

CONV, ATTENTION = "conv", "full_attention"


def layer_types(keys: Dict[str, Any]):
    """The layers' mixers from the scalars that stand for ``layer_types`` (the
    harness hands an architecture top-level scalars only): ``layer_pattern``,
    a letter a layer, ``c`` a conv mixer and ``a`` full attention."""
    kinds = tuple({"c": CONV, "a": ATTENTION}[letter] for letter in keys["layer_pattern"])
    if len(kinds) != keys["num_hidden_layers"]:
        raise ValueError(
            f"layer_pattern names {len(kinds)} layers, num_hidden_layers {keys['num_hidden_layers']}")
    return kinds


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run: ``num_experts``
    is the experts **held here**, from ``expert_offset`` on; ``router_experts``
    (the benchmark's key) the experts the router scores, which is the published
    ``num_experts`` and nothing else; ``layer_pattern`` and ``rope_theta`` what
    ``layer_types`` and ``rope_parameters`` say, as scalars; ``expert_bias_std``
    the spread of the seeded bias; ``compute_dtype`` / ``param_dtype`` the
    benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    wanted = {
        "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
        "rope_type": "default", "tie_word_embeddings": True,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ:
        raise ValueError(f"the program has one lfm2_moe block, and not one with {differ}")
    d, heads = keys["hidden_size"], keys["num_attention_heads"]
    if keys.get("head_dim", d // heads) * heads != d:
        raise ValueError(f"head_dim is not hidden_size {d} / num_attention_heads {heads}")
    return Lfm2MoeConfig(
        vocab_size=keys["vocab_size"], layer_types=layer_types(keys),
        dense_layers=keys["num_dense_layers"], embed_dim=d, num_heads=heads,
        kv_heads=keys["num_key_value_heads"], head_dim=d // heads,
        conv_kernel=keys["conv_L_cache"], mlp_dim=keys["intermediate_size"],
        expert_dim=keys["moe_intermediate_size"], router_experts=keys["router_experts"],
        num_experts=keys["num_experts"], expert_offset=keys["expert_offset"],
        experts_per_token=keys["num_experts_per_tok"],
        routed_scale=float(keys["routed_scaling_factor"]),
        bias_std=float(keys["expert_bias_std"]), rope_base=float(keys["rope_theta"]),
        norm_eps=keys["norm_eps"], max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The weights, on the device in one jitted call, in the type they are
    trained in (the program's own init, as ``init_sharded_state`` calls it)."""
    import jax

    from ray_tpu.models import lfm2_moe

    return jax.block_until_ready(
        jax.jit(lambda rng: lfm2_moe.init_params(cfg, rng))(jax.random.PRNGKey(seed)))


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_layers} layers {''.join('c' if k == CONV else 'a' for k in cfg.layer_types)} "
        f"(conv of {cfg.conv_kernel} taps; {cfg.num_heads} heads over {cfg.kv_heads} K/V x "
        f"{cfg.head_dim}) / {cfg.dense_layers} dense of {cfg.mlp_dim} / experts {cfg.num_experts} "
        f"held of {cfg.router_experts} from {cfg.expert_offset}, {cfg.experts_per_token} a token, "
        f"width {cfg.expert_dim} / vocab {cfg.vocab_size} / params {cfg.param_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["moe_intermediate_size"]


def matmul_params(keys: Dict[str, Any]) -> float:
    """Parameters a token is multiplied with on this chip **in expectation**: every
    layer's mixer (conv: in and out projections; attention: q, k, v, o), the dense
    layers' MLP, the router and ``num_experts_per_tok x num_experts / router_experts``
    experts of every expert layer (the held share of a token's choices: a choice
    held elsewhere is computed elsewhere), and the tied head. The input
    embedding is a gather; the convolution's taps and the norms multiply no matrix."""
    d, kinds = keys["hidden_size"], layer_types(keys)
    kv = d // keys["num_attention_heads"] * keys["num_key_value_heads"]
    mixer = {CONV: 4 * d * d, ATTENTION: 2 * d * d + 2 * d * kv}
    dense = keys["num_dense_layers"]
    held = keys["num_experts_per_tok"] * keys["num_experts"] / keys["router_experts"]
    return (
        sum(mixer[kind] for kind in kinds) + dense * 3 * d * keys["intermediate_size"]
        + (len(kinds) - dense) * (d * keys["router_experts"] + held * expert_params(keys))
        + d * keys["vocab_size"])


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require: 6 per
    matmul parameter per token (the experts in expectation, ``matmul_params``),
    and causal attention in the attention layers, 12 per query-key pair and
    feature over the half of the square the mask leaves. Recomputation (remat),
    the repeat of K and V and the convolution's 3 taps are not counted."""
    attention_layers = sum(kind == ATTENTION for kind in layer_types(keys))
    attention = 12.0 * attention_layers * batch * keys["hidden_size"] * seq * (seq + 1) / 2.0
    return 6.0 * matmul_params(keys) * batch * seq + attention


def experts_work(keys: Dict[str, Any], counted: Dict[str, float]) -> Dict[str, float]:
    """What the expert layers of the counted train steps had to do, from the
    steps' own counters (``moe_assignments``: token-expert pairs computed here,
    ``moe_experts_hit``: held experts with at least one pair, both summed over the
    expert layers and the steps): ``flops`` = 3 passes (forward, the input's
    gradient, the weights' gradient) x 2 a parameter of an expert a pair; ``bytes``
    = a pass reads a hit expert's weights once (3 reads) and the step writes
    their gradient once, and each pair's row of ``hidden_size`` goes in and comes
    out once a pass. The remat's replay of the forward is not counted."""
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    activations = {"bfloat16": 2, "float32": 4}[keys["compute_dtype"]]
    per_expert = expert_params(keys)
    return {
        "flops": 3 * 2.0 * per_expert * counted["moe_assignments"],
        "bytes": float(
            4 * itemsize * per_expert * counted["moe_experts_hit"]
            + 3 * 2 * activations * keys["hidden_size"] * counted["moe_assignments"]),
    }
