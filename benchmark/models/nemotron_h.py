"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): from the published
``config.json`` keys to the program's ``NemotronHConfig``, seeded weights made on
the device in one jitted call, and the operations and bytes a train step, its
expert layers and its chunked scans require."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

if importlib.util.find_spec("ray_tpu.models.nemotron_h") is None:
    # a checkout from before the model was built: said here, at once, and not by a
    # worker that has already taken the chip
    raise ImportError("this checkout's ray_tpu has no models/nemotron_h.py: it cannot run the cell")

# the published keys that no configuration may cut. The router's width is the
# benchmark's key ``router_experts`` and no published key (``n_routed_experts`` counts
# the experts held here and may be a chip's share):
# ``tests/benchmark/test_bench_nemotron_h.py`` holds it to the published count
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_experts_per_tok", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
    "n_groups", "conv_kernel", "chunk_size", "expand",
)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def pattern(keys: Dict[str, Any]) -> str:
    """The layers' letters, ``hybrid_override_pattern`` as run: ``M`` a Mamba-2 mixer,
    ``E`` an expert layer, ``*`` attention (``-``, a dense MLP, is in no published
    pattern of this model and not built)."""
    letters = keys["hybrid_override_pattern"]
    if len(letters) != keys["num_hidden_layers"] or set(letters) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(
            f"hybrid_override_pattern {letters!r} does not name num_hidden_layers "
            f"{keys['num_hidden_layers']} layers of M, E and *")
    return letters


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run: ``n_routed_experts``
    is the experts **held here**, from ``expert_offset`` on; ``router_experts`` (the
    benchmark's key) the experts the router scores, which is the published
    ``n_routed_experts`` and nothing else; ``residual_layers`` the published depth,
    which ``rescale_prenorm_residual`` divides by whatever the cut; ``expert_bias_std``
    the spread of the seeded bias and ``expert_bias_update_rate`` how far a step moves it
    towards an even load; ``compute_dtype`` / ``param_dtype`` the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import NemotronHConfig

    wanted = {
        "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
        "use_conv_bias": True, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
        "tie_word_embeddings": False, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "sliding_window": None,
    }
    differ = {k: keys[k] for k, v in wanted.items() if k in keys and keys[k] != v}
    if differ or keys["norm_eps"] != keys["layer_norm_epsilon"]:
        raise ValueError(
            f"the program has one nemotron_h block, and not one with {differ} or with two epsilons")
    return NemotronHConfig(
        vocab_size=keys["vocab_size"], pattern=pattern(keys), embed_dim=keys["hidden_size"],
        num_heads=keys["num_attention_heads"], kv_heads=keys["num_key_value_heads"],
        head_dim=keys["head_dim"], ssm_heads=keys["mamba_num_heads"],
        ssm_head_dim=keys["mamba_head_dim"], ssm_state=keys["ssm_state_size"],
        ssm_groups=keys["n_groups"], ssm_chunk=keys["chunk_size"], conv_kernel=keys["conv_kernel"],
        expert_dim=keys["moe_intermediate_size"],
        shared_dim=keys["moe_shared_expert_intermediate_size"],
        router_experts=keys["router_experts"], num_experts=keys["n_routed_experts"],
        expert_offset=keys["expert_offset"], experts_per_token=keys["num_experts_per_tok"],
        routed_scale=float(keys["routed_scaling_factor"]),
        bias_std=float(keys["expert_bias_std"]),
        bias_update_rate=float(keys["expert_bias_update_rate"]), norm_eps=keys["norm_eps"],
        time_step_min=keys["time_step_min"], time_step_max=keys["time_step_max"],
        time_step_floor=keys["time_step_floor"], residual_layers=keys["residual_layers"],
        max_seq_len=keys["max_position_embeddings"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
    )


def seeded_params(cfg, seed: int):
    """The weights, on the device in one jitted call, in the type they are
    trained in (the program's own init, as ``init_sharded_state`` calls it)."""
    import jax

    from ray_tpu.models import nemotron_h

    return jax.block_until_ready(
        jax.jit(lambda rng: nemotron_h.init_params(cfg, rng))(jax.random.PRNGKey(seed)))


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.num_layers} layers {cfg.pattern} (Mamba-2: "
        f"{cfg.ssm_heads} heads x {cfg.ssm_head_dim}, state {cfg.ssm_state}, {cfg.ssm_groups} "
        f"B/C groups, {cfg.conv_kernel} taps, sub-chunks of {cfg.ssm_chunk}; attention: "
        f"{cfg.num_heads} heads over {cfg.kv_heads} K/V x {cfg.head_dim}, no rotation) / experts "
        f"{cfg.num_experts} held of {cfg.router_experts} from {cfg.expert_offset}, "
        f"{cfg.experts_per_token} a token x {cfg.routed_scale}, relu^2 of width {cfg.expert_dim}, "
        f"shared {cfg.shared_dim} / vocab {cfg.vocab_size} untied / params "
        f"{cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert: up and down, no gate."""
    return 2 * keys["hidden_size"] * keys["moe_intermediate_size"]


def mixer_matmul_params(keys: Dict[str, Any]) -> Dict[str, float]:
    """Parameters a token is multiplied with in one layer of each kind, the held
    experts in expectation."""
    d = keys["hidden_size"]
    inner = keys["mamba_num_heads"] * keys["mamba_head_dim"]
    in_width = 2 * inner + 2 * keys["n_groups"] * keys["ssm_state_size"] + keys["mamba_num_heads"]
    q = keys["num_attention_heads"] * keys["head_dim"]
    kv = keys["num_key_value_heads"] * keys["head_dim"]
    held = keys["num_experts_per_tok"] * keys["n_routed_experts"] / keys["router_experts"]
    return {
        MAMBA: d * in_width + inner * d,
        ATTENTION: 2 * d * q + 2 * d * kv,
        EXPERTS: d * keys["router_experts"]
        + 2 * d * keys["moe_shared_expert_intermediate_size"] + held * expert_params(keys),
    }


def matmul_params(keys: Dict[str, Any]) -> float:
    """Parameters a token is multiplied with on this chip **in expectation**: a Mamba
    layer's in and out projections, an attention layer's q, k, v, o, an expert layer's
    router, shared expert and ``num_experts_per_tok x n_routed_experts /
    router_experts`` routed experts (the held share of a token's choices: a choice held
    elsewhere is computed elsewhere), and the untied head. The input embedding is a
    gather; the convolution's taps, the recurrence (:func:`scan_work`) and the norms
    multiply no parameter matrix."""
    per_layer = mixer_matmul_params(keys)
    return sum(per_layer[kind] for kind in pattern(keys)) + keys["hidden_size"] * keys["vocab_size"]


def scan_work(keys: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """What the Mamba layers' recurrences of one step require at the published
    ``chunk_size`` with nothing replayed, whatever implements the scan. ``flops``:
    three passes (forward, and the backward's two products for each of the forward's)
    of, a token and layer, ``2 (groups x chunk x n + heads x chunk x p + 2 heads x p x
    n)``: the C.B pairs of a group, the masked product of a head, the state read out
    and fed. ``bytes``: ``x``, ``dt``, ``B``, ``C`` read and ``y`` written once a pass
    in the compute type, and the states between sub-chunks (``heads x p x n`` float32 a
    sub-chunk and sequence) written once and read once a step."""
    heads, p, n = keys["mamba_num_heads"], keys["mamba_head_dim"], keys["ssm_state_size"]
    groups, chunk = keys["n_groups"], keys["chunk_size"]
    mamba_layers = pattern(keys).count(MAMBA)
    tokens = batch * seq * mamba_layers
    forward = 2.0 * (groups * chunk * n + heads * chunk * p + 2 * heads * p * n)
    rows = ITEMSIZE[keys["compute_dtype"]] * (2 * heads * p + heads + 2 * groups * n)
    states = 2 * 4 * heads * p * n * (tokens / chunk)
    return {"flops": 3 * forward * tokens, "bytes": float(3 * rows * tokens + states)}


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require: 6 per matmul
    parameter per token (the experts in expectation, ``matmul_params``), causal
    attention in the attention layers, 12 per query-key pair and feature over the half
    of the square the mask leaves, and the recurrences' own (:func:`scan_work`).
    Recomputation (remat), the repeat of K and V and the convolution's taps are not
    counted."""
    q = keys["num_attention_heads"] * keys["head_dim"]
    attention = 12.0 * pattern(keys).count(ATTENTION) * batch * q * seq * (seq + 1) / 2.0
    return (
        6.0 * matmul_params(keys) * batch * seq + attention + scan_work(keys, batch, seq)["flops"])


def experts_work(keys: Dict[str, Any], counted: Dict[str, float]) -> Dict[str, float]:
    """What the routed experts of the counted train steps had to do, from the steps'
    own counters (``moe_assignments``: token-expert pairs computed here,
    ``moe_experts_hit``: held experts with at least one pair, both summed over the
    expert layers and the steps): ``flops`` = 3 passes (forward, the input's gradient,
    the weights' gradient) x 2 a parameter of a two-matrix expert a pair; ``bytes`` = a
    pass reads a hit expert's weights once (3 reads) and the step writes their gradient
    once, and each pair's row of ``hidden_size`` goes in and comes out once a pass. The
    remat's replay, the router and the shared expert are not counted."""
    per_expert = expert_params(keys)
    return {
        "flops": 3 * 2.0 * per_expert * counted["moe_assignments"],
        "bytes": float(
            4 * ITEMSIZE[keys["param_dtype"]] * per_expert * counted["moe_experts_hit"]
            + 3 * 2 * ITEMSIZE[keys["compute_dtype"]] * keys["hidden_size"]
            * counted["moe_assignments"]),
    }
