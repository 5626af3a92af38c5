"""granite-4.0-h with routed experts (published ``model_type``
``granitemoehybrid``: granite-4.0-h-small): from the published ``config.json``
keys to the program's ``GraniteMoeHybridConfig`` with its expert layer, seeded
weights made on the device in one jitted call, and the operations and bytes
the expert layers require. What the block shares with the configuration
without experts (the layer pattern, a mixer's matrices, the recurrence's work)
is ``granitemoehybrid``'s and is taken from there. A file names this module at
its top level (``model_type``) because the harness finds an architecture by
that key, and keeps the published value under ``published_model_type``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark.models import granitemoehybrid
from benchmark.models.granitemoehybrid import (  # noqa: F401 — what the two blocks share
    WIDTHS, attention_params, layer_pattern, mamba_params, scan_flops_per_token, scan_work,
    seeded_params)


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` scalars as run:
    ``num_local_experts`` is the experts **held here**, from ``expert_offset`` on;
    ``router_experts`` (the benchmark's key) the experts the router scores, which
    is the published ``num_local_experts`` and nothing else; the layer pattern as
    ``layer_period`` / ``attention_layer_offset``; ``compute_dtype``,
    ``param_dtype`` and ``state_dtype`` the benchmark's. Everything but the routed
    half is mapped, and refused, by the module of the block without experts: it is
    handed the keys with the routed half taken out (no expert, the shared MLP's
    width for the only one)."""
    if not 0 < keys["num_experts_per_tok"] <= keys["router_experts"]:
        raise ValueError("this module is the block with routed experts: the router chooses some")
    shared = granitemoehybrid.program_config({
        **keys, "num_local_experts": 0, "num_experts_per_tok": 0,
        "intermediate_size": keys["shared_intermediate_size"]})
    return dataclasses.replace(
        shared, expert_dim=keys["intermediate_size"], router_experts=keys["router_experts"],
        num_experts=keys["num_local_experts"], expert_offset=keys["expert_offset"],
        experts_per_token=keys["num_experts_per_tok"])


def describe(cfg) -> str:
    return (
        f"hidden {cfg.embed_dim} / {cfg.ssm_layers} Mamba-2 layers ({cfg.ssm_heads} heads of "
        f"{cfg.ssm_head_dim} x {cfg.ssm_state} state, conv {cfg.conv_width}, sub-chunks of "
        f"{cfg.ssm_chunk}) and {cfg.periods} attention layers ({cfg.num_heads} heads over "
        f"{cfg.kv_heads} K/V of {cfg.head_dim}, no positions) in periods of {cfg.period} / "
        f"experts {cfg.num_experts} held of {cfg.router_experts} from {cfg.expert_offset}, "
        f"{cfg.experts_per_token} a token, width {cfg.expert_dim}, beside a shared MLP of "
        f"{cfg.mlp_dim} / vocab {cfg.vocab_size} tied / depth {cfg.num_layers} / params "
        f"{cfg.param_dtype.__name__}, state {cfg.state_dtype.__name__} / "
        f"{cfg.num_params() / 1e9:.3f}B params"
    )


def expert_params(keys: Dict[str, Any]) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * keys["hidden_size"] * keys["intermediate_size"]


def shared_params(keys: Dict[str, Any]) -> int:
    """Parameters of a layer's shared MLP."""
    return 3 * keys["hidden_size"] * keys["shared_intermediate_size"]


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters a token is multiplied with on this chip **at most**: every
    mixer's matrices, every layer's router, shared MLP and ``num_experts_per_tok``
    routed experts (fewer where the chosen are held elsewhere), and the tied
    head. The input embedding is a gather."""
    layers = keys["num_hidden_layers"]
    attention = layers // keys["layer_period"]
    second_half = (
        keys["hidden_size"] * keys["router_experts"] + shared_params(keys)
        + keys["num_experts_per_tok"] * expert_params(keys))
    return (
        (layers - attention) * mamba_params(keys) + attention * attention_params(keys)
        + layers * second_half + keys["hidden_size"] * keys["vocab_size"])


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


def experts_work(keys: Dict[str, Any], counters: Dict[str, Any]) -> Dict[str, float]:
    """What the **routed** experts of the counted device calls had to do, from
    the engine's counters: ``flops`` = 2 per parameter of an expert for every
    token-expert pair computed here (``moe_assignments``); ``bytes`` = an
    expert's weights for every (call, layer, held expert with a token)
    (``moe_experts_hit``). Activations are not counted, so both are lower bounds
    of what must move under ``extend.moe.experts``. The shared MLP is **not** in
    it (``shared_flops``, ``shared_bytes`` say what it takes: 2 per parameter a
    token and layer, ``moe_tokens``; its weights once a call and layer): its
    25 + 13 MB a layer reach the chip's VMEM by the scan's own prefetch
    (``copy-done``, under no scope), so the seconds under ``extend.moe.shared``
    leave out the read, and a share that counted its bytes over them read
    3.5 times the HBM rate for that part (PERF.md, PR 47)."""
    calls = counters["phase_n"]["dispatch"]
    itemsize = {"bfloat16": 2, "float32": 4}[keys["param_dtype"]]
    return {
        "flops": 2.0 * expert_params(keys) * counters["moe_assignments"],
        "bytes": float(itemsize * expert_params(keys) * counters["moe_experts_hit"]),
        "shared_flops": 2.0 * shared_params(keys) * counters["moe_tokens"],
        "shared_bytes": float(
            itemsize * shared_params(keys) * keys["num_hidden_layers"] * calls),
    }
