"""GPT-J (``model_type`` ``gptj``): from the published ``config.json`` keys to
the program's ``GPTConfig``, seeded weights made on the device in one jitted
call, and the operations one train step of a dense GPT-J block requires."""

from __future__ import annotations

from typing import Any, Dict

# the published keys that no configuration may cut
WIDTHS = ("n_embd", "n_head", "n_inner", "rotary_dim", "vocab_size")


def program_config(keys: Dict[str, Any]):
    """``keys`` holds the published ``config.json`` keys (GPT-J's names) as
    run; ``compute_dtype`` / ``param_dtype`` are the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    d, h = keys["n_embd"], keys["n_head"]
    if d % h:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {h}")
    return GPTConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["n_layer"], num_heads=h,
        head_dim=d // h, embed_dim=d, mlp_dim=keys["n_inner"] or 4 * d,
        max_seq_len=keys["n_positions"], rotary_dim=keys["rotary_dim"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
        tie_embeddings=bool(keys["tie_word_embeddings"]),
    )


def seeded_params(cfg, seed: int):
    """The server's weights: one jitted call, on the device, in the dtype they
    are served in (the program's ``make_params`` runs the same init eagerly)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    model = gpt.GPT(cfg)

    @jax.jit
    def init(rng):
        return gpt.unboxed_params(model.init(rng, jnp.zeros((1, 8), jnp.int32)))

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def describe(cfg) -> str:
    return (
        f"embed {cfg.embed_dim} / {cfg.num_heads} heads x {cfg.head_dim} / mlp "
        f"{cfg.mlp_dim} / vocab {cfg.vocab_size} / depth {cfg.num_layers} / "
        f"params {cfg.param_dtype.__name__} / {cfg.num_params() / 1e9:.2f}B params"
    )


def matmul_params(keys: Dict[str, Any]) -> int:
    """Parameters that a token is multiplied with: q, k, v, o, the two MLP
    matrices of every layer, and the output head. The input embedding is a
    gather; biases and layer norms are not matrix multiplications."""
    d = keys["n_embd"]
    f = keys["n_inner"] or 4 * d
    per_layer = 4 * d * d + 2 * d * f
    return keys["n_layer"] * per_layer + d * keys["vocab_size"]


def train_step_flops(keys: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require:
    6 per matmul parameter per token, and causal attention, in which a query
    sees on average (seq + 1) / 2 keys: 2 matmuls (QK^T, PV) x 2 ops x 3
    (forward + backward) = 12 per query-key pair per feature, over the half of
    the square the mask leaves. Recomputation (remat) is not counted."""
    tokens = batch * seq
    heads_x_dim = keys["n_embd"]     # n_head * head_dim
    attention = (
        12.0 * keys["n_layer"] * batch * heads_x_dim * seq * (seq + 1) / 2.0
    )
    return 6.0 * matmul_params(keys) * tokens + attention
