"""From a configuration's file to the program's ``GPTConfig``, and seeded
weights made on the device in one jitted call."""

from __future__ import annotations

from typing import Any, Dict


def published_keys(config: Dict[str, Any]) -> Dict[str, Any]:
    """The top level of a configuration's file without its nested groups: the
    published ``config.json`` keys as run, and the benchmark's two dtypes."""
    return {k: v for k, v in config.items() if not isinstance(v, (dict, list))}


def gpt_config(model: Dict[str, Any]):
    """``model`` holds the keys of the published ``config.json`` (GPT-J's
    names) as run; ``compute_dtype`` / ``param_dtype`` are the benchmark's."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    d, h = model["n_embd"], model["n_head"]
    if d % h:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {h}")
    return GPTConfig(
        vocab_size=model["vocab_size"], num_layers=model["n_layer"], num_heads=h,
        head_dim=d // h, embed_dim=d, mlp_dim=model["n_inner"] or 4 * d,
        max_seq_len=model["n_positions"], rotary_dim=model["rotary_dim"],
        dtype=jnp.dtype(model["compute_dtype"]).type,
        param_dtype=jnp.dtype(model["param_dtype"]).type,
        tie_embeddings=bool(model["tie_word_embeddings"]),
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
