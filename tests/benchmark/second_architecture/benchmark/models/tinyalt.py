"""``tinyalt``: a second architecture for the proof that one is added by new
files alone. Its published keys go by other names than GPT-J's
(``hidden_size``, ``num_attention_heads``, ``num_hidden_layers``); the program
has one decoder today, so it runs as that decoder, with a flop count of its
own."""

WIDTHS = ("hidden_size", "num_attention_heads", "intermediate_size", "rotary_dim", "vocab_size")


def program_config(keys):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    d, h = keys["hidden_size"], keys["num_attention_heads"]
    return GPTConfig(
        vocab_size=keys["vocab_size"], num_layers=keys["num_hidden_layers"], num_heads=h,
        head_dim=d // h, embed_dim=d, mlp_dim=keys["intermediate_size"],
        max_seq_len=keys["max_position_embeddings"], rotary_dim=keys["rotary_dim"],
        dtype=jnp.dtype(keys["compute_dtype"]).type,
        param_dtype=jnp.dtype(keys["param_dtype"]).type,
        tie_embeddings=bool(keys["tie_word_embeddings"]),
    )


def seeded_params(cfg, seed: int):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    model = gpt.GPT(cfg)

    @jax.jit
    def init(rng):
        return gpt.unboxed_params(model.init(rng, jnp.zeros((1, 8), jnp.int32)))

    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def describe(cfg) -> str:
    return f"tinyalt: hidden {cfg.embed_dim}, {cfg.num_layers} layers, vocab {cfg.vocab_size}"


def matmul_params(keys) -> int:
    d, f = keys["hidden_size"], keys["intermediate_size"]
    return keys["num_hidden_layers"] * (4 * d * d + 2 * d * f) + d * keys["vocab_size"]


def train_step_flops(keys, batch: int, seq: int) -> float:
    attention = 12.0 * keys["num_hidden_layers"] * batch * keys["hidden_size"] * seq * (seq + 1) / 2
    return 6.0 * matmul_params(keys) * batch * seq + attention
