"""``tinyalt``'s plain reference, with the two entry points every reference
has. The program runs ``tinyalt`` as its one decoder, so the mathematics is
``gptj_reference``'s under ``tinyalt``'s key names."""

from benchmark.reference import gptj_reference as same_mathematics


def _renamed(config):
    return {
        "n_embd": config["hidden_size"], "n_head": config["num_attention_heads"],
        "n_layer": config["num_hidden_layers"], "rotary_dim": config["rotary_dim"],
        "reference": {
            "program_layer_norm_epsilon": config["reference"]["program_norm_epsilon"],
        },
    }


def program_loss(program_params, tokens, config) -> float:
    return same_mathematics.program_loss(program_params, tokens, _renamed(config))


def program_logits(program_params, tokens, config, last: int):
    return same_mathematics.program_logits(program_params, tokens, _renamed(config), last)
