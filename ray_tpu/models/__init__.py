"""Model families (flax, logically-sharded, TPU-first)."""

from ray_tpu.models.gpt import (
    GPT,
    GPTConfig,
    gpt_1b,
    gpt_j_6b,
    gpt_nano,
    next_token_loss,
)
from ray_tpu.models.training import (
    TrainState,
    default_optimizer,
    init_params,
    init_sharded_state,
    make_eval_step,
    make_forward,
    make_train_step,
    state_shardings,
)

__all__ = [
    "GPT",
    "GPTConfig",
    "gpt_nano",
    "gpt_1b",
    "gpt_j_6b",
    "next_token_loss",
    "TrainState",
    "default_optimizer",
    "init_params",
    "init_sharded_state",
    "make_eval_step",
    "make_forward",
    "make_train_step",
    "state_shardings",
]
