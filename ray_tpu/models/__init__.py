"""Model families (flax, logically-sharded, TPU-first).

``gpt`` is imported here; the served and trained architectures beside it are
modules of their own, each composed from ``layers`` (and ``moe``) and imported by
name where a configuration asks for one: ``cohere2_moe``, ``keye_vl2``, ``kimi_k2``,
``granitemoehybrid``, ``minicpm_sala``, ``mimo_v2_flash``, ``qwen3_next``, ``glm_moe_dsa``,
``longcat_flash`` (serving) and
``lfm2_moe``, ``nemotron_h`` (training)."""

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
