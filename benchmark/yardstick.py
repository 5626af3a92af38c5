"""The arithmetic every cell is measured with: the chip's peaks, the
operations a train step requires, and percentiles. Kept with the benchmark so
that no PR that claims a gain can change it."""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

# Published peaks of one chip, keyed by jax's ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth,
# 16 GB of HBM). A device that is not here is an error, not a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}; add it to "
            f"yardstick.PEAKS with its source"
        )
    return PEAKS[device_kind][what]


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that a token is multiplied with: q, k, v, o, the two MLP
    matrices of every layer, and the output head. The input embedding is a
    gather; biases and layer norms are not matrix multiplications."""
    d = model["n_embd"]
    f = model["n_inner"] or 4 * d
    per_layer = 4 * d * d + 2 * d * f
    return model["n_layer"] * per_layer + d * model["vocab_size"]


def train_step_flops(model: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require:
    6 per matmul parameter per token, and causal attention, in which a query
    sees on average (seq + 1) / 2 keys: 2 matmuls (QK^T, PV) x 2 ops x 3
    (forward + backward) = 12 per query-key pair per feature, over the half of
    the square the mask leaves. Recomputation (remat) is not counted."""
    tokens = batch * seq
    heads_x_dim = model["n_embd"]     # n_head * head_dim
    attention = (
        12.0 * model["n_layer"] * batch * heads_x_dim * seq * (seq + 1) / 2.0
    )
    return 6.0 * matmul_params(model) * tokens + attention


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``q`` of the
    sample at or below it); with few samples the 95th is the maximum."""
    if not values:
        return float("nan")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def logits_error(got, want) -> float:
    """Root-mean-square difference of two arrays of logits as a share of the
    standard deviation of ``want``: 0 is equal, 1 is as far off as a constant."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
