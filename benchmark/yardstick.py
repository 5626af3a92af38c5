"""The arithmetic every cell is measured with: the chip's peaks, a kernel's
share of its roofline, percentiles, and how far two arrays of logits are
apart. Kept with the benchmark so that no PR that claims a gain can change it.
What one architecture's train step requires is in ``models/<model_type>.py``."""

from __future__ import annotations

import math
from typing import Dict, Sequence

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


def roofline_share(flops: float, bytes_moved: float, seconds: float, device_kind: str) -> float:
    """A kernel's share of its roofline, in percent: the time the chip needs
    at its peaks for ``flops`` operations or for ``bytes_moved`` bytes of HBM
    traffic, whichever is longer, over the ``seconds`` the kernel took. The
    reader brings its own count of operations and bytes; a share over 100
    says that count is too high or the time leaves out part of the work."""
    at_peak = max(
        flops / peak(device_kind, "bf16_flops"),
        bytes_moved / peak(device_kind, "hbm_bytes_per_s"),
    )
    return 100.0 * at_peak / seconds


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
