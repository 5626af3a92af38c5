"""Model FLOP/s utilization: the operations the forward and backward passes
require (``train_step_flops`` of the cell's ``models/<model_type>.py``: causal
attention counted over the half of the square the mask leaves, recomputation
not counted) x steps / the window / (chips x the chip's published bf16 peak).
In a traced run the profiler's own start and stop are left out of the window."""

from benchmark.yardstick import peak


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    rate = run["flops_per_step"] * run["steps"] / run["work_s"]
    return 100.0 * rate / (run["chips"] * peak(run["device_kind"], "bf16_flops"))
