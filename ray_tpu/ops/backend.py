"""Which platform a program is being built for: the one question every
kernel choice in the package asks (fused attention, the grouped matmul)."""

import jax


def on_tpu() -> bool:
    # a backend that fails to initialize must surface here, not quietly
    # become the O(T^2) XLA path
    return jax.devices()[0].platform == "tpu"
