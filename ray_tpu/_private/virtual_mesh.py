"""Virtual multi-device CPU mesh bring-up (shared by tests and the driver).

Multi-chip sharding logic is validated on an n-device virtual CPU platform
wherever the real devices are too few; this module is the one copy of the
recipe (env vars for child processes + jax.config for this process).

Reference analogue: the conftest trick in python/ray/tests/conftest.py of
the upstream project — shape multi-node logic on one host.
"""

from __future__ import annotations

import os
import re


def set_virtual_cpu_env(n_devices: int) -> None:
    """Point env vars at an n-device CPU platform (children inherit them)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", opt, flags
        )
    else:
        flags = (flags + " " + opt).strip()
    os.environ["XLA_FLAGS"] = flags


def ensure_virtual_devices(n_devices: int) -> None:
    """Guarantee ≥ n_devices jax devices: the real ones where there are
    enough, else an n-device virtual CPU platform."""
    import jax

    if len(jax.devices()) >= n_devices:
        return
    import jax.extend.backend as jeb

    jeb.clear_backends()
    set_virtual_cpu_env(n_devices)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    assert len(jax.devices()) >= n_devices, (
        f"virtual CPU mesh bring-up failed: need {n_devices}, "
        f"have {len(jax.devices())}"
    )


def backends_initialized() -> bool:
    """Whether this process has created a jax backend (and so holds the
    chip, where there is one)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()
