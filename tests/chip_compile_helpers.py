"""What ``tests/test_chip_compile_train.py`` and ``tests/test_chip_compile_serve.py``
share: the described chip, its memory and how a compiled program's bytes are
summed. Two files so that ``--dist loadfile`` can give each a worker: each worker
then loads the TPU's library, which the driver's command allows
(``ALLOW_MULTIPLE_LIBTPU_LOAD=1``); under pytest-xdist without it the worker that
comes second to the library skips its file (``v5e``), and one process runs both."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import gpt

HBM_BYTES = 16909336064  # bytes_limit of one v5e chip, as its memory_stats() reports


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _gptj(depth):
    return gpt.gpt_j_6b(num_layers=depth, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.fixture
def shaped(v5e):
    one = SingleDeviceSharding(v5e[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
