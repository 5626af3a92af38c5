"""Test fixtures.

JAX runs on a virtual 8-device CPU mesh, so multi-chip sharding logic is
testable anywhere and no test touches a chip; the runtime fixtures mirror
the reference's ray_start_regular / ray_start_cluster conftest fixtures
(reference: python/ray/tests/conftest.py:359,440).

The env vars are set before jax is imported (worker subprocesses inherit
them and get the same mesh); jax.config pins this process too, in case a
plugin imported jax first.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.virtual_mesh import set_virtual_cpu_env

set_virtual_cpu_env(8)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/soak tests, excluded from the tier-1 run",
    )


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    worker = ray_tpu.init(num_cpus=4, log_level="WARNING")
    yield worker
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A bare Cluster; tests add nodes and call ray_tpu.init(address=...)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        initialize_head=True, head_node_args={"num_cpus": 2, "resources": {"head": 1.0}}
    )
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture
def ray_start_small_store():
    import ray_tpu

    worker = ray_tpu.init(
        num_cpus=2, object_store_memory=64 * 1024 * 1024, log_level="WARNING"
    )
    yield worker
    ray_tpu.shutdown()


@pytest.fixture
def built_for_tpu(monkeypatch):
    """``built_for_tpu(True)``: what this test traces from here on takes the
    TPU's kernels, as on the chip (``False``: XLA's, as on the CPU). It answers
    for ``ops/backend.on_tpu``, the one place the package asks.
    ``dot_product_attention`` is a jit of its own whose cache does not key on
    the answer, so what was traced under another answer is dropped, before
    and after."""
    import jax

    from ray_tpu.ops import backend

    def answer(on: bool) -> None:
        jax.clear_caches()
        monkeypatch.setattr(backend, "on_tpu", lambda: on)

    yield answer
    jax.clear_caches()
