"""Test fixtures.

JAX runs on a virtual 8-device CPU mesh, so multi-chip sharding logic is
testable anywhere and no test touches a chip; the runtime fixtures mirror
the reference's ray_start_regular / ray_start_cluster conftest fixtures
(reference: python/ray/tests/conftest.py:359,440).

The env vars are set before jax is imported (worker subprocesses inherit
them and get the same mesh); jax.config pins this process too, in case a
plugin imported jax first.

How a CPU test compiles is decided here and nowhere else: XLA's CPU compile was
two fifths of a run's case-seconds (PR 60; ``ROADMAP.md`` D8 has the series).
LLVM builds what the CPU runs without its optimisation passes: the programs are
tiny and run a few times, so building them well costs more than running them
badly. The flag is the test run's (``_private/virtual_mesh.py`` is the program's
own driver's too), and the TPU compiler does not read it: what
``tests/test_chip_compile_*.py`` ask of a described v5e is the chip's answer with
it or without. And a run keeps what took a tenth of a second or more to compile
(an engine's programs, not the thousands of one-operation ones, whose entries
cost more to write than they save) in one directory of its own, so a tiny twin
that six processes build is compiled once. pytest-xdist's controller imports
this file before it starts its workers: it makes the directory, the workers and
every raylet and worker process a test starts inherit the variable, and the
controller removes it at the end. New each run: no entry outlives the tree that
wrote it. With the variable set already, the run uses that directory and
leaves it.
"""

import os
import shutil
import signal
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.virtual_mesh import set_virtual_cpu_env

set_virtual_cpu_env(8)
if "xla_backend_optimization_level" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

_run_cache = None   # the directory, in the one process that made it
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _run_cache = tempfile.mkdtemp(prefix="raytpu_tests_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")  # jax's own: 1.0

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/soak tests, excluded from the tier-1 run",
    )
    config.addinivalue_line(
        "markers", "limit(seconds): the test fails where it runs longer (``_a_tests_own_limit``)")


def pytest_unconfigure(config):
    if _run_cache is not None:
        shutil.rmtree(_run_cache, ignore_errors=True)


@pytest.fixture(autouse=True)
def _system_config_ends_with_the_test():
    """``ray_tpu.init(_system_config=...)`` and ``GlobalConfig.initialize`` outlive
    ``shutdown()``, and a pytest-xdist worker runs file after file in one process:
    without this, what one test sets (a worker idle timeout of 1 s) is what the
    next file's cluster runs under (its workers reaped before they report)."""
    from ray_tpu._private.config import GlobalConfig

    saved = dict(GlobalConfig._values)
    yield
    with GlobalConfig._lock:
        GlobalConfig._values = saved


@pytest.fixture(autouse=True)
def _a_tests_own_limit(request):
    """``@pytest.mark.limit(seconds)``: a time limit of the test's own, so that one
    that hangs fails by itself and does not ride the whole run to its cut (D8)."""
    marker = request.node.get_closest_marker("limit")
    if marker is None or not hasattr(signal, "setitimer"):
        yield
        return

    def late(signum, frame):
        raise TimeoutError(f"{request.node.nodeid} ran past its {marker.args[0]} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, marker.args[0])
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


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
