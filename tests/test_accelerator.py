"""Chip detection without jax, the compile-cache helper, and what a TPU
lease puts into its worker's environment."""

import os
import subprocess
import sys

import pytest

from ray_tpu._private import accelerator, node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_host(tmp_path, monkeypatch, *, pci=(), dev=()):
    """A /dev and a PCI sysfs tree holding only what the test names."""
    dev_root, pci_root = tmp_path / "dev", tmp_path / "pci"
    dev_root.mkdir()
    pci_root.mkdir()
    for i, (vendor, device) in enumerate(pci):
        d = pci_root / f"0000:00:{i:02x}.0"
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    for name in dev:
        path = dev_root / name
        path.parent.mkdir(exist_ok=True)
        path.touch()
    monkeypatch.setattr(accelerator, "_DEV_ROOT", str(dev_root))
    monkeypatch.setattr(accelerator, "_PCI_ROOT", str(pci_root))
    monkeypatch.delenv("RAYTPU_TPU_TOPOLOGY", raising=False)


V5E = ("0x1ae0", "0x0063")


@pytest.mark.parametrize(
    "pci,dev,want",
    [
        ((), (), {}),                                             # no chip
        ((("0x1ae0", "0x0042"),), ("vfio/0", "vfio/vfio"), {}),   # a Google NIC is no TPU
        # one chip of a four-chip host handed to this machine: sysfs shows
        # the host's four, the device nodes show ours
        ((V5E,) * 4, ("vfio/3", "vfio/vfio"), {"TPU": 1.0}),
        ((V5E,) * 4, ("vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"), {"TPU": 4.0}),
        ((("0x1ae0", "0x005e"),) * 4, ("accel0", "accel1", "accel2", "accel3"), {"TPU": 4.0}),
    ],
    ids=["none", "nic-only", "one-of-four-vfio", "four-vfio", "four-accel"],
)
def test_detect_from_device_nodes(tmp_path, monkeypatch, pci, dev, want):
    _fake_host(tmp_path, monkeypatch, pci=pci, dev=dev)
    assert node._detect_tpu_resources() == want


@pytest.mark.parametrize(
    "topology,want", [("v5e", 1.0), ("v5e-8", 8.0), ("v4-weird", 1.0)]
)
def test_topology_variable_overrides_the_host(tmp_path, monkeypatch, topology, want):
    _fake_host(tmp_path, monkeypatch)  # the host shows no chip at all
    monkeypatch.setenv("RAYTPU_TPU_TOPOLOGY", topology)
    assert node._detect_tpu_resources() == {"TPU": want}


def _python(code, **env):
    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", code], env={**base, "PYTHONPATH": REPO, **env},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_detection_never_imports_jax():
    assert _python(
        "import sys\n"
        "from ray_tpu._private import node\n"
        "node._detect_tpu_resources()\n"
        "print('jax' in sys.modules)",
        RAYTPU_TPU_TOPOLOGY="",
    ) == "False"


def test_compile_cache_dir_is_fixed_unless_the_variable_names_one(tmp_path):
    probe = "from ray_tpu._private import accelerator\nprint(accelerator.compile_cache_dir())"
    first, second = _python(probe), _python(probe)  # two processes, one path
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert _python(probe, JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == str(tmp_path)


@pytest.mark.parametrize("from_env", [True, False], ids=["variable-set", "variable-unset"])
def test_enable_compile_cache_sets_a_path_only_without_the_variable(
    tmp_path, monkeypatch, from_env
):
    import jax

    updates, listeners = [], []
    monkeypatch.setattr(accelerator, "_stats", None)
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(jax.monitoring, "register_event_listener", listeners.append)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert accelerator.compile_cache_stats() is None
    stats = accelerator.enable_compile_cache()
    assert accelerator.enable_compile_cache() is stats and len(listeners) == 1
    # jax reads the variable itself at import; code sets a path only without it
    assert updates == (
        [] if from_env
        else [("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]
    )
    for event in ("compile_requests_use_cache", "cache_hits", "cache_misses", "cache_hits"):
        listeners[0](f"/jax/compilation_cache/{event}")
    assert accelerator.compile_cache_stats() == {
        "dir": stats.dir, "requests": 1, "hits": 2, "writes": 1,
    }


def test_lease_pins_the_worker_platform(tmp_path, monkeypatch):
    """A TPU lease's worker may only come up on the TPU (a runtime that
    fails to initialize is then an error, not a CPU run) and inherits the
    driver's compile-cache variable; a CPU worker never takes the chip."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, resources={"TPU": 1.0}, log_level="WARNING")
    try:

        @ray_tpu.remote
        class Env:
            def get(self, name):
                return os.environ.get(name)

        # the CPU worker first: it starts the process-wide fork-server
        # template, which must not inherit this test's variable
        on_cpu = Env.options(num_cpus=0).remote()
        assert ray_tpu.get(on_cpu.get.remote("JAX_PLATFORMS"), timeout=120) == "cpu"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        on_chip = Env.options(num_tpus=1, num_cpus=0).remote()
        assert ray_tpu.get(on_chip.get.remote("JAX_PLATFORMS"), timeout=120) == "tpu"
        assert ray_tpu.get(
            on_chip.get.remote("JAX_COMPILATION_CACHE_DIR"), timeout=120
        ) == str(tmp_path)
    finally:
        ray_tpu.shutdown()
