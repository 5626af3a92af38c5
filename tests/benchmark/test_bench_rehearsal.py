"""Both flows end to end at a tiny size on CPU workers, through ``JaxTrainer``
and ``serve.run``, with the chip check stubbed: what breaks here would
otherwise be found on the chip. A CPU run has no device plane and no device
memory, so the contract check must name exactly those as missing."""

import pytest

import bench_helpers
from benchmark import chip, contract, manifest, run as run_mod, yardstick

NO_DEVICE = {"device.memory_peak_bytes is missing"}
NO_TRACE = NO_DEVICE | {"device.busy_s is missing", "device.window_s is missing"}


def per_architecture(kind, chips=1):
    """One case for each architecture with a tiny file: its twin of that kind."""
    return pytest.mark.parametrize(
        "twin", [pytest.param(cell, id=arch) for arch, cell in bench_helpers.twins(kind, chips)]
    )


trace_or_not = pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")                  # the chip check, stubbed
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    return root


def check_line(root, line, cell, traced):
    """The line holds the metrics the manifest lists for the real cell this
    twin mirrors, but for what only a device trace gives; the contract check
    names exactly those, and the device's own numbers, as missing."""
    mirrors = next(
        c["mirrors"] for arch in bench_helpers.tiny_architectures(root)
        for c in bench_helpers.tiny(arch, root)["cells"] if c["name"] == cell.name
    )
    wanted = manifest.Manifest(root).cell(mirrors).metrics(traced)
    assert {m["name"] for m in cell.metrics(traced)} == {m["name"] for m in wanted}
    from_trace = {m["name"] for m in wanted if m["source"] == "device_trace"}
    assert set(line["metrics"]) == {m["name"] for m in wanted} - from_trace
    assert set(contract.violations(line, cell.metrics(traced), traced)) == (
        NO_TRACE | {f"metric {n!r} is missing" for n in from_trace} if traced else NO_DEVICE
    )


@per_architecture("train")
@trace_or_not
def test_train_flow(root, twin, traced):
    line, cell, run = run_mod.run_cell(root, twin, 2**31 + 7, 1.5, traced)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == run["steps"] > 3
    assert run["window_s"] >= 1.5 and run["setup_s"] > 0
    assert len(run["report_s"]) == run["steps"] == len(run["step_metrics"])
    batch = cell.config["job"]["batch"]
    assert run["tokens_per_step"] == batch[0] * batch[1]
    # every scalar the step reports reaches the readers, step by step
    assert all({"loss", "grad_norm", "step_s"} <= set(m) for m in run["step_metrics"])
    # the mix's steps are sent ahead of the one waited for (the traced ones apart), and
    # every step that was sent is taken, in the order it was sent, before the clock is read
    assert 1 < run["ahead"] <= cell.traffic["ahead_steps_at_most"]
    counted = [m["step"] for m in run["step_metrics"]]
    assert counted == [counted[0] + i for i in range(run["steps"])]
    check_line(root, line, cell, traced)
    if not traced:
        assert line["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
            run["steps"] * run["tokens_per_step"] / run["window_s"]
        )


@per_architecture("train", chips=4)
def test_four_chip_train_flow_on_virtual_devices(root, twin):
    """One worker, four devices, fsdp2 x tp2: the mesh and the sharding rules
    of the four-chip cell (pytest's workers have eight virtual CPU devices)."""
    line, cell, run = run_mod.run_cell(root, twin, 11, 1.0, False)
    assert cell.chips == 4 and run["chips"] == 4 and line["correct"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    wanted = {m["name"] for m in cell.per_layer}
    assert "train.collective_exposed_share" in wanted


@per_architecture("serve")
@trace_or_not
def test_serve_flow(root, twin, traced):
    line, cell, run = run_mod.run_cell(root, twin, 5, 1.5, traced)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 12
    assert all(r["ok"] and r["done"] >= r["sent"] >= r["due"] >= 0 for r in run["records"])
    assert all(0 <= r["queue_s"] <= r["ttft_s"] for r in run["records"])
    counters = run["counters"]
    assert counters["steps"] > 0 and counters["prefix_hits"] == 0
    # every number of kv_stats, the groups of numbers too, as a delta over the load
    assert counters["admitted"] == 12 + 4 and counters["queue_s"] >= 0
    assert 0 < counters["cache_tokens"] <= counters["cache_slots"]
    assert 0 < counters["lanes_used"] <= counters["lane_slots"]
    assert counters["phase_n"]["step"] == counters["steps"]
    assert counters["phase_s"]["step"] > counters["phase_s"]["fetch"] > 0
    assert counters["h2d_bytes"] > 0 and counters["d2h_bytes"] > 0
    if traced:
        # the engine's own thread started and stopped the profiler around whole steps
        assert run["trace"]["engine"]["steps"] > 0 and run["trace"]["engine"]["in_step_s"] > 0
    check_line(root, line, cell, traced)


def test_a_parent_that_holds_a_backend_is_refused(monkeypatch):
    import jax

    jax.devices()                                   # pytest's process has long used jax
    with pytest.raises(chip.NoChip, match="initialized a jax backend"):
        chip.parent_holds_no_backend()
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    chip.parent_holds_no_backend()
    with pytest.raises(chip.NoChip, match="not 'cpu'"):
        chip.check_device({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, "x")
