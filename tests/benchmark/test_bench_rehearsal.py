"""Both flows end to end at a tiny size on CPU workers, through ``JaxTrainer``
and ``serve.run``, with the chip check stubbed: what breaks here would
otherwise be found on the chip. A CPU run has no device plane and no device
memory, so the contract check must name exactly those as missing."""

import pytest

import bench_helpers
from benchmark import chip, contract, run as run_mod, yardstick

NO_DEVICE = {"device.memory_peak_bytes is missing"}
NO_TRACE = NO_DEVICE | {"device.busy_s is missing", "device.window_s is missing"}


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")                  # the chip check, stubbed
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    return root


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
def test_train_flow(root, traced):
    line, cell, run = run_mod.run_cell(root, "tiny-train-cell", 2**31 + 7, 1.5, traced)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == run["steps"] > 3
    assert run["window_s"] >= 1.5 and run["setup_s"] > 0
    assert len(run["report_s"]) == run["steps"]
    assert run["tokens_per_step"] == 128
    problems = set(contract.violations(line, cell.metrics(traced), traced))
    if traced:
        assert set(line["metrics"]) == {"trainer.report_ms", "train.mfu_causal"}
        assert problems == NO_TRACE | {"metric 'device.idle_share.train' is missing"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert line["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
            run["steps"] * 128 / run["window_s"]
        )
        assert problems == NO_DEVICE


def test_four_chip_train_flow_on_virtual_devices(root):
    """One worker, four devices, fsdp2 x tp2: the mesh and the sharding rules
    of the four-chip cell (pytest's workers have eight virtual CPU devices)."""
    line, cell, run = run_mod.run_cell(root, "tiny-train4-cell", 11, 1.0, False)
    assert cell.chips == 4 and run["chips"] == 4 and line["correct"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    wanted = {m["name"] for m in cell.per_layer}
    assert "train.collective_exposed_share" in wanted


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
def test_serve_flow(root, traced):
    line, cell, run = run_mod.run_cell(root, "tiny-serve-cell", 5, 1.5, traced)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 12
    assert all(r["ok"] and r["done"] >= r["sent"] >= r["due"] >= 0 for r in run["records"])
    assert run["counters"]["steps"] > 0 and run["counters"]["prefix_hits"] == 0
    problems = set(contract.violations(line, cell.metrics(traced), traced))
    if traced:
        # the engine's own thread started and stopped the profiler around whole steps
        assert run["trace"]["engine"]["steps"] > 0 and run["trace"]["engine"]["in_step_s"] > 0
        assert set(line["metrics"]) == {
            "loadgen.late_p95_ms", "engine.step_ms", "engine.tokens_per_step",
            "ttft_p95_s", "tpot_p95_s",
        }
        assert problems == NO_TRACE | {"metric 'device.idle_share.serve' is missing"}
    else:
        assert set(line["metrics"]) == {"request_latency_mean_s", "setup_s"}
        assert problems == NO_DEVICE


def test_a_parent_that_holds_a_backend_is_refused(monkeypatch):
    import jax

    jax.devices()                                   # pytest's process has long used jax
    with pytest.raises(chip.NoChip, match="initialized a jax backend"):
        chip.parent_holds_no_backend()
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    chip.parent_holds_no_backend()
    with pytest.raises(chip.NoChip, match="not 'cpu'"):
        chip.check_device({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 1, "x")
