"""The reduction from a profiler trace to busy time, time per operation,
exposed collective time and idle gaps, on hand-made events and on a trace
recorded on four v5e chips (``recorded_trace_4chip.json``: three steps of a
two-layer model on fsdp2 x tp2, kept as ``load_xplane`` returned it)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "bench.train_step"


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.union_length([(0, 2), (1, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [
        (0, 2), (4, 8), (22, 29)
    ]
    assert tr.short_name("%fusion.12 = bf16[4,512]{1,0} fusion(%p)") == "fusion.12"


def test_self_time_of_nested_operations():
    # while.1 spans 0-100 and runs fusion.a (10-30) and all-reduce.1 (50-80)
    segs = tr.self_segments([
        ("fusion.a", 10, 20), ("while.1", 0, 100), ("all-reduce.1", 50, 30),
        ("copy.9", 120, 5),
    ])
    own = {}
    for name, a, b in segs:
        own[name] = own.get(name, 0) + b - a
    assert own == {"while.1": 50, "fusion.a": 20, "all-reduce.1": 30, "copy.9": 5}
    assert tr.union_length((a, b) for _, a, b in segs) == 105


def _planes(async_collective):
    ops = [("while.1", 100, 800), ("fusion.a", 100, 300), ("all-gather-done.2", 600, 100)]
    dev = {"XLA Ops": ops}
    if async_collective:
        # in flight from 350 to 700: hidden behind nothing from 400 to 600
        dev["Async XLA Ops"] = [("all-gather-start.2", 350, 350)]
    return {
        "/device:TPU:0": dev,
        "/host:CPU": {
            "python": [(STEP, 0, 1000), ("np.asarray(jax.Array)", 900, 100)],
            "other": [("Transfer", 0, 2000)],
        },
    }


def test_busy_exposed_collectives_and_gaps_on_hand_made_events():
    r = tr.reduce(_planes(async_collective=False), STEP)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(800e-9)
    assert r["collective_exposed_s"] == pytest.approx(100e-9)       # the -done alone
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"while.1": 400e-9, "fusion.a": 300e-9, "all-gather-done.2": 100e-9}
    )
    # the gap at 0-100 has only the step's span over it; 900-1000 the fetch
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {f"{STEP}:python": 100e-9, f"{STEP}:np.asarray(jax.Array)": 100e-9}
    )
    # while.1's own time (400-600 here) counts as another operation running
    r = tr.reduce(_planes(async_collective=True), STEP)
    assert r["per_device"][0]["collective_s"] == pytest.approx(350e-9)
    assert r["collective_exposed_s"] == pytest.approx(100e-9)


def test_no_annotation_or_no_device_plane_reduces_to_nothing():
    planes = _planes(False)
    assert tr.reduce(planes, "bench.engine_step") is None
    assert tr.reduce({"/host:CPU": planes["/host:CPU"]}, STEP) is None


def test_recorded_four_chip_trace():
    with open(os.path.join(HERE, "recorded_trace_4chip.json")) as f:
        planes = json.load(f)
    r = tr.reduce(planes, STEP)
    assert r["devices"] == 4 and len(r["per_device"]) == 4
    assert 0 < r["busy_s"] <= r["busiest_busy_s"] <= r["window_s"]
    # three steps of ~1 ms of device work each inside a ~19 ms window of host work
    assert r["window_s"] == pytest.approx(0.0193, rel=0.01)
    assert r["busy_s"] == pytest.approx(0.00301, rel=0.01)
    for d in r["per_device"]:
        assert 0 < d["collective_exposed_s"] <= d["collective_s"] <= d["busy_s"]
    assert r["collective_exposed_s"] == pytest.approx(0.00131, rel=0.01)
    names = [n for n, _ in r["device_ops"]]
    assert len(names) == 10 and any("all-reduce" in n for n in names)
    assert any("dot_product_attention" in n for n in names)      # the flash kernel
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert max(gaps, key=gaps.get) == f"{STEP}:np.asarray(jax.Array)"
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busiest_busy_s"], rel=1e-6)


def test_load_xplane_reads_a_trace_written_here(tmp_path):
    """On the CPU there is no device plane; the host's annotations are read."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(STEP):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    planes = tr.load_xplane(path)
    lo, hi = tr.annotation_window(planes, STEP)
    assert hi > lo and not any(tr.DEVICE_PLANE.match(p) for p in planes)
