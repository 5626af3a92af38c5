"""The reduction from a profiler trace to busy time, time per operation, per
kernel and per scope, exposed collective time and idle gaps, on hand-made
events and on traces recorded on v5e chips: ``recorded_trace_4chip.json``
(three steps of a two-layer model on fsdp2 x tp2, kept as ``load_xplane``
returned it) and ``recorded_trace_kernels.json`` (one step of the one-chip
train cell, with the scope of each instruction as ``load_scopes`` returned
them; ``benchmark/tools/record_trace.py`` wrote it)."""

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


def test_kernels_and_scopes_by_name():
    assert tr.kernel_of("flash_fwd.18") == "flash_fwd" and tr.kernel_of("fusion") == "fusion"
    assert tr.kernel_of("all-reduce.138") == "all-reduce"
    assert tr.kernel_of("dynamic-slice_dynamic-update-slice_fusion.4") == (
        "dynamic-slice_dynamic-update-slice_fusion"
    )
    # the innermost named scope, as the op_name holds it; flax's own paths are none
    assert tr.scope_of("jit(extend)/while/body/closed_call/extend.mlp/mul") == "extend.mlp"
    assert tr.scope_of(
        "jit(step)/transpose(jvp(train.forward))/GPT/blocks/while/body/closed_call/"
        "layers.<lambda>/layers/attn/add_any"
    ) == "transpose(jvp(train.forward))"
    assert tr.scope_of("jit(step)/jvp(train.loss)/while/body/mul") == "jvp(train.loss)"
    assert tr.scope_of("jit(gather)/paging.gather/while/body/dynamic_slice:") == "paging.gather"
    assert tr.scope_of("jit(step)/train.forward/moe.dispatch/sort") == "moe.dispatch"
    for none in ("", "checkpoint/rematted_computation/reduce_max", "jit(f)/GPT/blocks/attn/q"):
        assert tr.scope_of(none) == tr.NO_SCOPE


def test_time_per_kernel_and_per_scope_on_hand_made_events():
    planes = _planes(async_collective=False)
    planes["/device:TPU:0"]["XLA Ops"] += [
        ("fusion.7", 450, 50), ("flash_fwd.3", 500, 60), ("fusion.8", 560, 20),
        ("fusion.7", 910, 40),             # another program's fusion.7, after the step's
    ]
    planes["/device:TPU:0"]["XLA Modules"] = [
        ("jit_step(123)", 100, 800), ("jit_gather(77)", 905, 50),
    ]
    scopes = {
        "jit_step": {
            "fusion.a": "jit(step)/jvp(train.forward)/GPT/mul",
            "fusion.7": "jit(step)/train.optimizer/add",
            "flash_fwd.3": "jit(step)/jvp(train.forward)/GPT/attn/pallas_call",
            "while.1": "jit(step)/while",
        },
        "jit_gather": {"fusion.7": "jit(gather)/paging.gather/while/body/dynamic_slice"},
    }
    r = tr.reduce(planes, STEP, scopes=scopes)
    # every name, not the top ten; the same self time as device_ops, summed
    assert dict(map(tuple, r["ops_by_kernel"])) == pytest.approx({
        "while": 270e-9, "fusion.a": 300e-9, "fusion": 110e-9, "flash_fwd": 60e-9,
        "all-gather-done": 100e-9,
    })
    # an instruction's scope is its own module's: the two fusion.7 are told apart
    assert dict(map(tuple, r["ops_by_scope"])) == pytest.approx({
        "jvp(train.forward)": 360e-9, "train.optimizer": 50e-9, "paging.gather": 40e-9,
        tr.NO_SCOPE: 390e-9,
    })
    for key in ("ops_by_kernel", "ops_by_scope"):
        times = [t for _, t in r[key]]
        assert times == sorted(times, reverse=True) and sum(times) == pytest.approx(r["busy_s"])
    # a module whose proto the trace lacks falls back on tf_op by name alone (module "")
    fallback = tr.reduce(planes, STEP, scopes={"": {"fusion.7": "jit(x)/demo.scope/add"}})
    assert dict(map(tuple, fallback["ops_by_scope"])) == pytest.approx({
        "demo.scope": 90e-9, tr.NO_SCOPE: 750e-9,
    })
    # without scopes every operation is unscoped, and nothing else changes
    bare = tr.reduce(planes, STEP)
    assert bare["ops_by_scope"] == [[tr.NO_SCOPE, pytest.approx(840e-9)]]
    assert bare["device_ops"] == r["device_ops"] and bare["ops_by_kernel"] == r["ops_by_kernel"]
    assert tr.module_of("jit_step(8392355915514388644)") == "jit_step"


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


def test_recorded_one_chip_trace_names_the_flash_kernels_and_the_scopes():
    with open(os.path.join(HERE, "recorded_trace_kernels.json")) as f:
        recorded = json.load(f)
    r = tr.reduce(recorded["planes"], STEP, scopes=recorded["scopes"])
    assert r["devices"] == 1 and 0.9 < r["busy_s"] / r["window_s"] <= 1
    kernels = dict(map(tuple, r["ops_by_kernel"]))
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= set(kernels)
    assert all(kernels[k] > 0 for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert len(kernels) > 10 and not any(tr.kernel_of(k) != k for k in kernels)
    scopes = dict(map(tuple, r["ops_by_scope"]))
    assert {"transpose(jvp(train.forward))", "jvp(train.forward)", "train.optimizer"} <= set(scopes)
    assert any("train.loss" in s for s in scopes)
    assert sum(scopes.values()) == pytest.approx(sum(kernels.values())) == pytest.approx(r["busy_s"])
    # most of the step runs under a named scope
    assert scopes.get(tr.NO_SCOPE, 0.0) < 0.5 * r["busy_s"]
    assert r["device_ops"] == tr.reduce(recorded["planes"], STEP)["device_ops"]


def test_load_xplane_and_load_scopes_read_a_trace_written_here(tmp_path):
    """On the CPU there is no device plane: the host's annotations are read,
    no instruction has a scope, and the wire reader finds the named scope in
    the HLO proto the trace carries."""
    import glob

    import jax
    import jax.numpy as jnp

    from benchmark import xplane_wire

    jax.profiler.start_trace(str(tmp_path))
    def scoped(x):
        with jax.named_scope("demo.scope"):
            return (x @ x).sum()

    with jax.profiler.TraceAnnotation(STEP):
        jax.jit(scoped)(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    planes = tr.load_xplane(path)
    lo, hi = tr.annotation_window(planes, STEP)
    assert hi > lo and not any(tr.DEVICE_PLANE.match(p) for p in planes)
    assert tr.HOST_PLANE in xplane_wire.planes_metadata(path)
    scopes = tr.load_scopes(path)
    assert "" not in scopes                       # no device plane, so no tf_op
    assert "demo.scope" in {tr.scope_of(op) for op in scopes["jit_scoped"].values()}
