"""The last line's contract, held as code: good lines pass, and each way the
driver could not read a line is named before anything is printed."""

import copy
import io
import json

import pytest

from benchmark import contract

E2E = [
    {"name": "ttft_p95_s", "unit": "s"},
    {"name": "setup_s", "unit": "s"},
]
LAYER = [
    {"name": "engine.step_ms", "unit": "ms"},
    {"name": "device.idle_share.serve", "unit": "%"},
]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 13958643712}


def untraced():
    return contract.build(
        correct=True, attempted=20, failed=0,
        values={"ttft_p95_s": 3.2, "setup_s": 61.5, "engine.step_ms": 400.0},
        wanted=E2E, device=DEVICE,
    )


def traced(**device):
    return contract.build(
        correct=True, attempted=20, failed=1,
        values={"engine.step_ms": 412.5, "device.idle_share.serve": 93.1, "setup_s": 60.0},
        wanted=LAYER, device={**DEVICE, "busy_s": 0.41, "window_s": 6.02, **device},
        breakdown={
            "device_ops": [["fusion.12", 0.2], ["while.3", 0.1]],
            "idle_gaps": [["bench.engine_step:np.asarray(jax.Array)", 3.9]],
        },
    )


def test_good_lines_pass_and_hold_only_this_runs_metrics():
    line = untraced()
    assert contract.violations(line, E2E, traced=False, chips=1) == []
    assert set(line["metrics"]) == {"ttft_p95_s", "setup_s"}      # not engine.step_ms
    line = traced()
    assert contract.violations(line, LAYER, traced=True, chips=1) == []
    assert set(line["metrics"]) == {"engine.step_ms", "device.idle_share.serve"}
    assert line["breakdown"]["device_ops"][0] == ["fusion.12", 0.2]


def _without(line, *path):
    line = copy.deepcopy(line)
    node = line
    for k in path[:-1]:
        node = node[k]
    del node[path[-1]]
    return line


@pytest.mark.parametrize("line, wanted, is_traced, says", [
    (traced(busy_s=0.0), LAYER, True, "busy_s 0.0 is not above 0"),
    (traced(busy_s=7.0), LAYER, True, "at most window_s"),
    (_without(traced(), "metrics", "engine.step_ms"), LAYER, True,
     "metric 'engine.step_ms' is missing"),
    (_without(traced(), "device", "memory_peak_bytes"), LAYER, True,
     "device.memory_peak_bytes is missing"),
    (_without(traced(), "device", "busy_s"), LAYER, True, "device.busy_s is missing"),
    (_without(untraced(), "device", "kind"), E2E, False, "device.kind is missing"),
    (_without(untraced(), "failed"), E2E, False, "key 'failed' is missing"),
    ({**untraced(), "metrics": {**untraced()["metrics"],
                                "ttft_p95_s": {"value": float("nan"), "unit": "s"}}},
     E2E, False, "no finite value"),
    ({**untraced(), "metrics": {**untraced()["metrics"],
                                "ttft_p95_s": {"value": 3.2, "unit": "ms"}}},
     E2E, False, "has unit 'ms'"),
    ({**untraced(), "failed": 21}, E2E, False, "failed 21 > attempted 20"),
    ({**untraced(), "device": {**DEVICE, "count": 4}}, E2E, False, "the cell asks for 1"),
    ({**traced(), "breakdown": {"device_ops": [["x", 1.0]] * 11, "idle_gaps": []}},
     LAYER, True, "breakdown.device_ops"),
    ([1, 2], E2E, False, "not an object"),
])
def test_each_fault_is_named(line, wanted, is_traced, says):
    problems = contract.violations(line, wanted, traced=is_traced, chips=1)
    assert any(says in p for p in problems), problems


def test_a_reader_that_found_nothing_leaves_its_metric_out_and_that_is_a_violation():
    line = contract.build(
        correct=True, attempted=1, failed=0,
        values={"ttft_p95_s": None, "setup_s": 10.0}, wanted=E2E, device=DEVICE,
    )
    assert "ttft_p95_s" not in line["metrics"]
    assert contract.violations(line, E2E, traced=False) == ["metric 'ttft_p95_s' is missing"]


def test_emit_prints_one_json_line_or_nothing():
    out = io.StringIO()
    contract.emit(untraced(), E2E, traced=False, chips=1, out=out)
    assert out.getvalue().count("\n") == 1
    assert json.loads(out.getvalue())["device"]["kind"] == "TPU v5 lite"
    out = io.StringIO()
    with pytest.raises(contract.ContractViolation) as err:
        contract.emit(traced(busy_s=0), LAYER, traced=True, chips=1, out=out)
    assert out.getvalue() == "" and err.value.problems
