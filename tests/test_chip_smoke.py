"""chip_smoke.py's flow, rehearsed at a tiny size on the CPU: what breaks
here would otherwise be found on the chip, where finding it costs chip time.
The chip run itself (real widths, the TPU, the flash kernel in the compiled
step) is `python chip_smoke.py` through the chip tool."""

import dataclasses
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from ray_tpu.models.gpt import gpt_nano

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_plan():
    cfg = gpt_nano()
    return chip_smoke.Plan(
        train_cfg=cfg, serve_cfg=cfg, cuts="tiny rehearsal", batch=(2, 64), steps=3,
        engine=dict(
            num_blocks=64, block_size=16, prefill_chunk=32, prefill_lanes=2,
            lane_buckets=(1, 4), prefill_token_buckets=(8, 32), cache_buckets=(128,),
        ),
        prompt_len=40, other_prompt_lens=(20, 50, 90), max_new_tokens=4,
        platform="cpu", min_flash_kernels=0,
    )


@pytest.mark.slow  # ~18 s of a tier-1 budget that is nearly spent; the driver
# runs this flow on the chip after every PR, the four-chip flow below never
def test_one_chip_flow_on_cpu_workers(monkeypatch):
    # pytest's process has long used jax; on the CPU that costs a child nothing
    jax.devices()
    with pytest.raises(SystemExit, match="parent initialized a jax backend"):
        chip_smoke.assert_parent_off_jax()
    monkeypatch.setattr(chip_smoke, "assert_parent_off_jax", lambda: None)
    device = chip_smoke.run_one_chip(_tiny_plan(), seed=0)
    assert device["platform"] == "cpu" and device["pid"] != os.getpid()


def test_four_chip_flow_on_virtual_devices(monkeypatch):
    from ray_tpu._private import accelerator

    # the compile cache is for the process that holds a chip, not for pytest
    monkeypatch.setattr(accelerator, "enable_compile_cache", lambda: None)
    device = chip_smoke.run_four_chips(_tiny_plan(), seed=0)
    assert device["count"] == 4 and len(jax.devices()) >= 4


def test_a_kernel_that_did_not_run_is_a_failure():
    plan = _tiny_plan()
    history = [{"loss": 2.0, "step_s": 0.1}, {"loss": 1.0, "step_s": 0.1}, {"loss": 0.5, "step_s": 0.1}] + [{
        "summary": True, "init_s": 0.0, "compile_s": 0.0, "flash_kernels": 0,
        "param_bytes_per_device": {}, "peak_bytes_per_device": {},
    }]
    assert chip_smoke.check_training(plan, history, "x") == [2.0, 1.0, 0.5]
    with pytest.raises(SystemExit, match="flash"):
        chip_smoke.check_training(
            dataclasses.replace(plan, min_flash_kernels=3), history, "x"
        )


def test_no_accelerator_no_result():
    """Off-chip the script exits non-zero, names the missing device, and
    never prints a result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "RAYTPU_TPU_TOPOLOGY": ""},
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode != 0
    assert "no TPU chip found" in out.stderr
    assert '"ok"' not in out.stdout
