"""The train cells' kernel check: every ``tpu_custom_call`` of the compiled step
is counted under its kernel's name, and a configuration's ``job.min_kernels``
states, a name, what one forward and one backward pass require of that kernel
with nothing replayed. Fed small compiled texts whose lines are shaped like the
compiler's; the floors are the real files'."""

import json
import os

import pytest

import bench_helpers
from benchmark import chip, run as run_mod, yardstick
from benchmark.traffic import train_fixed_batch as traffic

CONFIGS = os.path.join(bench_helpers.REPO, "benchmark", "configs")
FLASH = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


def floors(config: str) -> dict:
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        return json.load(f)["job"]["min_kernels"]


def compiled_text(**kernels: int) -> str:
    """A compiled step's text in small: ``kernels`` Pallas calls a name, numbered
    as the compiler numbers the copies of an instruction (the first bare, one
    the ``ROOT`` of its computation), among instructions that are none."""
    lines = ["HloModule jit_step, is_scheduled=true", "", "%body.1 (p: bf16[8,128]) -> bf16[8,128] {"]
    n = 0
    for name, count in kernels.items():
        for i in range(count):
            n += 1
            lines.append(
                f"  {'ROOT ' if n == 2 else ''}%{name}{'.%d' % (n + 6) if i else ''} = "
                f"(bf16[128,4096,64]{{2,1,0:T(8,128)(2,1)}}, f32[128,4096,1]{{2,1,0}}) "
                f"custom-call(%bitcast.{n}, %copy-done.{n}), "
                f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}, '
                f'metadata={{op_name="jit(step)/jvp(train.forward)/jit({name})/pallas_call"}}, '
                f'backend_config={{"custom_call_config":{{"body":"TUzvUgFNTElS"}}}}')
    lines += [
        '  %fusion.7 = bf16[8,128]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.7',
        '  %custom-call.3 = f32[8]{0} custom-call(%p), custom_call_target="Sharding"',
        '  %sort.4 = s32[65536]{0} custom-call(%p), custom_call_target="TopK", '
        'metadata={op_name="jit(step)/tpu_custom_call_is_no_target_here"}',
        "}",
    ]
    return "\n".join(lines)


def test_a_kernel_is_counted_under_its_instructions_name_less_the_copys_number():
    text = compiled_text(flash_fwd=2, gmm=3, tgmm=1)
    assert traffic.kernels_of(text) == {"flash_fwd": 2, "gmm": 3, "tgmm": 1}
    assert text.count("tpu_custom_call") == 7           # what a total would read: one too many
    assert traffic.kernels_of("HloModule jit_step\n%fusion.1 = f32[] fusion()") == {}


CASES = {
    # LFM2's program as it stands: 3 flash + 4 expert layers x (6 gmm, 2 of them remat's replay, + 2 tgmm)
    "lfm2-parents-35": (
        "lfm2-24b-a2b-train-ep2", dict(FLASH, gmm=24, tgmm=8), []),
    # every required kernel once and nothing replayed (PR 48's step): 3 + 16 + 8
    "lfm2-nothing-replayed-27": (
        "lfm2-24b-a2b-train-ep2", dict(FLASH, gmm=16, tgmm=8), []),
    # attention fell back to XLA: 32 grouped matmuls pass a total of 27, and are not correct
    "lfm2-no-flash-among-32": (
        "lfm2-24b-a2b-train-ep2", dict(gmm=24, tgmm=8),
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    "lfm2-forward-kernel-alone-missing": (
        "lfm2-24b-a2b-train-ep2", dict(flash_bwd_dq=1, flash_bwd_dkv=1, gmm=24, tgmm=8),
        ["flash_fwd"]),
    # the expert layer's weight gradients in XLA
    "lfm2-no-tgmm": ("lfm2-24b-a2b-train-ep2", dict(FLASH, gmm=24), ["tgmm"]),
    # the expert layer without the grouped matmul
    "lfm2-no-gmm": ("lfm2-24b-a2b-train-ep2", dict(FLASH, tgmm=8), ["gmm"]),
    # a layer's rows' gradient in XLA: 8 gmm short of what the backward requires
    "lfm2-backward-gmm-missing": (
        "lfm2-24b-a2b-train-ep2", dict(FLASH, gmm=8, tgmm=8), ["gmm"]),
    "gptj-1chip-3": ("gptj-6b-train-1chip", dict(FLASH), []),
    "gptj-4chip-3": ("gptj-6b-train-4chip", dict(FLASH), []),
    # a remat that replays the forward kernel is the program's choice: still correct
    "gptj-1chip-forward-replayed": ("gptj-6b-train-1chip", dict(FLASH, flash_fwd=2), []),
    "gptj-1chip-xla-attention": (
        "gptj-6b-train-1chip", {}, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    "gptj-4chip-no-dkv": (
        "gptj-6b-train-4chip", dict(flash_fwd=1, flash_bwd_dq=1), ["flash_bwd_dkv"]),
}


@pytest.mark.parametrize("config, kernels, missing", CASES.values(), ids=list(CASES))
def test_a_step_is_held_to_the_kernels_its_mathematics_needs(config, kernels, missing):
    problems = traffic.missing_kernels(
        traffic.kernels_of(compiled_text(**kernels)), floors(config))
    assert len(problems) == len(missing)
    for name, problem in zip(missing, problems):
        # the failure names the kernel that is missing, its count and its floor
        assert problem.startswith(f"{kernels.get(name, 0)} {name} in the compiled step")
        assert f"want >= {floors(config)[name]}" in problem


def test_the_floors_are_what_the_passes_require_and_no_replay():
    lfm2 = floors("lfm2-24b-a2b-train-ep2")
    with open(os.path.join(CONFIGS, "lfm2-24b-a2b-train-ep2.json")) as f:
        file = json.load(f)
    expert_layers = file["num_hidden_layers"] - file["num_dense_layers"]
    attention_layers = file["layer_types"].count("full_attention")
    assert (expert_layers, attention_layers) == (4, 1)
    # forward gate_up and out, the same two transposed for the rows' gradient; two weight gradients
    assert lfm2 == dict(FLASH, gmm=4 * expert_layers, tgmm=2 * expert_layers)
    assert sum(lfm2.values()) == 27
    assert floors("gptj-6b-train-1chip") == floors("gptj-6b-train-4chip") == FLASH
    # the words beside the numbers say that remat's replay is not required
    assert "NOT required" in file["job"]["min_kernels_why"]


def test_a_file_without_floors_asks_for_nothing():
    """The tiny files run off the chip, where no kernel compiles."""
    for arch in bench_helpers.tiny_architectures():
        for cell in bench_helpers.tiny(arch)["cells"]:
            assert "min_kernels" not in cell.get("job", {})
    assert traffic.missing_kernels({}, {}) == []


def test_a_run_whose_step_lacks_a_required_kernel_is_not_correct(tmp_path, monkeypatch, capsys):
    """The whole of a run but the look for a chip, with the attention's kernel
    gone from the compiled step (as everywhere on the CPU) under a file that
    asks for it: ``correct`` comes out false and the line names the kernel."""
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    path = os.path.join(root, bench_helpers.TINY, "gptj.json")
    with open(path) as f:
        tiny = json.load(f)
    tiny["cells"][0]["job"]["min_kernels"] = FLASH
    with open(path, "w") as f:
        json.dump(tiny, f)
    bench_helpers.add_tiny_cells(root)
    line, _, run = run_mod.run_cell(root, "tiny-train-cell", 2**31 + 13, 1.0, False)
    assert not line["correct"] and not run["correct"] and run["steps"] > 2
    said = capsys.readouterr().out
    assert "NOT CORRECT: 0 flash_fwd in the compiled step" in said and "want >= 1" in said
