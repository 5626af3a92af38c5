"""What the chip's compiler says about the train path, asked without a chip:
the flash kernels, the GPT-J, LFM2 and Nemotron-3-Nano steps, the collectives a
mesh's step holds.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
described (not attached) ``v5e:2x2``: it refuses what the chip would refuse
— a kernel whose tiles do not fit, a Mosaic kernel under a mesh without a
``shard_map``, a program larger than the device's memory. Nothing runs, so
these say nothing about results or speed; that is ``chip_smoke.py``'s job.

Code that asks ``jax.devices()`` sees the CPU here and would take its XLA
path, so the tests answer for the package's one probe (``built_for_tpu``,
``tests/conftest.py``).
"""

import dataclasses
import math
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile_helpers import HBM_BYTES, _device_bytes, _gptj, v5e  # noqa: F401 — v5e is a fixture
from ray_tpu._private import accelerator
from ray_tpu.models import gpt
from ray_tpu.models.training import (
    abstract_state,
    default_optimizer,
    make_train_step,
    state_shardings,
)
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.mesh import MeshSpec


@pytest.mark.parametrize("head_dim", [256, 128])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_kernels_compile(v5e, head_dim, backward):
    qkv = jax.ShapeDtypeStruct(
        (4, 16, 2048, head_dim), jnp.bfloat16, sharding=SingleDeviceSharding(v5e[0])
    )

    def fwd(q, k, v):
        return dot_product_attention(q, k, v, causal=True, use_pallas=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = jax.jit(fn).lower(qkv, qkv, qkv).compile().as_text()
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


_SPECS = {"one-chip": (MeshSpec(), 1), "fsdp2xtp2": (MeshSpec(dp=-1, fsdp=2, tp=2), 4)}
_STEPS = {}     # compiled once a module: 12 s each


def _gptj_step(v5e, built_for_tpu, mesh_id, depth=2, batch=(2, 2048)):
    """The whole train step at GPT-J's widths, depth 2, batch 2 x 2048 unless
    told otherwise, compiled for the described chips as ``make_train_step``
    builds it (its compiler options are the mesh's): ``(mesh, compiled, text)``."""
    key = (mesh_id, depth, batch)
    if key not in _STEPS:
        built_for_tpu(True)
        spec, n_devices = _SPECS[mesh_id]
        cfg = _gptj(depth)
        mesh = spec.build(v5e[:n_devices])
        opt = default_optimizer(1e-4)
        _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct(batch, jnp.int32))
        shardings = nn.meta.unbox(state_shardings(mesh, abstract))
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            nn.meta.unbox(abstract), shardings,
        )
        tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=shd.batch_sharding(mesh))
        step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
        compiled = step.lower(state, tokens).compile()
        _STEPS[key] = mesh, compiled, compiled.as_text()
    return _STEPS[key]


@pytest.mark.parametrize("mesh_id", list(_SPECS))
def test_gptj_width_train_step_compiles(v5e, mesh_id, built_for_tpu):
    """On the mesh the flash kernel is only legal under shard_map ("Mosaic
    kernels cannot be automatically partitioned")."""
    _, compiled, text = _gptj_step(v5e, built_for_tpu, mesh_id)
    assert text.count("tpu_custom_call") >= 3
    assert _device_bytes(compiled) < HBM_BYTES


def test_one_chip_step_fits_the_chip_at_the_cells_own_size(v5e, built_for_tpu):
    """``gptj-train-1chip-fixed-batch`` as it runs: depth 6, 4 x 2048 tokens,
    bf16 parameters and moments. The layers' kept kernel outputs and the loss's
    float32 sum of the head's gradient have to fit beside 9.7 GB of state."""
    _, compiled, text = _gptj_step(v5e, built_for_tpu, "one-chip", depth=6, batch=(4, 2048))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert _device_bytes(compiled) < HBM_BYTES


_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def _cell_step(v5e, built_for_tpu, config_name):
    """A train cell's step as it runs: the configuration's file at its ``job.batch``,
    compiled for one described chip: ``(cfg, file, compiled)``."""
    import importlib
    import json

    from benchmark.manifest import published_keys

    built_for_tpu(True)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs", config_name + ".json")) as f:
        file = json.load(f)
    architecture = importlib.import_module("benchmark.models." + file["model_type"])
    cfg = architecture.program_config(published_keys(file))
    batch = tuple(file["job"]["batch"])
    mesh = MeshSpec().build(v5e[:1])
    opt = default_optimizer(file["job"]["learning_rate"])
    _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct(batch, jnp.int32))
    shardings = nn.meta.unbox(state_shardings(mesh, abstract))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        nn.meta.unbox(abstract), shardings,
    )
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=shd.batch_sharding(mesh))
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    return cfg, file, step.lower(state, tokens).compile()


def test_lfm2_moe_share_train_step_compiles_and_copies_no_expert_stack(v5e, built_for_tpu):
    """``lfm2-24b-a2b-train-1chip-fixed-batch`` as it runs: the configuration's
    file (a dense layer and one period at the published widths, 32 of 64 experts
    held, 1,375,254,912 parameters: 8.25 GB of bfloat16 weights and moments as
    arguments), 4 x 4096 tokens. It fits the chip; the three flash kernels and
    the grouped matmuls of four expert layers are in it, what the configuration's
    ``job.min_kernels`` asks for and no more (forward and both gradients, 6 a
    layer: a layer's remat keeps both results and replays neither); and no
    instruction copies, transposes or slices out a layer's stack of experts
    (604 MB), as a scan over stacked layers or a kernel that wants a whole operand
    would make it.

    The backward's passes over sorted rows are loops over the blocks that hold a
    pair, a block's gradient written where the value it is the gradient of lay: two
    loops an expert layer, which carry ``gate_up`` [65536, 3072] and ``out``
    [65536, 2048], the values the remat kept, and copy neither. They are kept as
    their bits: kept as floats each goes through a ``reduce-precision`` that XLA
    cannot alias through (9 in the text, 16,156,333,056 B). The step compiles to
    15,820,434,432 B, where the step that replayed both took 16,613,009,408."""
    from benchmark.traffic import train_fixed_batch
    from ray_tpu.models import moe

    cfg, file, compiled = _cell_step(v5e, built_for_tpu, "lfm2-24b-a2b-train-ep2")
    batch = tuple(file["job"]["batch"])
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 6 * cfg.num_params()        # weights and two moments
    assert _device_bytes(compiled) < HBM_BYTES
    assert text.count('custom_call_target="tpu_custom_call"') == 27
    assert not train_fixed_batch.missing_kernels(
        train_fixed_batch.kernels_of(text), file["job"]["min_kernels"])
    assert _device_bytes(compiled) <= 1.01 * 15_820_434_432
    assert text.count(" reduce-precision(") <= 2
    pairs = batch[0] * batch[1] * cfg.experts_per_token
    assert moe.row_block(pairs, moe.GMM_TRAIN_TILING[0]) * moe.ROW_BLOCKS == pairs == 65536
    sorted_rows = r"bf16\[65536,(1536|2048|3072)\]"
    loops = [line for line in text.splitlines() if re.search(r" while\(", line)]
    for carried in ("bf16[65536,3072]", "bf16[65536,2048]"):
        assert sum(carried in line and "train.moe.experts" in line for line in loops) >= 4, carried
    copied = re.findall(rf"= {sorted_rows}\S* copy\(", text)
    assert not copied, copied[:3]
    stack = r"(bf16|f32)\[(1,)?32,(2048,3072|1536,2048)\]"
    moved = re.findall(
        rf"= {stack}\S* (?:copy|transpose|dynamic-slice|dynamic-update-slice)\(", text)
    assert not moved, moved[:3]


def test_nemotron_h_share_train_step_fits_the_chip_with_its_kernels(v5e, built_for_tpu):
    """``nemotron-3-nano-train-1chip-fixed-batch`` as it runs: the configuration's file
    (four Mamba-2 layers, four expert layers of 16 held experts, one attention layer at
    the published widths, 986,254,848 parameters: 5.9 GB of bfloat16 weights and
    moments as arguments), 2 x 8192 tokens. It fits the chip with both grouped
    matmuls' results kept across the four expert layers and the chunked scan as its
    kernel pair (``granitemoehybrid.ssm_scan``), whose backward keeps the states
    between sub-chunks alone (268 MB a layer; plain autodiff's residuals of 64
    sub-chunks a sequence asked for 20.5 GB). The three flash kernels and the grouped
    matmuls are in it at the floors the configuration's ``job.min_kernels`` asks for
    and no more (a layer's remat replays neither kernel), and beside them the scan's
    two by name: a forward in each of the four layers' forward and replay, one backward
    each. No array a head and sub-chunk wide (``f32[64,2,64,128,128]`` and kin: the
    pairs, decays and weights of a sub-chunk) exists outside a kernel. The pointwise
    stage on either side of the scan is a kernel pair too (``ssm_conv``: the taps, the
    bias, ``silu`` and the split; ``ssm_gate_norm``: ``D x``, the gate, the grouped norm),
    called as often as the scan's, and under ``train.ssm.conv`` and ``train.ssm.norm``
    nothing but a kernel makes a float32 ``[2, 8192, 6144]`` or ``[2, 8192, 4096]`` (the
    parent's passes between fusions; ``y`` itself is the scan kernel's, under its own
    scope), and ``z`` and ``xbc`` are read as columns of the in-projection's result, not
    copied out of it: 14,248,942,592 B for the parent's 14,401,249,792. And the file's
    ``compiled_bytes_per_device`` still bounds what the compiler says.

    An expert layer here holds 16 of 128 experts, an eighth of the 98,304 worst-case
    pairs, and **no pass of it outside its kernels walks all 98,304 sorted rows**
    (``moe.WALKED_SHARE``): six times a layer the kernel over the held blocks' row tiles
    (``moe_held_rows``: the bits of ``up`` kept, the activation from them and again in
    the replay, the bits of ``out`` kept; the activation's gradient, ``out`` read back);
    no instruction under ``train.moe.experts`` copies or bit-casts a ``[98304, 1856]`` or
    ``[98304, 2688]`` array (the parent's two whole copies a kept result: 17.6 ms a
    step), and none outside a ``while`` makes one but a kernel: the gather, the combine
    and both their gradients are loops over blocks of 6,144 rows."""
    from benchmark.traffic import train_fixed_batch
    from ray_tpu.models import moe

    cfg, file, compiled = _cell_step(v5e, built_for_tpu, "nemotron-3-nano-30b-a3b-train-ep8")
    memory = compiled.memory_analysis()
    assert cfg.num_params() == 986_254_848
    assert memory.argument_size_in_bytes > 6 * cfg.num_params()        # weights and two moments
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    kernels = train_fixed_batch.kernels_of(text)
    mamba_layers, expert_layers = cfg.pattern.count("M"), cfg.pattern.count("E")
    assert kernels == {
        **file["job"]["min_kernels"],
        **{stage + "_fwd": 2 * mamba_layers for stage in ("ssm_conv", "ssm_scan", "ssm_gate_norm")},
        **{stage + "_bwd": mamba_layers for stage in ("ssm_conv", "ssm_scan", "ssm_gate_norm")},
        "moe_held_rows": 6 * expert_layers}
    assert not train_fixed_batch.missing_kernels(kernels, file["job"]["min_kernels"])
    batch = tuple(file["job"]["batch"])
    pairs = batch[0] * batch[1] * cfg.experts_per_token
    assert cfg.num_experts <= moe.WALKED_SHARE * cfg.router_experts
    assert moe.row_block(pairs, moe.GMM_TRAIN_TILING[0]) * moe.ROW_BLOCKS == pairs == 98304
    sorted_rows = rf"(?:bf16|u16|f32)\[{pairs},(?:{cfg.expert_dim}|{cfg.embed_dim})\]"
    experts = [line for line in text.splitlines() if "train.moe.experts" in line]
    moved = [line for line in experts
             if re.search(rf"= {sorted_rows}\S* (?:copy|bitcast-convert)\(", line)]
    assert not moved, moved[:3]
    entry = text[text.index("ENTRY "):].splitlines()
    whole = [line for line in entry if "train.moe.experts" in line and re.match(
        rf"\s*(?:ROOT )?%\S+ = {sorted_rows}\S* (?!custom-call\(|get-tuple-element\()", line)]
    assert not whole, whole[:3]
    loops = [line for line in entry if re.search(r" while\(", line) and "train.moe.experts" in line]
    for carried, least in ((f"bf16[{pairs},{cfg.embed_dim}]", 3), (f"f32[{batch[0] * batch[1]},{cfg.embed_dim}]", 2)):
        # the gather, its replay and the un-sort's gradient; the combine and the sort's gradient
        assert sum(carried in line for line in loops) >= least * expert_layers, carried
    chunk = cfg.ssm_chunk
    a_sub_chunk_wide = re.findall(rf"(?:f32|bf16)\[(?:\d+,){{2,}}{chunk},{chunk}\]", text)
    assert not a_sub_chunk_wide, sorted(set(a_sub_chunk_wide))
    # the stages on either side of the scan: float32 a token and channel wide lives in VMEM
    wide = rf"f32\[{batch[0]},{batch[1]},(?:{cfg.conv_dim}|{cfg.ssm_inner})\]"
    made = []
    for line in text.splitlines():
        instruction = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(", line)   # its shape, its operation
        if instruction and re.search(r"train\.ssm\.(?:conv|norm)", line) and re.search(
                wide, instruction[1]) and instruction[2] not in ("custom-call", "get-tuple-element"):
            made.append(line[:200])
    assert not made, made[:3]
    # and they read z and xbc where the in-projection wrote them: neither is cut out as a copy
    cut = rf"= bf16\[{batch[0]},{batch[1]},(?:{cfg.conv_dim}|{cfg.ssm_inner})\]\S* (?:slice|copy)\("
    copied = [line[:200] for line in text.splitlines() if "train.ssm." in line and re.search(cut, line)]
    assert not copied, copied[:3]
    stated = file["compiled_bytes_per_device"]
    assert _device_bytes(compiled) <= 1.01 * stated["total"]
    assert stated["total"] == (
        stated["argument"] + stated["temp"] + stated["output"] - stated["alias"])


def test_lfm2_step_is_the_parents_under_the_default_activation(capsys, built_for_tpu):
    """``moe.trained_experts_ffn``'s activation is an argument since PR 62 and LFM2 names
    none: its step, lowered for the TPU as ``scripts/program_digest.py`` lowers it
    (operation names and scopes kept, source lines dropped), is text for text PR 61's.
    A change that means to move LFM2's step replaces the digest, and says so."""
    import importlib.util
    import json

    from benchmark.manifest import published_keys
    from benchmark.models import lfm2_moe as architecture

    root = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "program_digest", os.path.join(root, "scripts", "program_digest.py"))
    program_digest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program_digest)
    with open(os.path.join(root, "benchmark", "configs", "lfm2-24b-a2b-train-ep2.json")) as f:
        file = json.load(f)
    built_for_tpu(True)
    program_digest.train_program(
        "lfm2", architecture.program_config(published_keys(file)), file["job"], 1)
    assert capsys.readouterr().out.split() == [
        "lfm2", "train", "step", "4x4096", "on", "1",
        "21522d9971a76161b6a4bb0efd8172ab21ed660b17b3e2ae3c7f8720d921ca4a"]


def _computations(text):
    """The instructions of each computation of a compiled program, by name."""
    computations, lines = {}, None
    for line in text.splitlines():
        opened = re.match(r"%?([\w.\-]+) \(.*\{$", line)
        if opened:
            lines = computations[opened.group(1)] = []
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line.strip())
    return computations


def _loop_bodies(text):
    computations = _computations(text)
    return [computations[name] for name in set(re.findall(r"body=%?([\w.\-]+)", text))]


def _kernels(body):
    return sum('custom_call_target="tpu_custom_call"' in line for line in body)


def _layer_bodies(text):
    """The instructions of the scanned layer's forward and backward loop
    bodies: the two ``while`` bodies that call the flash kernels. The forward's
    holds the forward kernel; the backward's holds dq and dk/dv and no third:
    the forward kernel's output and logsumexp are kept, not made again."""
    forward, backward = sorted(filter(_kernels, _loop_bodies(text)), key=_kernels)
    return forward, backward


@pytest.mark.parametrize("mesh_id", list(_SPECS))
def test_backward_layer_runs_two_kernels(v5e, mesh_id, built_for_tpu):
    """dq and dk/dv. A third would be the forward kernel run again for the
    output and logsumexp that the layer's remat keeps (under ``shard_map`` on
    the mesh as on one chip: the names are in the kernel's forward rule)."""
    forward, backward = _layer_bodies(_gptj_step(v5e, built_for_tpu, mesh_id)[2])
    assert (_kernels(forward), _kernels(backward)) == (1, 2)


def test_loss_loop_reduces_no_head_gradient_a_chunk(v5e, built_for_tpu):
    """The loss's loop carries each chip's partial sum of the head's gradient
    (float32, ``[embed, vocab / tp]``) and the sum over the batch's axes is
    taken once, behind the loop: the body holds the reductions over the
    vocabulary's shards (maximum, sum and target logit, the hidden state's
    gradient) and none of an ``[embed, vocab / tp]`` operand; a chunk's logits
    are multiplied once, and so is each of the two gradients."""
    _, _, text = _gptj_step(v5e, built_for_tpu, "fsdp2xtp2")
    cfg = _gptj(2)
    head = f"[{cfg.embed_dim},{cfg.vocab_size // 2}]"
    (loss,) = [
        body for body in _loop_bodies(text)
        if any("train.loss" in line for line in body) and any(f"f32{head}" in line for line in body)]
    reductions = [line for line in loss if re.search(r" all-reduce(-start)?\(", line)]
    assert reductions and not any(head in line.split(" all-reduce")[0] for line in reductions)
    assert not any(re.search(r" (reduce-scatter|all-gather|all-to-all)(-start)?\(", line) for line in loss)
    behind = [
        line for line in text.splitlines()
        if re.search(r" all-reduce(-start)?\(", line) and head in line.split(" all-reduce")[0]]
    assert len(behind) == 1 and "train.loss" in behind[0]


def test_one_chip_step_holds_no_collective_and_gets_no_option(v5e, built_for_tpu):
    mesh, _, text = _gptj_step(v5e, built_for_tpu, "one-chip")
    assert accelerator.compiler_options(mesh) == {}
    assert not _COLLECTIVE.search(text)


def _tp_hops(body):
    """The ``collective-permute-start``s of a layer body that carry a chip's
    share of the stream to its tp neighbour (tp is the mesh's innermost axis:
    chips 0 and 1, 2 and 3), by the scope they were sent under."""
    hops = [
        line for line in body
        if " collective-permute-start(" in line and re.search(r"train\.tp\.\w+/ppermute", line)]
    for line in hops:
        assert re.search(r"source_target_pairs=\{\{[01],[01]\},\{[01],[01]\},\{[23],[23]\},\{[23],[23]\}\}", line), line
        assert re.search(r"\(bf16\[(1,)?1024,4096\]", line.split(" = ")[1]), line      # half the tokens
    return sorted(re.search(r"train\.tp\.(\w+)/ppermute", line).group(1) for line in hops)


def test_no_layer_body_all_reduces_the_stream_over_the_tp_pairs(v5e, built_for_tpu):
    """``Block.scattered``: the sum of attention's output and the MLP's product
    over the tp pair is no all-reduce (the parent's forward body held one of a
    ``[batch, seq, embed]`` operand, its backward body one of ``d hidden``, both
    synchronous). The forward sends a chip's normed half to its neighbour in
    front of q, k, v and ``wi`` and the neighbour's partial sum behind ``o`` and
    ``wo``; the backward sends the normed half again (the replay), ``d out``'s
    half in front of ``o``'s and ``wo``'s gradients and ``d hidden``'s partial
    sum behind q, k, v and ``wi``'s. What is still reduced in a layer body is a
    vector: the gradients of the biases and of LayerNorm's scale and bias, each
    summed over a chip's own tokens."""
    _, _, text = _gptj_step(v5e, built_for_tpu, "fsdp2xtp2")
    forward, backward = _layer_bodies(text)
    assert _tp_hops(forward) == ["gather", "scatter"]
    assert _tp_hops(backward) == ["gather", "gather", "scatter"]
    for body in (forward, backward):
        for line in body:
            if re.search(r" (all-reduce|all-to-all|reduce-scatter)(-start)?\(", line):
                shapes = re.findall(r"\w+\[([\d,]*)\]", line.split(" all-")[0].split(" reduce-")[0])
                assert all(       # vectors alone: none as long as a token's row of ``wi``
                    math.prod(map(int, filter(None, dims.split(",")))) <= 8192 for dims in shapes), line
    assert not [line for line in forward if re.search(r" all-reduce(-start)?\(", line)]


def test_four_chip_step_at_the_cells_own_size_is_smaller_than_the_parents(v5e, built_for_tpu):
    """``gptj-train-4chip-full-depth`` as it runs: depth 28, 4 x 2048 tokens.
    The layer inputs that the remat keeps are a chip's own half of the tokens
    (28 x 16.8 MB where they were 33.5), so the step compiles to less than the
    parent's 16,419,761,664 bytes a device (PERF.md section 7, S7b (8))."""
    _, compiled, _ = _gptj_step(v5e, built_for_tpu, "fsdp2xtp2", depth=28, batch=(4, 2048))
    assert _device_bytes(compiled) < 16_419_761_664 - 28 * 8_388_608
    _STEPS.pop(("fsdp2xtp2", 28, (4, 2048)))      # 28 layers' text: not worth keeping


def test_backward_layer_reduces_no_gradient_behind_its_matmul(v5e, built_for_tpu):
    """PR 36's parent held six synchronous fused all-reduce + slice
    (``all-reduce-scatter``), one behind each weight gradient's matmul, in the
    backward body; no compiler setting made one asynchronous. ``ring_dense``
    multiplies a gradient a shard at a time and sends each partial sum on (one
    ``collective-permute`` a weight on an fsdp axis of two) while the next
    shard multiplies, inside the layer's ``shard_map`` over tp; the head's
    gradient is reduced once after the loss's loop, not once a chunk inside it.
    No loop body holds a fused all-reduce + slice or a reduce-scatter; the one
    the program has left is the embedding's gradient, once a step."""
    mesh, compiled, text = _gptj_step(v5e, built_for_tpu, "fsdp2xtp2")
    forward, backward = _layer_bodies(text)
    sends = [line for line in backward if " collective-permute-start(" in line]
    gradients = [line for line in sends if "/shard_map/ppermute" in line]
    assert len(gradients) == 6 + 1      # one hop a weight on an fsdp axis of two, and ``wi``'s bias
    assert all("source_target_pairs={{0,2},{2,0},{1,3},{3,1}}" in line for line in gradients)
    for body in _loop_bodies(text):
        assert not [
            line for line in body
            if "all-reduce-scatter" in line or re.search(r" reduce-scatter(-start)?\(", line)]
    assert " reduce-scatter(" not in text
    (fused,) = [line for line in text.splitlines() if "calls=%all-reduce-scatter" in line]
    assert "/wte/" in fused
    assert _device_bytes(compiled) < HBM_BYTES


# a layer's six kernels as a chip of the fsdp2 x tp2 mesh holds them gathered
# over fsdp: q, k, v ``[embed, heads / 2, kv]``, o, ``wi`` and ``wo``
_GATHERED = {"4096,8,256": 3, "8,256,4096": 1, "4096,8192": 1, "8192,4096": 1}


def _weight_gathers(body):
    """The gathers of a layer's kernels over fsdp in a layer body, which is in
    the order the chip runs it: ``(synchronous, asynchronous)``, the shapes of
    the ``all-gather`` instructions, and for every ``async-collective-start``
    ... ``-done`` pair (the compiler's fusion round an all-gather that travels
    while other instructions run) its shape and how many of the layer's
    products lie between its two ends."""
    weight = r"bf16\[1,(%s)\]" % "|".join(_GATHERED)
    synchronous = [
        m.group(1) for m in (re.search(rf"= {weight}\S* all-gather\(", line) for line in body) if m]
    started, asynchronous = {}, []
    for at, line in enumerate(body):
        start = re.match(rf"%?async-collective-start([.\d]*) = \(\S+, {weight}", line)
        done = re.match(r"%?async-collective-done([.\d]*) = ", line)
        if start:
            started[start.group(1)] = at, start.group(2)
        elif done and done.group(1) in started:
            since, shape = started.pop(done.group(1))
            asynchronous.append((shape, sum(
                " fusion(" in between and bool(re.search(r'op_name="[^"]*dot_general"', between))
                for between in body[since + 1:at])))
    assert not started
    return synchronous, asynchronous


def test_each_layer_body_gathers_each_weight_once_beside_a_product(v5e, built_for_tpu):
    """``Block.scattered``'s step is compiled without the mesh's option, so a
    weight sharded over fsdp arrives whole and not as a matmul in chunks (the
    parent's layer bodies held twelve and more ``collective-permute`` chunks,
    which ``train.collective_exposed_share`` read; an ``async-collective-done``
    is a name its reader does not know: PERF.md section 7, S7b (11)). What
    keeps a whole gather from being a wait: each body gathers each of the six
    kernels once (the backward not twice, as it did with chunks); every gather
    but the layer's first is an ``async-collective-start`` ... ``-done`` pair
    with a product of the layer between its ends; the backward holds no
    synchronous gather of a kernel. The forward's first (of q, k, v: two at
    this size, one at the cell's) are synchronous ``all-gather``s, with nothing
    of the layer in front of them to travel beside: that wait is a real one
    (0.36 + 0.46 ms a layer, PERF.md section 5)."""
    mesh, _, text = _gptj_step(v5e, built_for_tpu, "fsdp2xtp2")
    assert accelerator.compiler_options(mesh)       # the mesh's, which this step does not take
    forward, backward = _layer_bodies(text)
    for body, most_synchronous in ((forward, 2), (backward, 0)):
        synchronous, asynchronous = _weight_gathers(body)
        assert len(synchronous) <= most_synchronous and set(synchronous) <= {"4096,8,256"}
        gathered = synchronous + [shape for shape, _ in asynchronous]
        assert {shape: gathered.count(shape) for shape in set(gathered)} == _GATHERED
        assert all(products >= 1 for _, products in asynchronous), asynchronous
        # and nothing else of more than a vector is gathered synchronously
        others = [
            line for line in body
            if re.search(r" all-gather(-start)?\(", line)
            and not re.search(r" = bf16\[1,(%s)\]" % "|".join(_GATHERED), line)]
        assert all(re.search(r" = \w+\[(1,)?\d+\]", line) for line in others), others


@pytest.mark.parametrize(
    "mesh_spec,n_devices,cfg,seq,takes",
    [
        (MeshSpec(dp=-1, fsdp=2, tp=2), 4, {}, 64, False),
        (MeshSpec(dp=-1, fsdp=2, tp=2), 4, {}, 63, True),
        (MeshSpec(dp=-1, fsdp=2, tp=2), 4, {"moe_num_experts": 4}, 64, True),
        (MeshSpec(dp=-1, fsdp=4), 4, {}, 64, True),
        (MeshSpec(dp=-1, tp=2), 2, {}, 64, False),
        (MeshSpec(), 1, {}, 64, False),
    ],
    ids=["scattered", "seq-not-divided", "experts", "fsdp-alone", "tp-alone", "one-chip"],
)
def test_a_step_takes_the_meshes_option_unless_its_blocks_scatter(
        v5e, monkeypatch, mesh_spec, n_devices, cfg, seq, takes):
    """``accelerator.compiler_options`` reads the mesh alone (the weights'
    all-gathers as matmuls in chunks wherever fsdp shards them). The one step
    compiled without it is the one whose blocks take their products apart
    round tp, and one predicate says which: ``GPTConfig.scatter_axis``, asked
    by ``Block`` for the path and by ``make_train_step``, with the tokens'
    shape, for the option. A step with experts, or on a sequence that tp does
    not divide, runs the plain block and keeps the parent's chunks."""
    cfg = dataclasses.replace(gpt.gpt_nano(), **cfg)
    mesh = mesh_spec.build(v5e[:n_devices])
    compiled_with = []
    jit = jax.jit

    def seen(f, **kwargs):
        compiled_with.append(kwargs.get("compiler_options"))
        return jit(f, **kwargs)

    opt = default_optimizer(1e-4)
    batch = (4, seq)
    _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct(batch, jnp.int32))
    shardings = nn.meta.unbox(state_shardings(mesh, abstract))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        nn.meta.unbox(abstract), shardings)
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=shd.batch_sharding(mesh))
    monkeypatch.setattr(jax, "jit", seen)
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    text = step.lower(state, tokens).as_text(debug_info=True)
    monkeypatch.undo()
    option = accelerator.compiler_options(mesh)
    assert bool(option) == (mesh.shape["fsdp"] > 1)
    assert compiled_with[-1] == (option if takes else {})
    assert ("train.tp.scatter" in text) == (
        cfg.scatter_axis(mesh, shd.DEFAULT_RULES, seq) is not None) == (
        mesh.shape["tp"] > 1 and not takes)
