"""``parallel/ring_dense.dense``: the plain product everywhere, and on a mesh
whose fsdp axis shards the kernel a weight gradient reduced round that axis a
shard at a time (virtual 8-device CPU mesh; the chip's schedule is
``tests/test_chip_compile.py``'s and the benchmark's)."""

import dataclasses
import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt
from ray_tpu.parallel import ring_dense
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.sharding import DEFAULT_RULES

# a layer's three kinds of kernel: [*in, *out], logical names, dims contracted
KERNELS = {
    "wi": ((32, 64), ("embed", "mlp"), 1),
    "wo": ((64, 32), ("mlp", "embed"), 1),
    "q": ((32, 4, 8), ("embed", "heads", "kv"), 1),
    "o": ((4, 8, 32), ("heads", "kv", "embed"), 2),
}
MESHES = {
    "dp2-fsdp2-tp2": MeshSpec(dp=2, fsdp=2, tp=2),
    "fsdp4-tp2": MeshSpec(dp=-1, fsdp=4, tp=2),
    "fsdp2-sp2-tp2": MeshSpec(dp=-1, fsdp=2, sp=2, tp=2),
    "fsdp8": MeshSpec(dp=-1, fsdp=8),
    "dp8": MeshSpec(dp=-1),
    "dp4-tp2": MeshSpec(dp=-1, tp=2),
}


def _case(kernel):
    shape, axes, n_in = KERNELS[kernel]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (8, 16) + shape[:n_in], jnp.float32)
    w = jax.random.normal(keys[1], shape, jnp.float32)
    dy = jax.random.normal(keys[2], (8, 16) + shape[n_in:], jnp.float32)
    return x, w, dy, axes, n_in


@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("mesh_id", list(MESHES))
def test_product_and_both_gradients_are_the_plain_ones(mesh_id, kernel):
    x, w, dy, axes, n_in = _case(kernel)
    mesh = MESHES[mesh_id].build()

    def of(dense):
        return jax.jit(lambda x, w: jax.vjp(dense, x, w)[1](dy) + (dense(x, w),))(x, w)

    plain = of(lambda x, w: ring_dense.dense(x, w, n_in))
    with mesh:
        ringed = of(lambda x, w: ring_dense.dense(x, w, n_in, mesh, axes, DEFAULT_RULES))
    for got, want in zip(ringed, plain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize(
    "mesh_id,hops",
    [("dp2-fsdp2-tp2", 1), ("fsdp4-tp2", 3), ("fsdp8", 7), ("dp8", 0), ("dp4-tp2", 0)],
)
def test_gradient_takes_one_hop_less_than_the_axis_has_chips(mesh_id, hops):
    """``n - 1`` sends of one shard each, and the gradient comes out sharded as
    the kernel is; without an fsdp axis nothing is wrapped at all."""
    x, w, dy, axes, n_in = _case("wi")
    mesh = MESHES[mesh_id].build()

    def gradient(x, w):
        dense = lambda x, w: ring_dense.dense(x, w, n_in, mesh, axes, DEFAULT_RULES)
        return jax.vjp(dense, x, w)[1](dy)[1]

    text = str(jax.make_jaxpr(gradient)(x, w))
    assert text.count("ppermute") == hops
    assert ("shard_map" in text) == bool(hops)
    if hops:
        with mesh:
            dw = jax.jit(gradient)(x, w)
        assert dw.sharding.is_equivalent_to(NamedSharding(mesh, P("fsdp", "tp")), 2)


def test_no_mesh_no_rules_or_one_device_is_the_plain_product():
    x, w, _, axes, n_in = _case("o")
    one = MeshSpec().build(jax.devices()[:1])
    mesh = MESHES["fsdp4-tp2"].build()
    for args in ((), (one, axes, DEFAULT_RULES), (mesh, axes, ()), (mesh, None, DEFAULT_RULES)):
        text = str(jax.make_jaxpr(
            lambda x, w: jax.grad(lambda x, w: ring_dense.dense(x, w, n_in, *args).sum(), 1)(x, w)
        )(x, w))
        assert "shard_map" not in text and "custom_vjp" not in text


# ---------------------------------------------------------------------------
# a layer's output reduced round tp in hops, the stream between layers scattered
# ---------------------------------------------------------------------------

TP_MESHES = {
    "dp2-fsdp2-tp2": MeshSpec(dp=2, fsdp=2, tp=2),
    "dp4-tp2": MeshSpec(dp=-1, tp=2),
    "dp2-tp4": MeshSpec(dp=-1, tp=4),
}
SEQ = 32


def _layers(module, mesh, rules=DEFAULT_RULES, seq=SEQ, cfg=None):
    """``(parameters, x) -> output`` of a ``Block`` or of two scanned ones, on
    ``mesh`` under ``rules``, with parameters made by the plain module and nudged
    off their initial zeros."""
    cfg = cfg or gpt.gpt_nano()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, seq, cfg.embed_dim), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (8, seq))
    make = {"block": gpt.Block, "two-layer-scan": gpt.ScannedBlocks}[module]
    params = nn.meta.unbox(make(cfg).init(jax.random.PRNGKey(0), x, positions))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(
        tree, [p + 0.02 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])

    def apply(params, x):
        with nn.logical_axis_rules(list(rules)):
            return make(cfg, mesh).apply(params, x, positions)

    return apply, params, x


def _output_and_gradients(apply, params, x):
    weigh = jax.random.normal(jax.random.PRNGKey(3), x.shape, x.dtype)
    return jax.jit(lambda p, x: (
        apply(p, x), jax.grad(lambda p, x: (apply(p, x) * weigh).sum(), (0, 1))(p, x)))(params, x)


@pytest.mark.parametrize("module", ["block", "two-layer-scan"])
@pytest.mark.parametrize("mesh_id", list(TP_MESHES))
def test_scattered_block_is_the_plain_block(mesh_id, module):
    """Output, input gradient and every weight gradient, float32: the hops add
    what the all-reduce added."""
    mesh = TP_MESHES[mesh_id].build()
    assert ring_dense.scatter_axis(mesh, DEFAULT_RULES, SEQ) == "tp"
    want = _output_and_gradients(*_layers(module, None))
    got = _output_and_gradients(*_layers(module, mesh))
    for path, each in jax.tree_util.tree_leaves_with_path(got):
        assert np.isfinite(np.asarray(each)).all(), path
    jax.tree.map(
        lambda got, want: np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5 * float(np.abs(want).max())),
        got, want)


def test_scattered_block_in_bfloat16_is_the_plain_block_to_its_rounding():
    cfg = dataclasses.replace(gpt.gpt_nano(), dtype=jnp.bfloat16)
    mesh = TP_MESHES["dp2-fsdp2-tp2"].build()
    want = _output_and_gradients(*_layers("block", None, cfg=cfg))
    got = _output_and_gradients(*_layers("block", mesh, cfg=cfg))
    jax.tree.map(
        lambda got, want: np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0.05, atol=0.03 * float(np.abs(np.asarray(want, np.float32)).max())),
        got, want)


@pytest.mark.parametrize(
    "mesh_id,tp_hops,ring_hops,bias_hops",
    [("dp4-tp2", 1, 0, 3), ("dp2-tp4", 3, 0, 1), ("dp2-fsdp2-tp2", 1, 6, 2)])
def test_a_layer_sends_one_hop_less_than_tp_has_chips_a_half_product(
        mesh_id, tp_hops, ring_hops, bias_hops):
    """Forward: ``n - 1`` hops in front of q, k, v and ``wi`` and ``n - 1`` behind
    ``o`` and ``wo``; the backward mirrors both; the six weight gradients keep
    their ring round fsdp inside the layer's ``shard_map``, and the gradient of
    ``wi``'s bias is summed by hops round each of the batch's axes. No sum over
    tp is left to a ``psum``, and the compiled program reduces nothing the size
    of the stream: what is left over tp is the gradient of LayerNorm's and
    ``wo``'s replicated vectors, each summed over a chip's own tokens."""
    mesh = TP_MESHES[mesh_id].build()
    apply, params, x = _layers("block", mesh)
    forward = str(jax.make_jaxpr(apply)(params, x))
    assert forward.count("ppermute") == 2 * tp_hops and "psum" not in forward
    both = jax.grad(lambda p, x: (apply(p, x) ** 2).sum(), (0, 1))
    text = str(jax.make_jaxpr(both)(params, x))
    assert text.count("ppermute") == 4 * tp_hops + ring_hops + bias_hops
    assert not any("tp" in axes for axes in re.findall(r"psum\[[^\]]*axes=\(([^)]*)\)", text))
    with mesh:
        lowered = jax.jit(both).lower(params, x)
    assert lowered.as_text().count("collective_permute") == 4 * tp_hops + ring_hops + bias_hops
    half = x.size // mesh.shape["tp"] // (mesh.size // mesh.shape["tp"])
    reduced = re.findall(r"= \w+\[([\d,]*)\][^ ]* all-reduce(?:-start)?\(", lowered.compile().as_text())
    assert all(math.prod(map(int, filter(None, dims.split(",")))) < half for dims in reduced), reduced


def _refuse(*_args, **_kw):
    raise AssertionError("the scattered path was entered")


@pytest.mark.parametrize(
    "case", ["no-mesh", "one-device", "no-rules", "tp1", "sp2", "seq-tp-does-not-divide", "experts"])
def test_where_nothing_can_be_scattered_the_block_is_the_plain_one(case, monkeypatch):
    """The path is chosen from the mesh, the rules and the shapes; anywhere the
    stream cannot lie scattered over tp the block is today's, hop for hop."""
    monkeypatch.setattr(gpt.Block, "scattered", _refuse)
    for name in ("arriving", "home", "in_order", "by_hop"):
        monkeypatch.setattr(ring_dense, name, _refuse)
    cfg, seq, rules = gpt.gpt_nano(), SEQ, DEFAULT_RULES
    mesh = {
        "no-mesh": lambda: None,
        "one-device": lambda: MeshSpec().build(jax.devices()[:1]),
        "no-rules": lambda: TP_MESHES["dp4-tp2"].build(),
        "tp1": lambda: MeshSpec(dp=-1, fsdp=4).build(),
        "sp2": lambda: MeshSpec(dp=-1, fsdp=2, sp=2, tp=2).build(),
        "seq-tp-does-not-divide": lambda: TP_MESHES["dp2-tp4"].build(),
        "experts": lambda: TP_MESHES["dp4-tp2"].build(),
    }[case]()
    if case == "no-rules":
        rules = ()
    if case == "seq-tp-does-not-divide":
        seq = 30
    if case == "experts":
        cfg = gpt.gpt_nano(moe_num_experts=4)
    else:
        assert ring_dense.scatter_axis(mesh, rules, seq) is None
    apply, params, x = _layers("block", mesh, rules, seq, cfg)
    plain, _, _ = _layers("block", None, rules, seq, cfg)
    assert "tp" not in re.findall(r"ppermute\[[^\]]*axis_name=\(?'?(\w+)", str(jax.make_jaxpr(apply)(params, x)))
    np.testing.assert_allclose(
        np.asarray(jax.jit(apply)(params, x)), np.asarray(jax.jit(plain)(params, x)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("model", ["gptj", "lfm2"])
def test_a_one_chip_train_step_is_the_step_without_a_mesh(model, monkeypatch):
    """The two one-chip train cells' steps: on a mesh of one device nothing of
    the scattered layer is entered, and the step lowers to the operations it
    lowers to with no mesh at all (the mesh adds its sharding annotations, all
    of them of one device, and nothing else)."""
    from ray_tpu.models import lfm2_moe
    from ray_tpu.models.training import default_optimizer, init_sharded_state, make_train_step

    monkeypatch.setattr(gpt.Block, "scattered", _refuse)
    for name in ("arriving", "home", "in_order", "by_hop"):
        monkeypatch.setattr(ring_dense, name, _refuse)
    cfg = gpt.gpt_nano() if model == "gptj" else lfm2_moe.lfm2_moe_nano()
    one = MeshSpec().build(jax.devices()[:1])
    opt = default_optimizer(1e-3)
    state, shardings = init_sharded_state(cfg, one, opt, jax.random.PRNGKey(0), (2, 32))
    tokens = jnp.zeros((2, 32), jnp.int32)

    def operations(mesh):
        step = make_train_step(
            cfg, opt, mesh, state_shardings_tree=None if mesh is None else shardings, donate=False)
        text = step.lower(state, tokens).as_text()
        # the operations alone: no sharding annotation, no value or function name
        text = re.sub(r"sdy\.sharding_constraint", "", text)
        return sorted(re.findall(r"= \"?(stablehlo\.\w+|sdy\.\w+)", text))

    on_one = operations(one)
    assert not [op for op in on_one if "collective" in op or "all_reduce" in op or "manual" in op]
    assert on_one == operations(None)


@pytest.mark.parametrize("seq,scatters", [(32, True), (31, False)], ids=["divided", "not-divided"])
def test_a_step_is_compiled_for_what_its_blocks_do_with_the_sequence(seq, scatters, monkeypatch):
    """Where the mesh has a compiler option (on the chip: fsdp's gathers in
    chunks; here one the CPU's compiler takes) ``make_train_step`` waits for the
    tokens' shape: the step whose blocks scatter the stream over tp is the jit
    without the option, any other the jit with it, and either way it is called,
    lowered and traced as the plain jit is, and trains the same step."""
    from ray_tpu._private import accelerator
    from ray_tpu.models import training

    option = {"xla_cpu_enable_fast_math": False}
    cfg = gpt.gpt_nano()
    mesh = MESHES["dp2-fsdp2-tp2"].build()
    opt = training.default_optimizer(1e-3)
    state, shardings = training.init_sharded_state(cfg, mesh, opt, jax.random.PRNGKey(0), (4, seq))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, seq), 0, cfg.vocab_size)
    plain = training.make_train_step(cfg, opt, mesh, state_shardings_tree=shardings, donate=False)
    assert not isinstance(plain, training._StepBySequence)         # the CPU has no option
    monkeypatch.setattr(accelerator, "compiler_options", lambda mesh: option)
    built, jit = [], jax.jit
    monkeypatch.setattr(
        jax, "jit", lambda f, **kw: built.append(kw.get("compiler_options")) or jit(f, **kw))
    step = training.make_train_step(cfg, opt, mesh, state_shardings_tree=shardings, donate=False)
    assert isinstance(step, training._StepBySequence) and not built     # nothing built before a shape
    with mesh:
        (_, got), (_, want) = step(state, tokens), plain(state, tokens)
        assert step.lower(state, tokens).as_text() == step.trace(state, tokens).lower().as_text()
    assert built == [{} if scatters else option]           # one jit, whatever asked for it
    assert cfg.train_model(mesh).scatters(DEFAULT_RULES, seq) == scatters
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]), rtol=1e-6)
    np.testing.assert_allclose(float(got["grad_norm"]), float(want["grad_norm"]), rtol=1e-5)


@pytest.mark.parametrize("mesh_id", ["dp2-fsdp2-tp2", "fsdp8", "dp8", "none"])
def test_biased_and_both_gradients_are_the_plain_ones(mesh_id):
    """``x + bias`` and both gradients, the bias's summed by hops round the
    batch's axes (``fsdp8``: seven; no mesh: none)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    xs = tuple(jax.random.normal(k, (8, 16, 64), jnp.float32) for k in keys[:2])
    bias = jax.random.normal(keys[2], (64,), jnp.float32)
    mesh = None if mesh_id == "none" else MESHES.get(mesh_id, TP_MESHES.get(mesh_id)).build()

    def of(biased):
        return jax.jit(jax.grad(
            lambda xs, bias: sum((y ** 2).sum() for y in biased(xs, bias)), (0, 1)))(xs, bias)

    hopped = lambda xs, bias: ring_dense.biased(xs, bias, mesh, ("mlp",), DEFAULT_RULES)  # noqa: E731
    jax.tree.map(
        lambda got, want: np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-3),
        of(hopped), of(lambda xs, bias: ring_dense.biased(xs, bias)))
    hops = str(jax.make_jaxpr(jax.grad(lambda b: hopped(xs, b)[0].sum()))(bias)).count("ppermute")
    assert hops == {"dp2-fsdp2-tp2": 2, "fsdp8": 7, "dp8": 7, "none": 0}[mesh_id]
