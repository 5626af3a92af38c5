"""Model family + sharded train step on a virtual 8-device CPU mesh.

(mirrors the reference's train library tests, reference:
python/ray/train/tests/; sharding logic is what the driver's
dryrun_multichip validates on more devices.)
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.cohere2_moe import cohere2_moe_nano
from ray_tpu.models.gpt import (
    GPT, GPTConfig, blockwise_next_token_loss, gpt_nano, next_token_loss)
from ray_tpu.models.training import (
    abstract_state,
    default_optimizer,
    init_sharded_state,
    make_train_step,
    init_params,
)
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.sharding import logical_to_spec, DEFAULT_RULES
from jax.sharding import PartitionSpec


def test_mesh_spec_resolve():
    spec = MeshSpec(dp=-1, tp=2)
    sizes = spec.resolve(8)
    assert sizes["dp"] == 4 and sizes["tp"] == 2
    mesh = spec.build()
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2


def test_mesh_spec_errors():
    with pytest.raises(ValueError):
        MeshSpec(dp=-1, tp=-1).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolve(8)


def test_logical_to_spec():
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build()
    spec = logical_to_spec(("batch", "seq", "embed"), DEFAULT_RULES, mesh)
    assert spec == PartitionSpec(("dp", "fsdp"), None, None) or spec == PartitionSpec(
        ("dp", "fsdp"),
    )
    # sp axis is size 1 → seq replicated; embed → fsdp is already used by batch
    spec2 = logical_to_spec(("embed", "mlp"), DEFAULT_RULES, mesh)
    assert spec2 == PartitionSpec("fsdp", "tp")


def test_forward_shapes():
    cfg = gpt_nano()
    params = init_params(cfg, jax.random.PRNGKey(0), (2, 16))
    model = GPT(cfg)
    logits = model.apply({"params": params}, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_masked():
    logits = jnp.zeros((1, 4, 8))
    tokens = jnp.array([[1, 2, 3, 4]])
    mask = jnp.array([[1, 1, 0, 0]])
    loss = next_token_loss(logits, tokens, mask)
    assert np.isclose(float(loss), np.log(8), atol=1e-5)


def test_sharded_train_step_loss_decreases():
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build()
    cfg = gpt_nano()
    opt = default_optimizer(learning_rate=1e-2)
    state, shardings = init_sharded_state(
        cfg, mesh, opt, jax.random.PRNGKey(0), (4, 32)
    )
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    with mesh:
        state, m0 = step(state, tokens)
        for _ in range(10):
            state, m = step(state, tokens)
    assert float(m["loss"]) < float(m0["loss"])
    assert int(m["step"]) == 11
    # params actually sharded over fsdp/tp
    wi = state.params["blocks"]["layers"]["mlp"]["wi"]["kernel"]
    assert len(wi.sharding.device_set) > 1


def test_flops_positive():
    assert gpt_nano().num_params() > 0


@pytest.mark.parametrize(
    "cfg",
    [gpt_nano(), gpt_nano(tie_embeddings=True), cohere2_moe_nano()],
    ids=["gpt_nano", "gpt_nano-tied", "cohere2_moe_nano"],
)
def test_num_params_counts_the_initialised_tree(cfg):
    """``describe`` and the MFU reader of the benchmark divide by it."""
    tree = jax.eval_shape(lambda: cfg.init_params(0))
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(tree))


def test_gptconfig_holds_what_the_model_is_and_no_run_switch():
    """How a step runs (kernel or XLA, tiles, what the remat saves, the loss's
    chunk, scanned or unrolled) is the code's to decide, from the platform and
    the shapes: a new field here has to be a key of the model, or argue with
    this test."""
    model_keys = {
        "vocab_size", "num_layers", "num_heads", "head_dim", "embed_dim", "mlp_dim",
        "max_seq_len", "rotary_dim", "dtype", "param_dtype", "tie_embeddings",
    }
    kept = {"remat", "seq_parallel_impl"}
    moe = {"moe_num_experts", "moe_top_k", "moe_capacity_factor", "moe_aux_weight"}
    assert {f.name for f in dataclasses.fields(GPTConfig)} == model_keys | kept | moe
    for gone in (
        "remat_policy", "scan_layers", "attn_use_pallas", "attn_block_q", "attn_block_k",
        "ce_chunk", "parallel_residual",
    ):
        with pytest.raises(TypeError):
            GPTConfig(**{gone: None})


def _train_step_program(**changes):
    cfg = dataclasses.replace(gpt_nano(), num_heads=1, head_dim=64, **changes)
    optimizer = default_optimizer()
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    _, state = abstract_state(cfg, optimizer, tokens)
    step = make_train_step(cfg, optimizer, donate=False)
    return jax.make_jaxpr(step)(nn.meta.unbox(state), tokens)


def _train_step_jaxpr():
    return str(_train_step_program())


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (scan
    and remat bodies, custom rules, jits), depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for each in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(each, "jaxpr", each)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _products_with(jaxpr, size):
    """The ``dot_general``s with an operand that has a dimension of ``size``."""
    return [
        eqn for eqn in _equations(jaxpr)
        if eqn.primitive.name == "dot_general"
        and any(size in var.aval.shape for var in eqn.invars)]


def _command_a_plus_extend_jaxpr():
    cfg = cohere2_moe_nano()
    kv = jax.ShapeDtypeStruct((cfg.num_layers, 2, 64, cfg.kv_heads, cfg.head_dim), cfg.dtype)
    return str(jax.make_jaxpr(cfg.make_extend_fn())(
        jax.eval_shape(lambda: cfg.init_params(0)), jax.ShapeDtypeStruct((2, 8), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), kv, kv))


@pytest.mark.parametrize(
    "program,kernel",
    [(_train_step_jaxpr, "flash_fwd"), (_command_a_plus_extend_jaxpr, "name=gmm")],
    ids=["train-step", "command-a-plus-extend"],
)
def test_one_probe_decides_every_kernel(built_for_tpu, program, kernel):
    """``ops/backend.on_tpu`` alone puts the flash kernels into the train step
    and the grouped matmul into Command A+'s ``extend``, and alone takes them
    out: no field, argument or second probe stands beside it."""
    built_for_tpu(True)
    assert kernel in program()
    built_for_tpu(False)
    text = program()
    assert kernel not in text and "pallas_call" not in text


@pytest.fixture
def on_the_chip(monkeypatch, built_for_tpu):
    """Command A+'s ``extend`` as the chip traces it, on the CPU:
    ``backend.on_tpu`` answers yes, a chunk's attention kernel runs interpreted
    at small tiles (two key blocks over a 64-slot cache), the grouped matmul is
    XLA's. Yields the shapes the kernel was called with."""
    from ray_tpu.models import moe
    from ray_tpu.ops import attention

    real = attention.masked_attention
    seen = []

    def interpreted(q, k, v, mask, kv_len, **kw):
        seen.append((q.shape, k.shape, v.shape, mask.shape, kv_len.shape))
        return real(q, k, v, mask, kv_len, interpret=True, block_q=16, block_k=32, **kw)

    monkeypatch.setattr(moe, "grouped_matmul", lambda rows, w, sizes: jax.lax.ragged_dot(rows, w, sizes))
    monkeypatch.setattr(attention, "masked_attention", interpreted)
    built_for_tpu(True)
    return seen


_RUBBISH = 1e4      # what a cache slot nobody wrote may hold: finite, and far from a key


def _command_a_plus_calls(cfg):
    """Two prefill calls of two lanes over a 64-slot cache, and what is real in
    each: a chunk from an empty cache (32 tokens: lane 0 has 28, lane 1 has 8 and
    24 of padding), then a chunk of 16 behind them (lane 0's queries stand at 28 ..
    43, past ``sliding_window`` = 24; lane 1 has 10 tokens and 6 of padding).
    Returns ``run(extend)`` -> per call ``(logits, hidden, k_new, v_new)`` and the
    calls' ``valid``."""
    params = cfg.init_params(0)
    # sharper scores than weights of 0.02 give: what a query may not read would
    # otherwise move nothing
    attn = params["blocks"]["layers"]["attn"]
    attn["q"]["kernel"], attn["k"]["kernel"] = attn["q"]["kernel"] * 8, attn["k"]["kernel"] * 8
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, cfg.vocab_size))
    first = np.where(np.arange(32) < np.array([[28], [8]]), tokens[:, :32], -1)
    second = np.where(np.arange(16) < np.array([[16], [10]]), tokens[:, 32:], -1)
    held = np.array([28, 8])
    shape = (cfg.num_layers, 2, 64, cfg.kv_heads, cfg.head_dim)

    def run(extend):
        empty = jnp.full(shape, _RUBBISH, cfg.dtype)
        one = extend(params, jnp.asarray(first), jnp.zeros((2,), jnp.int32), empty, empty)
        caches = []
        for rows in one[2:4]:       # the real rows alone, as the engine pages them back
            cache = np.full(shape, _RUBBISH, np.float32)
            for lane, n in enumerate(held):
                cache[:, lane, :n] = np.asarray(rows)[:, lane, :n]
            caches.append(jnp.asarray(cache))
        two = extend(params, jnp.asarray(second), jnp.asarray(held, jnp.int32), *caches)
        return one[:4], two[:4]

    return run, (first >= 0, second >= 0)


def test_command_a_plus_chunk_attends_in_the_kernel_as_off_the_chip(on_the_chip, built_for_tpu):
    """What a prefill chunk runs on the chip, ``ops/attention.masked_attention``
    under the layer's causal-and-window mask up to the lane's live bound
    (interpreted here), gives the dense form's logits, hidden state and new K/V
    rows for every real token, through sliding and full layers alike (layer
    ``i + 1``'s rows are made from layer ``i``'s attend), over a cache whose
    unwritten slots hold rubbish. And the window is seen: a window that
    hides nothing gives other logits."""
    cfg = cohere2_moe_nano()
    assert cfg.sliding_layers == (True, True, False) * 2
    run, valid = _command_a_plus_calls(cfg)
    got = run(cfg.make_extend_fn())
    groups = cfg.num_heads // cfg.kv_heads
    # one trace a program: the scan's body holds the kernel once for all six layers
    assert on_the_chip == [
        ((2, tc, cfg.kv_heads, groups, cfg.head_dim), (2, 64, cfg.kv_heads, cfg.head_dim),
         (2, 64, cfg.kv_heads, cfg.head_dim), (2, tc, 64), (2,)) for tc in (32, 16)]
    built_for_tpu(False)
    want = run(cfg.make_extend_fn())
    assert len(on_the_chip) == 2
    for real, call, dense in zip(valid, got, want):
        for a, b in zip(call[:2], dense[:2]):           # logits, hidden: [b, tc, ...]
            np.testing.assert_allclose(np.asarray(a)[real], np.asarray(b)[real], atol=3e-5, rtol=3e-5)
        for a, b in zip(call[2:], dense[2:]):           # K and V: [layers, b, tc, ...]
            np.testing.assert_allclose(
                np.asarray(a)[:, real], np.asarray(b)[:, real], atol=3e-5, rtol=3e-5)
    wide = cohere2_moe_nano(sliding_window=256)
    unwindowed = _command_a_plus_calls(wide)[0](wide.make_extend_fn())
    assert np.abs(np.asarray(unwindowed[1][0])[0, -1] - np.asarray(want[1][0])[0, -1]).max() > 1e-2


def test_command_a_plus_decode_call_never_reaches_the_kernel(
        on_the_chip, built_for_tpu, monkeypatch):
    """One query a lane (``tc == 1``) attends densely on the chip too
    (``layers.plain_attend``): the program is the one built off the chip."""
    from ray_tpu.ops import attention

    def never(*a, **kw):
        raise AssertionError("a decode call attends in layers.plain_attend")

    monkeypatch.setattr(attention, "masked_attention", never)
    cfg = cohere2_moe_nano()
    params = cfg.init_params(0)
    cache = jax.random.normal(
        jax.random.PRNGKey(2), (cfg.num_layers, 2, 64, cfg.kv_heads, cfg.head_dim), cfg.dtype)
    call = (params, jnp.asarray([[5], [7]]), jnp.asarray([40, 3], jnp.int32), cache, cache)
    got = cfg.make_extend_fn()(*call)
    built_for_tpu(False)
    for a, b in zip(got, cfg.make_extend_fn()(*call)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    built_for_tpu(True)
    with pytest.raises(AssertionError, match="plain_attend"):      # and two queries a lane do
        cfg.make_extend_fn()(params, jnp.asarray([[5, 6], [7, 8]]), *call[2:])


def test_the_train_step_multiplies_the_head_three_times():
    """Logits, the hidden state's gradient and the kernel's: the loss makes its
    gradients from the logits it has, where a rematerialized chunk multiplied a
    fourth time to have them again."""
    vocab = 384     # no other dimension of the step
    assert len(_products_with(_train_step_program(vocab_size=vocab).jaxpr, vocab)) == 3


def test_the_backward_layer_runs_no_forward_kernel(built_for_tpu):
    """The layer's remat keeps the forward kernel's output and logsumexp, so
    the step holds the kernel once (the forward's), and dq and dk/dv once."""
    built_for_tpu(True)
    kernels = sorted(
        eqn.params["name"] for eqn in _equations(_train_step_program().jaxpr)
        if eqn.primitive.name == "pallas_call")
    assert kernels == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_gradient_with_the_kernels_residuals_kept_is_the_gradient_without_remat(
        built_for_tpu, monkeypatch):
    """What the policy keeps is what the backward kernels would have been given
    anyway: the same gradient as with no remat at all, to float32 rounding. The
    kernels run interpreted, so that the names are in the program."""
    from ray_tpu.ops import attention

    built_for_tpu(True)
    real = attention.flash_attention
    monkeypatch.setattr(
        attention, "flash_attention",
        lambda q, k, v, causal, scale, block_q, block_k, interpret: real(
            q, k, v, causal, scale, 32, 32, True))
    cfg = dataclasses.replace(gpt_nano(), num_heads=2, head_dim=64)
    params = init_params(cfg, jax.random.PRNGKey(0), (2, 64))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)

    def gradient(remat):
        model = GPT(dataclasses.replace(cfg, remat=remat))
        program = jax.make_jaxpr(jax.grad(
            lambda p: next_token_loss(model.apply({"params": p}, tokens), tokens)))(params)
        names = [
            eqn.params["name"] for eqn in _equations(program.jaxpr)
            if eqn.primitive.name in ("pallas_call", "name")]
        return jax.core.eval_jaxpr(program.jaxpr, program.consts, *jax.tree.leaves(params)), names

    kept, names = gradient(remat=True)
    plain, _ = gradient(remat=False)
    assert names.count("flash_fwd") == 1 and {"flash_out", "flash_lse"} <= set(names)
    for a, b in zip(kept, plain):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


_LOSS_CHUNK = 8


@pytest.mark.parametrize("head", ["bias", "no-bias", "tied"])
@pytest.mark.parametrize("length", [2 * _LOSS_CHUNK + 1, 2 * _LOSS_CHUNK + 5], ids=["whole", "pads"])
@pytest.mark.parametrize("masked", ["no-mask", "stretch", "all"])
def test_blockwise_loss_and_its_gradients_are_the_full_logits(masked, length, head):
    """Value and gradients of the loss that makes its gradients chunk by chunk
    against autodiff of the plain loss over the whole float32 logits, under a
    cotangent other than 1."""
    b, d, vocab = 2, 16, 40
    keys = jax.random.split(jax.random.PRNGKey(length), 4)
    hidden = jax.random.normal(keys[0], (b, length, d), jnp.float32)
    # the tied head is the embedding, transposed where the model transposes it
    weight = 0.3 * jax.random.normal(keys[1], (vocab, d) if head == "tied" else (d, vocab))
    bias = 0.1 * jax.random.normal(keys[2], (vocab,)) if head == "bias" else None
    tokens = jax.random.randint(keys[3], (b, length), 0, vocab)
    mask = {
        "no-mask": None,
        "stretch": jnp.ones((b, length), jnp.int32).at[:, 3:_LOSS_CHUNK + 2].set(0),
        "all": jnp.zeros((b, length), jnp.int32),
    }[masked]

    def kernel_of(weight):
        return weight.T if head == "tied" else weight

    def blockwise(hidden, weight, bias):
        return 2.5 * blockwise_next_token_loss(
            hidden, kernel_of(weight), bias, tokens, mask, chunk=_LOSS_CHUNK)

    def plain(hidden, weight, bias):
        logits = hidden @ kernel_of(weight)
        return 2.5 * next_token_loss(logits if bias is None else logits + bias, tokens, mask)

    wrt = (0, 1, 2) if bias is not None else (0, 1)
    value, gradients = jax.value_and_grad(blockwise, wrt)(hidden, weight, bias)
    expected, expected_gradients = jax.value_and_grad(plain, wrt)(hidden, weight, bias)
    np.testing.assert_allclose(value, expected, rtol=1e-6, atol=1e-6)
    if masked == "all":
        assert float(value) == 0.0
    for got, want in zip(gradients, expected_gradients):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if masked == "all":
        assert all(float(jnp.abs(g).max()) == 0.0 for g in gradients)
    else:
        assert float(jnp.abs(gradients[1]).max()) > 1e-3
    # undifferentiated (an eval step), it is the forward scan: the logits' product alone
    undifferentiated = jax.make_jaxpr(blockwise)(hidden, weight, bias)
    assert len(_products_with(undifferentiated.jaxpr, vocab)) == 1
    assert len(_products_with(jax.make_jaxpr(jax.grad(blockwise, wrt))(
        hidden, weight, bias).jaxpr, vocab)) == 3


def test_blockwise_loss_sums_the_head_gradient_in_float32():
    """With a bfloat16 head the chunks' shares of the kernel's gradient are
    added in float32 and rounded once: nearer the float32 gradient than a sum
    rounded a chunk at a time can be relied on to be, and in the kernel's dtype."""
    b, t, d, vocab = 2, 8 * _LOSS_CHUNK + 1, 16, 40
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(keys[0], (b, t, d), jnp.bfloat16)
    kernel = (0.3 * jax.random.normal(keys[1], (d, vocab))).astype(jnp.bfloat16)
    tokens = jax.random.randint(keys[2], (b, t), 0, vocab)
    got = jax.grad(
        lambda k: blockwise_next_token_loss(hidden, k, None, tokens, chunk=_LOSS_CHUNK))(kernel)
    want = jax.grad(lambda k: next_token_loss(
        hidden.astype(jnp.float32) @ k, tokens))(kernel.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    # one rounding of the sum to bfloat16, and the cotangent's rounding in each product
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2e-2, atol=2e-4)


def _block_and_its_parts(moe, dtype):
    """A ``Block`` with every bias drawn at random (they initialise to zero,
    and ``mlp/wo/bias`` is what the sum is arranged around), its input, and
    ``x + attn(h) + mlp(h)`` computed from the parts the plain way."""
    from ray_tpu.models import gpt
    from ray_tpu.models.moe import MoeMlp

    cfg = dataclasses.replace(
        gpt_nano(), dtype=dtype, **(dict(moe_num_experts=4, moe_top_k=2) if moe else {}))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.embed_dim), dtype)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    block = gpt.Block(cfg)
    params = nn.meta.unbox(block.init(jax.random.PRNGKey(1), x, positions)["params"])
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: 0.1 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if path[-1].key == "bias" else leaf, params)
    out = block.apply({"params": params}, x, positions, mutable=["losses"])[0]
    hidden = gpt._layer_norm(cfg, "ln").apply({"params": params["ln"]}, x)
    attn = gpt.Attention(cfg).apply({"params": params["attn"]}, hidden, positions)
    mlp = (MoeMlp(cfg) if moe else gpt.Mlp(cfg)).apply(
        {"params": params["mlp"]}, hidden, mutable=["losses"])[0]
    return out, x + attn + mlp, params


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_block_is_x_plus_attention_plus_mlp(moe, dtype):
    """The block sums attention's output and the MLP's product before the
    MLP's bias (one tp all-reduce a layer): the same mathematics as
    ``x + attn(h) + mlp(h)``, to the rounding of a different order of adds."""
    out, plain, params = _block_and_its_parts(moe, dtype)
    assert out.dtype == plain.dtype == dtype
    if not moe:
        assert float(jnp.abs(params["mlp"]["wo"]["bias"]).max()) > 0.01
    tolerance = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(plain, np.float32), **tolerance)


def test_param_tree_of_a_layer_is_where_checkpoints_and_extend_find_it():
    params = init_params(gpt_nano(), jax.random.PRNGKey(0), (1, 8))
    layer = params["blocks"]["layers"]
    paths = {
        "/".join(k.key for k in path) for path, _ in jax.tree_util.tree_leaves_with_path(layer)}
    assert paths == {
        "ln/scale", "ln/bias",
        "attn/q/kernel", "attn/k/kernel", "attn/v/kernel", "attn/o/kernel",
        "mlp/wi/kernel", "mlp/wi/bias", "mlp/wo/kernel", "mlp/wo/bias",
    }


class _DescribedMesh:
    """What ``compiler_options`` reads of a mesh: its devices' platform and
    its axis sizes (``tests/test_chip_compile.py`` hands it the TPU compiler's
    own described devices)."""

    def __init__(self, platform, **axes):
        import types

        self.shape = {"dp": 1, "fsdp": 1, "tp": 1, **axes}
        self.devices = np.empty(tuple(self.shape.values()), object)
        self.devices[...] = types.SimpleNamespace(platform=platform)


@pytest.mark.parametrize(
    "mesh,some",
    [
        (lambda: None, False),
        (lambda: MeshSpec().build(jax.devices()[:1]), False),
        (lambda: MeshSpec(dp=-1).build(), False),
        (lambda: MeshSpec(dp=-1, fsdp=2, tp=2).build(), False),
        (lambda: _DescribedMesh("tpu"), False),
        (lambda: _DescribedMesh("tpu", dp=4), False),
        (lambda: _DescribedMesh("tpu", dp=2, tp=2), False),
        (lambda: _DescribedMesh("cpu", fsdp=2, tp=2), False),
        (lambda: _DescribedMesh("tpu", fsdp=2, tp=2), True),
        (lambda: _DescribedMesh("tpu", fsdp=4), True),
    ],
    ids=["no-mesh", "one-device", "cpu-dp-only", "cpu-fsdp-tp", "tpu-one-device", "tpu-dp-only",
         "tpu-dp-tp", "cpu-described-fsdp", "tpu-fsdp-tp", "tpu-fsdp"],
)
def test_compiler_options_are_read_from_the_mesh(mesh, some):
    """Options for the TPU compiler only where the mesh's own devices are TPUs
    and it has an fsdp axis to reduce gradients over; the CPU compiler refuses
    an ``xla_tpu_*`` name ("No such compile option")."""
    from ray_tpu._private import accelerator

    options = accelerator.compiler_options(mesh())
    assert bool(options) == some
    assert all(name.startswith("xla_tpu_") for name in options)
