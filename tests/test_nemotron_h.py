"""Nemotron-3-Nano's block on the train path at a small size, in float32 on the CPU,
against the plain reference (``benchmark/reference/nemotron_h_reference.py``, which
knows nothing of ``models/nemotron_h.py``, ``models/moe.py`` or the chunked scan): loss
and every parameter family's gradient, the chunked scan with and without groups and the
TPU's kernel pair for it (``ssm_scan``, interpreted) against the recurrence a token at a
time, the eight shares tied to the uncut layer, the un-gated expert layer against a loop
over experts, one whole step.

Tolerances: both sides compute in float32 at the CPU's full matmul precision and
differ in the order of their sums alone (a chunked scan against a token at a time; a
sort and a ragged dot against a loop over experts with dense masks; a blockwise loss
against full logits; a remat), so a loss agrees to a few float32 roundings (2e-6 of
itself) and a gradient to 1e-5 of its largest entry. The chunked scan sums a
sub-chunk's 8 tokens in another order than the recurrence and passes ``exp`` of
differences where the recurrence multiplies decays: 1e-5 of the largest entry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h_reference as reference
from ray_tpu.models import moe, nemotron_h
from ray_tpu.models.gpt import blockwise_next_token_loss
from ray_tpu.models import granitemoehybrid
from ray_tpu.models.granitemoehybrid import ssm_chunked, ssm_conv, ssm_gate_norm, ssm_scan
from ray_tpu.models.training import default_optimizer, init_sharded_state, make_train_step
from ray_tpu.parallel.mesh import MeshSpec

BATCH = (2, 32)


def model_keys(cfg):
    """The reference's view of ``cfg``: the published keys it reads."""
    return {
        "hybrid_override_pattern": cfg.pattern, "mamba_num_heads": cfg.ssm_heads,
        "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
        "ssm_state_size": cfg.ssm_state, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.experts_per_token, "routed_scaling_factor": cfg.routed_scale,
        "norm_topk_prob": True, "expert_offset": cfg.expert_offset, "norm_eps": cfg.norm_eps,
        "layer_norm_epsilon": cfg.norm_eps,
    }


def program_loss(cfg, params, tokens):
    (hidden, kernel, bias), aux, counters = nemotron_h.forward(cfg, params, tokens)
    return blockwise_next_token_loss(hidden, kernel, bias, tokens) + aux, counters


def close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rel * max(np.abs(want).max(), 1e-30), (
        np.abs(got - want).max(), np.abs(want).max())


@pytest.fixture(scope="module")
def nano():
    cfg = nemotron_h.nemotron_h_nano()
    params = jax.jit(lambda rng: nemotron_h.init_params(cfg, rng))(jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), BATCH, 0, cfg.vocab_size)
    return cfg, params, tokens


@pytest.fixture(scope="module")
def both_gradients(nano):
    """Loss and gradients of the program and of the reference, each by ``jax.grad`` of
    its own forward, laid out as the program's parameters."""
    cfg, params, tokens = nano
    bias = params["expert_bias"]
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    (loss, _), grads = jax.value_and_grad(
        lambda t: program_loss(cfg, {**t, "expert_bias": bias}, tokens), has_aux=True)(trained)
    want, ref_grads = jax.value_and_grad(lambda t: reference.loss(
        reference.from_program_params({**t, "expert_bias": bias}), tokens, model_keys(cfg)))(trained)
    return (loss, grads), (want, ref_grads)


def test_the_loss_is_the_references(both_gradients):
    (loss, _), (want, _) = both_gradients
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


# every parameter family, by the names its arrays have in a layer's tree (or at the top)
FAMILIES = {
    "W_in": ("in",), "taps": ("conv", "conv_bias"), "dt_bias": ("dt_bias",), "A_log": ("A_log",),
    "D": ("D",), "group_norm": ("norm",), "W_out": ("out",), "layer_norms": ("ln",),
    "router": ("router",), "held_experts": ("wi", "wo"),
    "shared_expert": ("shared_wi", "shared_wo"), "attention": ("q", "k", "v", "o"),
    "embedding": ("wte",), "head": ("head",), "final_norm": ("ln_f",),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_familys_gradient_is_the_references(both_gradients, family):
    (_, grads), (_, ref_grads) = both_gradients
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    compared = 0
    for (path, got), want in zip(leaves, jax.tree.leaves(ref_grads)):
        if path[-1].key in FAMILIES[family]:
            assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
            close(got, want, 1e-5)
            compared += 1
    assert compared >= len(FAMILIES[family])
    # and no array of the model is of no family
    assert {path[-1].key for path, _ in leaves} == {n for names in FAMILIES.values() for n in names}


def test_the_counts_are_the_published_models_and_the_cuts(nano):
    cfg, params, _ = nano
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))
    assert nemotron_h.NemotronHConfig().num_params() == 31_577_940_288
    cut = nemotron_h.NemotronHConfig(vocab_size=16384, pattern="MEMEM*EME", num_experts=16)
    assert cut.num_params() == 986_254_848
    with pytest.raises(ValueError, match="not among the 128"):
        dataclasses.replace(cut, expert_offset=113)
    with pytest.raises(ValueError, match="not M, E or"):
        dataclasses.replace(cut, pattern="ME-")


# -- the chunked scan ------------------------------------------------------------------


def scan_inputs(groups, lanes=2, t=32, heads=8, p=8, n=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (lanes, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (lanes, t, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    shape = (lanes, t, n) if groups is None else (lanes, t, groups, n)
    b, c = jax.random.normal(keys[3], shape), jax.random.normal(keys[4], shape)
    return x, dt, a, b, c, jax.random.normal(keys[5], (lanes, t, heads, p))


def chunked(x, dt, a, b, c, chunk=8):
    state = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]), jnp.float32)
    return ssm_chunked(state, x, dt, a, b, c, chunk, jnp.float32)[0]


def token_by_token(x, dt, a, b, c):
    """The reference's recurrence, each head handed its group's B and C."""
    heads = x.shape[2]
    if b.ndim == 3:
        b, c = b[:, :, None], c[:, :, None]
    b, c = (jnp.repeat(v, heads // v.shape[2], axis=2) for v in (b, c))
    return reference.recurrence(x, dt, a, b, c)


def is_the_recurrence(scan, inputs, weigh):
    """Values and the gradient of every input (``x``, ``dt``, ``A``, ``B``, ``C``)."""
    got, got_grads = jax.value_and_grad(
        lambda *v: (scan(*v) * weigh).sum(), argnums=range(5))(*inputs)
    want, want_grads = jax.value_and_grad(
        lambda *v: (token_by_token(*v) * weigh).sum(), argnums=range(5))(*inputs)
    close(scan(*inputs), token_by_token(*inputs), 1e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
    for g, w in zip(got_grads, want_grads):
        close(g, w, 2e-5)


@pytest.mark.parametrize("groups", [None, 1, 4, 8], ids=["no-axis", "1-group", "4-groups", "8-groups"])
def test_the_chunked_scan_is_the_recurrence_a_token_at_a_time(groups):
    """With B and C in groups, in one group and as granite hands them over (no group
    axis)."""
    *inputs, weigh = scan_inputs(groups)
    is_the_recurrence(chunked, inputs, weigh)


# the TPU's kernel pair, interpreted: what a train step runs on the chip


def kernels(x, dt, a, b, c, state=None, heads=8):
    """``(y, last)`` of ``ssm_scan``'s kernels, sub-chunks of 8, from zeros unless told."""
    if state is None:
        state = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]), jnp.float32)
    return ssm_scan(state, x, dt, a, b, c, 8, jnp.float32, heads=heads, interpret=True)


def in_two_calls(x, dt, a, b, c):
    """The second half from the state the first left: a state that is not zeros comes
    in, and its gradient carries the second half's back into the first."""
    first, state = kernels(x[:, :16], dt[:, :16], a, b[:, :16], c[:, :16])
    second, _ = kernels(x[:, 16:], dt[:, 16:], a, b[:, 16:], c[:, 16:], state)
    return jnp.concatenate([first, second], axis=1)


@pytest.mark.parametrize("calls", [1, 2], ids=["from-zeros", "from-a-state"])
@pytest.mark.parametrize("groups", [1, 4, 8], ids=["1-group", "4-groups", "8-groups"])
def test_the_scan_kernels_are_the_recurrence_a_token_at_a_time(groups, calls):
    *inputs, weigh = scan_inputs(groups)
    is_the_recurrence(in_two_calls if calls == 2 else lambda *v: kernels(*v)[0], inputs, weigh)


@pytest.mark.parametrize("heads", [4, 2], ids=["2-blocks-a-group", "4-blocks-a-group"])
def test_a_group_in_several_blocks_of_heads_is_the_group_in_one(heads):
    """A grid step holds at most ``heads`` heads: a group of more is several blocks,
    each with the group's B and C, whose gradients are summed over the blocks."""
    *inputs, weigh = scan_inputs(1)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 16))

    def loss(heads, state, *v):
        y, last = kernels(*v, state, heads)
        return (y * weigh).sum() + (last * state).sum()

    got = jax.grad(loss, argnums=range(1, 7))(heads, state, *inputs)
    want = jax.grad(loss, argnums=range(1, 7))(8, state, *inputs)
    for g, w in zip(got, want):
        close(g, w, 1e-6)
    for g, w in zip(kernels(*inputs, state, heads), kernels(*inputs, state)):
        close(g, w, 1e-6)


def test_the_scan_kernels_in_bfloat16_are_the_loop_in_bfloat16():
    """The compute type a train step states: the kernels round where the loop rounds
    (the operands of the pairs, of the masked product and of the state's feed), so the
    two stay a rounding of bfloat16 apart, values and gradients."""
    x, dt, a, b, c, weigh = scan_inputs(4)
    x, b, c = (v.astype(jnp.bfloat16) for v in (x, b, c))
    state = jnp.zeros((2, 8, 8, 16), jnp.float32)

    def loss(scan, *v):
        y, last = scan(state, *v, 8, jnp.bfloat16)[:2]
        return (y * weigh).sum() + last.sum()

    got = jax.value_and_grad(
        lambda *v: loss(lambda *w: ssm_scan(*w, interpret=True), *v), argnums=range(5))(x, dt, a, b, c)
    want = jax.value_and_grad(lambda *v: loss(ssm_chunked, *v), argnums=range(5))(x, dt, a, b, c)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(g.astype(jnp.float32), w.astype(jnp.float32), 2e-2)


def test_a_padded_token_changes_no_state_in_the_kernels():
    x, dt, a, b, c, _ = scan_inputs(4)
    padded = jnp.zeros((32,), bool).at[jnp.array([5, 6, 16, 31])].set(True)
    y, last = kernels(x, jnp.where(padded[:, None], 0.0, dt), a, b, c)
    real = ~np.asarray(padded)
    # the 28 real tokens alone, as 32 with the padding at the end (dt = 0 there too)
    alone = tuple(
        jnp.concatenate([v[:, real], jnp.zeros_like(v[:, :4])], axis=1) for v in (x, dt, b, c))
    y_alone, last_alone = kernels(alone[0], alone[1], a, *alone[2:])
    close(last, last_alone, 1e-5)
    close(y[:, real], y_alone[:, :28], 1e-5)


@pytest.mark.parametrize("groups", [1, 4], ids=["1-group", "4-groups"])
def test_the_kernels_keep_the_state_before_each_sub_chunk(groups):
    """What the backward rebuilds a sub-chunk from: ``between[i]`` is the state before
    sub-chunk ``i``, the caller's before the first."""
    x, dt, a, b, c, _ = scan_inputs(groups)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 16))
    y, last, between = granitemoehybrid._ssm_scan_call(
        *granitemoehybrid._ssm_scan_operands(state, x, dt, a, b, c, 8, 8 // groups),
        chunk=8, dtype=jnp.float32, keep=True, interpret=True)
    want_y, want_last, after = ssm_chunked(state, x, dt, a, b, c, 8, jnp.float32)
    # [lanes, blocks, sub-chunks, n, heads x p] -> [sub-chunks, lanes, heads, p, n]
    before = granitemoehybrid._scan_heads_of(jnp.moveaxis(between, 2, 0), 8 // groups)
    np.testing.assert_array_equal(before[0], state)
    close(before[1:], after[:-1], 1e-6)
    close(granitemoehybrid._scan_heads_of(last, 8 // groups), want_last, 1e-6)
    close(y.reshape(x.shape), want_y, 1e-6)


def test_the_mixer_runs_the_kernels_on_the_tpu_and_the_loop_elsewhere(nano, built_for_tpu):
    """``mamba_mixer`` asks the platform, nobody else: built for the TPU its scan and
    the pointwise stage on either side of it are kernel pairs (interpreted here), forward
    and backward, and agree with the loop and the ``jax.numpy`` lines it is built with on
    the CPU."""
    from jax.experimental.pallas import tpu as pltpu

    cfg, params, _ = nano
    p = params["layers"][0]
    r = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.embed_dim))

    def loss(p, r):
        return (nemotron_h.mamba_mixer(cfg, p, r) ** 2).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, r)
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss))(p, r))
    built_for_tpu(True)
    with pltpu.force_tpu_interpret_mode():
        text = str(jax.make_jaxpr(jax.grad(loss))(p, r))
        for stage in ("ssm_conv", "ssm_scan", "ssm_gate_norm"):     # the head, the scan, the tail
            assert text.count(stage + "_fwd") >= 1 and text.count(stage + "_bwd") >= 1, stage
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, r)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(g, w, 1e-5)


def stage_inputs(stage, groups, dtype=jnp.float32, lanes=2, t=32, heads=8, p=8, n=16, seed=0):
    """One pointwise stage of the mixer as ``(fn(*operands, **blocks), operands)``: the
    ``head`` (``ssm_conv``: ``xbc``, the taps, the bias -> ``x``, ``b``, ``c``) or the
    ``tail`` (``ssm_gate_norm``: ``y``, ``x``, ``z``, ``D``, the norm's weight)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    inner, channels = heads * p, heads * p + 2 * groups * n
    if stage == "head":
        bound = 4 ** -0.5
        return (lambda *v, **blocks: ssm_conv(*v, inner, **blocks)), (
            jax.random.normal(keys[0], (lanes, t, channels)).astype(dtype),
            jax.random.uniform(keys[1], (4, channels), jnp.float32, -bound, bound),
            jax.random.uniform(keys[2], (channels,), jnp.float32, -bound, bound))
    return (lambda *v, **blocks: (ssm_gate_norm(*v, groups, 1e-5, **blocks),)), (
        jax.random.normal(keys[0], (lanes, t, inner)),
        *(jax.random.normal(k, (lanes, t, inner)).astype(dtype) for k in keys[1:3]),
        1.0 + 0.1 * jax.random.normal(keys[3], (heads,)),
        (1.0 + 0.1 * jax.random.normal(keys[4], (inner,))).astype(dtype))


STAGE_CASES = {     # groups, operands' dtype, rows a grid step, the most apart (of the largest entry)
    "1-group": (1, jnp.float32, 32, 2e-6),
    "8-groups": (8, jnp.float32, 32, 2e-6),
    # two blocks of rows a lane: the head's halo crosses a block's edge, forward (the
    # three rows before a block) and backward (the three after it), and the parameters'
    # gradients are summed over the blocks
    "blocks-of-rows": (4, jnp.float32, 16, 2e-6),
    # the compute type a train step states. The kernels sum float32 and round once where
    # plain autodiff rounds each tap's product of the head's ``dxbc`` to bfloat16 first
    "bfloat16": (4, jnp.bfloat16, 16, 1e-2),
    # as the mixer calls them: ``xbc`` and ``z`` are columns of the in-projection's result
    # and the kernels read them there, so the gradient of the cut is the kernels' own
    "cut-from-a-wider-array": (4, jnp.float32, 16, 2e-6),
}


@pytest.mark.parametrize("case", list(STAGE_CASES))
@pytest.mark.parametrize("stage", ["head", "tail"])
def test_a_stages_kernel_pair_is_its_jax_numpy_lines(stage, case):
    """Each pointwise stage of the mixer, the kernel pair (interpreted) against the
    ``jax.numpy`` lines it replaces on the TPU: every result and the gradient of every
    input, the taps, the bias, ``D`` and the norm's weight among them, two lanes."""
    groups, dtype, rows, apart = STAGE_CASES[case]
    fn, operands = stage_inputs(stage, groups, dtype)
    weigh = [jax.random.normal(jax.random.PRNGKey(7 + i), out.shape)
             for i, out in enumerate(jax.eval_shape(fn, *operands))]

    def loss(*v, **blocks):
        outs = fn(*v, **blocks)
        return sum((out.astype(jnp.float32) * w).sum() for out, w in zip(outs, weigh)), outs

    def results_and_gradients(**blocks):
        (_, outs), grads = jax.value_and_grad(
            lambda *v: loss(*v, **blocks), argnums=range(len(operands)), has_aux=True)(*operands)
        return outs, grads

    blocks = dict(rows=rows, interpret=True)
    if case == "cut-from-a-wider-array":
        cut = operands[0 if stage == "head" else 2]
        wide = jnp.concatenate([cut[..., :64] + 1.0, cut, cut[..., :16] - 1.0], axis=-1)
        blocks["within"] = (wide, 64)
    want, got = results_and_gradients(), results_and_gradients(**blocks)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        close(g.astype(jnp.float32), w.astype(jnp.float32), apart)


@pytest.mark.parametrize("rows", [32, 16], ids=["one-block", "blocks-of-rows"])
def test_a_lanes_first_rows_read_zeros_and_not_the_lane_befores_last(rows):
    """The head's taps reach three rows back: at a sequence's start those are zeros, in
    the second lane too, whose rows lie behind the first lane's in the array; and the
    backward's taps reach three rows on, past a sequence's end to nothing. A lane alone
    gives the bits it gives beside another."""
    fn, (xbc, taps, bias) = stage_inputs("head", 4)

    def run(xbc):
        loss = lambda xbc: sum((out ** 2).sum() for out in fn(xbc, taps, bias, rows=rows, interpret=True))
        return jax.grad(loss)(xbc), fn(xbc, taps, bias, rows=rows, interpret=True)

    both = run(xbc)
    for lane in range(2):
        alone = run(xbc[lane:lane + 1])
        for g, w in zip(jax.tree.leaves(both), jax.tree.leaves(alone)):
            np.testing.assert_array_equal(g[lane:lane + 1], w)


def test_groups_matter_and_one_group_is_no_axis():
    x, dt, a, b, c, _ = scan_inputs(4)
    first = tuple(jnp.broadcast_to(v[:, :, :1], v.shape) for v in (b, c))
    assert float(jnp.abs(chunked(x, dt, a, b, c) - chunked(x, dt, a, *first)).max()) > 0.1
    # all heads on group 0 is what the form without the axis computes, bit for bit
    np.testing.assert_array_equal(
        chunked(x, dt, a, b[:, :, :1], c[:, :, :1]), chunked(x, dt, a, b[:, :, 0], c[:, :, 0]))


@pytest.mark.parametrize("groups", [None, 4], ids=["no-axis", "4-groups"])
def test_a_sub_chunks_result_does_not_depend_on_where_in_the_call_it_lies(groups):
    x, dt, a, b, c, _ = scan_inputs(groups)
    state = jnp.zeros((2, 8, 8, 16), jnp.float32)
    y, _, between = ssm_chunked(state, x, dt, a, b, c, 8, jnp.float32)
    third = slice(16, 24)
    alone, after, _ = ssm_chunked(
        between[1], x[:, third], dt[:, third], a, b[:, third], c[:, third], 8, jnp.float32)
    np.testing.assert_array_equal(alone, y[:, third])
    np.testing.assert_array_equal(after, between[2])


def test_a_state_kept_in_bfloat16_moves_the_recurrence_past_the_tolerance():
    x, dt, a, b, c, _ = scan_inputs(8)
    want = token_by_token(x, dt, a, b, c)
    rounded = reference.recurrence(x, dt, a, b, c, round_state=True)    # 8 heads, 8 groups
    assert float(jnp.abs(rounded - want).max()) > 100 * 1e-5 * float(jnp.abs(want).max())


# -- the expert layer ------------------------------------------------------------------


def loop_over_experts(x, weights, chosen, wi, wo, offset, activation):
    out = jnp.zeros((x.shape[0], wo.shape[-1]), jnp.float32)
    for e in range(wi.shape[0]):
        mask = (weights * (chosen == offset + e)).sum(-1)
        out = out + mask[:, None] * (activation(x @ wi[e]) @ wo[e])
    return out


@pytest.mark.parametrize("offset", [0, 8], ids=["first-share", "a-middle-share"])
@pytest.mark.parametrize(
    "activation,columns", [(moe.relu_squared, 1), (moe.gated_silu, 2)], ids=["relu2", "gated"])
def test_the_trained_expert_layer_is_a_loop_over_the_held_experts(activation, columns, offset):
    """Values and gradients, with pairs of absent experts present: 4 of 16 held."""
    n, d, f, k = 48, 16, 24, 3
    keys = jax.random.split(jax.random.PRNGKey(offset), 5)
    x = jax.random.normal(keys[0], (n, d))
    wi = 0.3 * jax.random.normal(keys[1], (4, d, columns * f))
    wo = 0.3 * jax.random.normal(keys[2], (4, f, d))
    weights, chosen = moe.sigmoid_bias_top_k(
        x, jax.random.normal(keys[3], (d, 16)), 0.1 * jax.random.normal(keys[4], (16,)), k, 2.5)
    held = (chosen >= offset) & (chosen < offset + 4)
    assert 0 < int(held.sum()) < n * k          # some pairs are held here, some elsewhere

    def program(x, weights, wi, wo):
        return moe.trained_experts_ffn(
            x, weights, chosen, wi, wo, offset, activation=activation, routed=16)[0]

    def plain(x, weights, wi, wo):
        return loop_over_experts(x, weights, chosen, wi, wo, offset, activation)

    weigh = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    close(program(x, weights, wi, wo), plain(x, weights, wi, wo), 1e-5)
    got = jax.grad(lambda *v: (program(*v) * weigh).sum(), argnums=range(4))(x, weights, wi, wo)
    want = jax.grad(lambda *v: (plain(*v) * weigh).sum(), argnums=range(4))(x, weights, wi, wo)
    for g, w in zip(got, want):
        close(g, w, 1e-5)
    counters = moe.trained_experts_ffn(
        x, weights, chosen, wi, wo, offset, activation=activation, routed=16)[1]
    assert int(counters[1]) == int(held.sum())


SHARES = {"none": 0, "an-eighth": 1, "a-half": 4, "all": 8}        # held, of 8 experts routed


@pytest.mark.parametrize("form", ["whole-forward", "held-blocks"])
@pytest.mark.parametrize("kernel", ["ragged", "interpreted", "poisoned", "poisoned-3e38"])
@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize(
    "activation,columns", [(moe.relu_squared, 1), (moe.gated_silu, 2)], ids=["relu2", "gated"])
def test_at_every_held_share_the_layer_is_one_expert_at_a_time(
        activation, columns, share, kernel, form, monkeypatch):
    """Every token chooses 2 of 8 experts evenly, and the experts from 0 on are held:
    none of them (no block walked: zeros, and every gradient zero and finite), an eighth
    of the pairs, a half, all. The result and the gradients of the tokens, the weights
    and both expert stacks are those of one expert at a time over every token, through
    the ragged dot, through the TPU's kernels interpreted, and with every row of no
    group poisoned in every intermediate before a pass could read it (NaN; 3e38, whose
    sums are not finite), which is how a pass that walked a row of no group would show.
    In both forms of the layer, whatever share of the pairs the routing then holds:
    ``routed`` says a quarter of the scored experts is held here (every pass walks the
    held blocks alone, this model's cell), or says nothing (the forward's are whole)."""
    from test_lfm2_moe import KERNELS

    n, d, f, k, routed = 64, 16, 24, 2, 8
    held = SHARES[share]
    keys = jax.random.split(jax.random.PRNGKey(held), 5)
    x = jax.random.normal(keys[0], (n, d))
    wi = 0.3 * jax.random.normal(keys[1], (max(held, 1), d, columns * f))
    wo = 0.3 * jax.random.normal(keys[2], (max(held, 1), f, d))
    weights = jax.random.uniform(keys[3], (n, k), minval=0.2)
    at = jax.random.permutation(keys[4], n)
    first = at % routed
    chosen = jnp.stack([first, (first + 1 + at // routed % (routed - 1)) % routed], 1)
    offset = 0 if held else routed                   # no expert held: the one here is nobody's
    pairs = int(((chosen >= offset) & (chosen < offset + wi.shape[0])).sum())
    assert pairs == n * k * held // routed

    def program(x, weights, wi, wo):
        return moe.trained_experts_ffn(
            x, weights, chosen, wi, wo, offset, tiling=(16, 32, 128), activation=activation,
            routed=4 * wi.shape[0] if form == "held-blocks" else None)

    def plain(x, weights, wi, wo):
        return loop_over_experts(x, weights, chosen, wi, wo, offset, activation)

    weigh = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    want = jax.value_and_grad(
        lambda *v: (lambda y: ((y * weigh).sum(), y))(plain(*v)), range(4), has_aux=True)(
            x, weights, wi, wo)
    KERNELS[kernel](monkeypatch)
    (_, (y, counters)), grads = jax.value_and_grad(
        lambda *v: (lambda y, c: ((y * weigh).sum(), (y, c)))(*program(*v)), range(4),
        has_aux=True)(x, weights, wi, wo)
    close(y, want[0][1], 1e-5)
    for g, w in zip(grads, want[1]):
        assert np.isfinite(np.asarray(g)).all()
        close(g, w, 1e-5)
    counted = dict(zip(moe.TRAINED_COUNTERS, map(int, counters)))
    block = moe.row_block(n * k, 16)
    assert counted["moe_assignments"] == pairs
    assert counted["moe_rows_visited"] == -(-pairs // block) * block
    if not pairs:
        assert not np.asarray(y).any() and not any(np.asarray(g).any() for g in grads)


def test_the_eight_shares_add_up_to_the_uncut_layer_and_so_do_their_gradients():
    """ep8 at a small size: eight configurations that differ in ``expert_offset`` alone
    (0, 16, ..., 112), each with its 16 of the 128 experts; the shared expert and the
    residual counted once, their parts sum to what the reference gives with all 128."""
    cfg = nemotron_h.nemotron_h_nano(router_experts=128, num_experts=16, experts_per_token=6)
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    d, f = cfg.embed_dim, cfg.expert_dim
    x = jax.random.normal(keys[0], (2, 24, d))
    whole = {
        "ln": 1.0 + 0.1 * jax.random.normal(keys[1], (d,)),
        "router": jax.random.normal(keys[2], (d, 128)),
        "wi": 0.2 * jax.random.normal(keys[3], (128, d, f)),
        "wo": 0.2 * jax.random.normal(keys[4], (128, f, d)),
        "shared_wi": 0.2 * jax.random.normal(keys[5], (d, cfg.shared_dim)),
        "shared_wo": 0.2 * jax.random.normal(keys[6], (cfg.shared_dim, d)),
    }
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (128,))

    def shared_of(x):
        r = nemotron_h.layers.rms_norm(x, whole["ln"], cfg.norm_eps)
        return moe.relu_squared(r @ whole["shared_wi"]) @ whole["shared_wo"]

    def shares(x):
        total, pairs = x + shared_of(x), 0
        for offset in range(0, 128, 16):
            share = dataclasses.replace(cfg, expert_offset=offset)
            held = slice(offset, offset + 16)
            p = {**whole, "wi": whole["wi"][held], "wo": whole["wo"][held]}
            y, counters, _ = nemotron_h._layer(share, nemotron_h.EXPERTS, x, p, bias)
            total, pairs = total + (y - x - shared_of(x)), pairs + counters[1]
        return total, pairs

    def uncut(x):
        model = {**model_keys(cfg), "expert_offset": 0}
        with jax.default_matmul_precision("highest"):
            return reference.layer_of("E", x, whole, bias, model)

    total, pairs = shares(x)
    assert int(pairs) == 2 * 24 * 6              # every pair is some share's, once
    close(total, uncut(x), 1e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    close(
        jax.grad(lambda x: (shares(x)[0] * weigh).sum())(x),
        jax.grad(lambda x: (uncut(x) * weigh).sum())(x), 1e-5)


def test_the_seeded_bias_moves_the_chosen_set_of_many_tokens(nano):
    cfg, params, tokens = nano
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (512, cfg.embed_dim))
    args = (x, p["router"], params["expert_bias"][0], cfg.experts_per_token, cfg.routed_scale)
    _, with_bias = moe.sigmoid_bias_top_k(*args)
    _, without = moe.sigmoid_bias_top_k(args[0], args[1], jnp.zeros_like(args[2]), *args[3:])
    moved = (jnp.sort(with_bias, -1) != jnp.sort(without, -1)).any(-1).mean()
    assert float(moved) > 0.1


@pytest.mark.parametrize("wrong", [w for w in reference.WRONG if w != "bf16_state"])
def test_each_left_out_mechanism_moves_the_references_loss(nano, wrong):
    """Past twice the tolerance the program's loss is held to (a state kept in bfloat16
    moves this small model's loss less than that: the scan's own test holds it)."""
    cfg, params, tokens = nano
    ref_params = reference.from_program_params(params)
    want = float(reference.loss(ref_params, tokens, model_keys(cfg)))
    reads = float(reference.loss(ref_params, tokens, model_keys(cfg), wrong=wrong))
    assert abs(reads - want) > 2 * 2e-6 * want, (wrong, reads, want)


@pytest.mark.parametrize("rate", [0.0, 0.01], ids=["bias-handed-on", "bias-balanced"])
def test_a_step_trains_counts_and_hands_the_bias_on_or_moves_it_by_the_loads(nano, rate):
    """With no ``bias_update_rate`` the bias leaves a step as it came; with one, every
    scored expert's bias has moved that far towards an even load, by the loads the
    step's own forward counted (the first step's are the initial weights' own), and
    the loads are no scalar the step reports."""
    cfg, _, tokens = nano
    cfg = dataclasses.replace(cfg, bias_update_rate=rate)
    mesh = MeshSpec().build(jax.devices()[:1])
    opt = default_optimizer(1e-3)
    state, shardings = init_sharded_state(cfg, mesh, opt, jax.random.PRNGKey(1), BATCH)
    bias = jax.device_get(state.params["expert_bias"])
    counted = jax.device_get(nemotron_h.forward(cfg, state.params, tokens)[2])
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    losses, after_one = [], None
    with mesh:
        for _ in range(3):
            state, metrics = step(state, tokens)
            after_one = after_one or jax.device_get(state.params["expert_bias"])
            losses.append(float(metrics["loss"]))
    assert losses[2] < losses[1] < losses[0]
    tokens_a_step = BATCH[0] * BATCH[1]
    assert set(moe.TRAINED_COUNTERS) <= set(metrics) and "moe_loads" not in metrics
    assert float(metrics["moe_tokens"]) == 4 * tokens_a_step           # four expert layers
    assert 0 < float(metrics["moe_assignments"]) < 4 * tokens_a_step * cfg.experts_per_token
    if not rate:
        assert "moe_loads" not in counted
        for before, after in zip(bias, jax.device_get(state.params["expert_bias"])):
            np.testing.assert_array_equal(before, after)
    else:
        loads = counted["moe_loads"]
        assert loads.shape == (4, cfg.router_experts)
        assert (loads.sum(-1) == tokens_a_step * cfg.experts_per_token).all()
        held = slice(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
        assert loads[:, held].sum() == counted["moe_assignments"]
        mean = tokens_a_step * cfg.experts_per_token / cfg.router_experts
        for before, after, load in zip(bias, after_one, loads):
            np.testing.assert_allclose(after - before, rate * np.sign(mean - load), atol=1e-7)
    # the bias has no moment: the optimizer's state is of the trained parameters alone
    trained = sum(x.size for k, v in state.params.items() if k != "expert_bias"
                  for x in jax.tree.leaves(v))
    moments = [x.size for x in jax.tree.leaves(state.opt_state) if x.ndim]
    assert sum(moments) == 2 * trained


@pytest.mark.parametrize("rate", [0.001, 0.01])
def test_the_rule_moves_a_bias_against_its_experts_load_and_not_where_it_is_even(rate):
    loads = jnp.asarray([[4, 0, 2, 2], [1, 1, 1, 5]], jnp.int32)
    buffers = {"expert_bias": [jnp.zeros(4), jnp.full(4, 0.5)], "kept": 3}
    after, counted = nemotron_h.balance_bias(rate, buffers, {"moe_loads": loads, "moe_tokens": 8})
    assert counted == {"moe_tokens": 8} and after["kept"] == 3
    np.testing.assert_allclose(after["expert_bias"][0], rate * np.array([-1, 1, 0, 0]), atol=1e-9)
    np.testing.assert_allclose(
        after["expert_bias"][1], 0.5 + rate * np.array([1, 1, 1, -1]), atol=1e-7)


def test_the_rule_evens_a_lopsided_load_and_with_it_the_held_share():
    """A router whose logits share an offset an expert (what a random block's hidden
    rows give it) sends a few experts most of the pairs, and the share of the pairs
    that the first experts hold is whatever those offsets are (0.45 of its expectation
    here); some hundred steps of the rule at 0.01 bring every expert to the mean as
    nearly as steps of 0.01 can (the loads then swing between two states, the busiest
    expert at 1.4 means) and the held share to its expectation."""
    n, scored, k, held = 4096, 32, 4, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    h = jax.random.normal(keys[0], (n, 16))
    router = jax.random.normal(keys[1], (16, scored)) / 4.0
    h = h + 2.0 * jax.random.normal(keys[2], (16,))            # a part every token shares

    @jax.jit
    def loads_under(bias):
        _, chosen = moe.sigmoid_bias_top_k(h, router, bias, k, 2.5)
        return (chosen.reshape(-1, 1) == jnp.arange(scored)).sum(0, dtype=jnp.int32)

    buffers = {"expert_bias": [jnp.zeros(scored)]}
    mean = n * k / scored
    first = loads_under(buffers["expert_bias"][0])
    assert float(first.max()) > 3 * mean and float(first[:held].sum()) < 0.6 * held * mean
    for _ in range(150):
        loads = loads_under(buffers["expert_bias"][0])
        buffers, _ = nemotron_h.balance_bias(0.01, buffers, {"moe_loads": loads[None]})
    last = loads_under(buffers["expert_bias"][0])
    assert float(last.max()) < 1.6 * mean and float(last.min()) > 0.4 * mean
    assert abs(float(last[:held].sum()) / (held * mean) - 1) < 0.15


def test_the_remat_keeps_the_routers_choice_beside_the_grouped_matmuls_results(nano):
    """An expert layer's backward reads the two kept results in the rows the forward's
    choice sorted the pairs into. A replay that ran ``top_k`` again could choose
    otherwise where two experts' biased scores lie an ulp apart, every later group's
    rows would lie one off, and a row of no group, which holds whatever the buffer
    held, would be read as the last group's (on the chip: gradients of 1e29 and NaN
    at one seed's 78th step). So the choice is kept, once a layer, as integers."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg, params, tokens = nano
    bias = params["expert_bias"]
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    kept = saved_residuals(
        lambda t: program_loss(cfg, {**t, "expert_bias": bias}, tokens)[0], trained)
    named = [(str(aval), why.split("'")[1]) for aval, why in kept if "named '" in why]
    pairs = BATCH[0] * BATCH[1] * cfg.experts_per_token
    chosen = (f"int32[{BATCH[0] * BATCH[1]},{cfg.experts_per_token}]", moe.ROUTED)
    assert named.count(chosen) == cfg.pattern.count("E")
    for name in moe.TRAINED_RESIDUALS:
        assert sum(n == name and a.startswith(f"uint32[{pairs},") for a, n in named) == 4


def test_the_backward_runs_no_grouped_matmul_again_and_keeps_no_float_of_a_sorted_row(nano):
    """Four expert layers whose every pass walks the held blocks alone: a forward pair
    of grouped matmuls, the rows' two gradients and the weights' two each, 6, none made
    again by the replay, which reads both results back from the bits it kept; no kept
    value goes through ``reduce_precision`` (they are integers to the remat)."""
    from test_lfm2_moe import _eqns

    cfg, params, tokens = nano
    bias = params["expert_bias"]
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda t: program_loss(cfg, {**t, "expert_bias": bias}, tokens)[0]))(trained).jaxpr
    assert cfg.num_experts <= moe.WALKED_SHARE * cfg.router_experts
    assert _eqns(jaxpr, "ragged_dot_general") == cfg.pattern.count("E") * 6
    assert _eqns(jaxpr, "reduce_precision") == 0
