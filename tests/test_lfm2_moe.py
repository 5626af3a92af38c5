"""LFM2-24B-A2B's block on the train path at a small size, in float32 on the
CPU, against the plain reference (``benchmark/reference/lfm2_moe_reference.py``,
which knows nothing of ``models/lfm2_moe.py`` or ``models/moe.py``): loss and
every parameter's gradient, one whole update, the convolution alone, the bias
as a buffer, the share tied to the uncut layer, and nothing dropped.

Tolerances: both sides compute in float32 at the CPU's full matmul precision and
differ in the order of their sums alone (a sort and a ragged dot against a loop
over experts with dense masks; a blockwise loss against full logits; a remat),
so a loss agrees to a few float32 roundings (1e-6 of itself) and a gradient to
1e-5 of its largest entry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import lfm2_moe_reference as reference
from ray_tpu.models import lfm2_moe, moe
from ray_tpu.models.gpt import blockwise_next_token_loss
from ray_tpu.models.training import default_optimizer, init_sharded_state, make_train_step
from ray_tpu.ops.attention import FLASH_RESIDUALS
from ray_tpu.parallel.mesh import MeshSpec

BATCH = (2, 48)


def model_keys(cfg):
    """The reference's view of ``cfg``: the published keys it reads."""
    return {
        "hidden_size": cfg.embed_dim, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads, "norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_base, "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scale, "router_experts": cfg.router_experts,
        "expert_offset": cfg.expert_offset,
    }


def program_loss(cfg, params, tokens):
    (hidden, kernel, bias), aux, counters = lfm2_moe.forward(cfg, params, tokens)
    return blockwise_next_token_loss(hidden, kernel, bias, tokens) + aux, counters


@pytest.fixture(scope="module")
def nano():
    cfg = lfm2_moe.lfm2_moe_nano()
    params = jax.jit(lambda rng: lfm2_moe.init_params(cfg, rng))(jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), BATCH, 0, cfg.vocab_size)
    return cfg, params, tokens


def close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rel * max(np.abs(want).max(), 1e-30), (
        np.abs(got - want).max(), np.abs(want).max())


def as_reference_tree(cfg, tree):
    """A tree laid out as the program's parameters, as the reference lays its own out."""
    return reference.from_program_params({**tree, "expert_bias": jax.tree.map(
        jnp.zeros_like, lfm2_moe.init_params(cfg, jax.random.PRNGKey(0))["expert_bias"])})


def test_loss_and_every_gradient_are_the_references(nano):
    cfg, params, tokens = nano
    trained = {k: v for k, v in params.items() if k != "expert_bias"}
    (loss, counters), grads = jax.value_and_grad(
        lambda t: program_loss(cfg, {**t, "expert_bias": params["expert_bias"]}, tokens),
        has_aux=True)(trained)
    ref_params = reference.from_program_params(params)
    want, ref_grads = jax.value_and_grad(reference.loss)(ref_params, tokens, model_keys(cfg))
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    # the reference's gradient of a layer's bias is all zeros too: it only chooses
    got = as_reference_tree(cfg, grads)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path((got["wte"], got["ln_f"], [p for p, _ in got["layers"]])),
            jax.tree.leaves((ref_grads["wte"], ref_grads["ln_f"], [p for p, _ in ref_grads["layers"]]))):
        assert np.abs(np.asarray(w)).max() > 0, path        # every parameter is trained
        close(g, w, 1e-5)
    assert all(not np.asarray(b).any() for _, b in ref_grads["layers"] if b is not None)
    tokens_n = BATCH[0] * BATCH[1]
    assert int(counters["moe_tokens"]) == 4 * tokens_n
    assert 0 < int(counters["moe_assignments"]) < 4 * tokens_n * cfg.experts_per_token


def test_the_convolution_is_three_shifted_products_forward_and_backward(nano):
    cfg, params, _ = nano
    p = params["first"][0]
    r = jax.random.normal(jax.random.PRNGKey(1), (2, 17, cfg.embed_dim))

    def shifted(p, r):
        """Written out: v_t = w_0 u_(t-2) + w_1 u_(t-1) + w_2 u_t, zeros before the sequence."""
        gate_in, gate_out, x = jnp.split(r @ p["in"], 3, -1)
        u = gate_in * x
        zero = jnp.zeros_like(u[:, :1])
        u1 = jnp.concatenate([zero, u[:, :-1]], 1)
        u2 = jnp.concatenate([zero, zero, u[:, :-2]], 1)
        return (gate_out * (p["conv"][0] * u2 + p["conv"][1] * u1 + p["conv"][2] * u)) @ p["out"]

    close(lfm2_moe.conv_mixer(cfg, p, r), shifted(p, r), 1e-6)
    close(reference.conv_mixer(r, p), shifted(p, r), 1e-6)
    up = jax.random.normal(jax.random.PRNGKey(2), r.shape)
    got = jax.grad(lambda p, r: (lfm2_moe.conv_mixer(cfg, p, r) * up).sum(), (0, 1))(p, r)
    want = jax.grad(lambda p, r: (shifted(p, r) * up).sum(), (0, 1))(p, r)
    for name in ("in", "conv", "out"):
        close(got[0][name], want[0][name], 1e-5)
    close(got[1], want[1], 1e-5)
    # causal: an output does not move with a later input
    later = r.at[:, 9:].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(lfm2_moe.conv_mixer(cfg, p, later)[:, :9]),
        np.asarray(lfm2_moe.conv_mixer(cfg, p, r)[:, :9]))


def test_one_update_is_optax_on_the_references_gradients(nano):
    cfg, _, tokens = nano
    mesh = MeshSpec().build(jax.devices()[:1])
    opt = default_optimizer(1e-3)
    state, shardings = init_sharded_state(cfg, mesh, opt, jax.random.PRNGKey(7), BATCH)
    before = jax.tree.map(np.asarray, state.params)
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    with mesh:
        state, metrics = step(state, tokens)
    # the bias is a buffer: no moment holds it, and the step hands it on bit for bit
    moments = jax.tree_util.tree_leaves_with_path(state.opt_state)
    assert moments and not any("expert_bias" in jax.tree_util.keystr(path) for path, _ in moments)
    jax.tree.map(np.testing.assert_array_equal, before["expert_bias"],
                 jax.tree.map(np.asarray, state.params["expert_bias"]))

    ref_params = reference.from_program_params(before)
    want_loss, ref_grads = jax.value_and_grad(reference.loss)(
        ref_params, tokens, model_keys(cfg))
    assert float(metrics["loss"]) == pytest.approx(float(want_loss), rel=2e-6)
    trained = {k: v for k, v in ref_grads.items()}
    trained["layers"] = [p for p, _ in ref_grads["layers"]]
    weights = {**ref_params, "layers": [p for p, _ in ref_params["layers"]]}
    assert float(metrics["grad_norm"]) == pytest.approx(float(optax.global_norm(trained)), rel=1e-5)
    updates, _ = opt.update(trained, opt.init(weights), weights)
    want = optax.apply_updates(weights, updates)
    got = reference.from_program_params(jax.tree.map(np.asarray, state.params))
    # Adam's first step moves every weight by about the learning rate, whatever
    # its gradient's size, so a gradient's float32 roundings show at 1e-3 of a step
    for g, w, old in zip(
            jax.tree.leaves((got["wte"], got["ln_f"], [p for p, _ in got["layers"]])),
            jax.tree.leaves((want["wte"], want["ln_f"], want["layers"])),
            jax.tree.leaves((weights["wte"], weights["ln_f"], weights["layers"]))):
        assert np.abs(np.asarray(w) - np.asarray(old)).max() > 5e-4       # it moved
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    assert {*moe.COUNTERS, "moe_rows_visited", "loss", "grad_norm", "step"} == set(metrics)
    assert all(np.asarray(metrics[name]).dtype == np.int32 for name in moe.TRAINED_COUNTERS)


def test_the_seeded_bias_moves_the_chosen_set_of_many_tokens(nano):
    cfg, params, tokens = nano
    keys = model_keys(cfg)
    ref_params = reference.from_program_params(params)
    p, bias = ref_params["layers"][1]
    r = reference.rms_norm(ref_params["wte"][tokens], p["ln_2"], cfg.norm_eps)
    _, with_bias = reference.route(r, p["router"], bias, keys)
    _, without = reference.route(r, p["router"], bias, keys, wrong="no_bias")
    moved = (np.sort(np.asarray(with_bias), -1) != np.sort(np.asarray(without), -1)).any(-1)
    assert moved.mean() > 0.1, moved.mean()
    # and the program's router makes the reference's choices, bias and all
    weights, chosen = moe.sigmoid_bias_top_k(
        r.reshape(-1, cfg.embed_dim), p["router"], bias, cfg.experts_per_token, cfg.routed_scale)
    np.testing.assert_array_equal(np.asarray(chosen).reshape(with_bias.shape), np.asarray(with_bias))
    # which a loss that ignores the bias does not reproduce
    assert abs(float(reference.loss(ref_params, tokens, keys, wrong="no_bias"))
               - float(reference.loss(ref_params, tokens, keys))) > 1e-5


def test_the_two_shares_add_up_to_the_uncut_layer_and_so_do_their_gradients(nano):
    """Experts 0..3 on one chip, 4..7 on the other: the parts they give add up
    to what the reference gives with all eight, and so do the gradients with
    respect to the layer's input under one upstream gradient."""
    cfg, _, _ = nano
    whole = dataclasses.replace(cfg, num_experts=8)
    p = jax.jit(lambda rng: lfm2_moe.init_params(whole, rng))(jax.random.PRNGKey(3))["periods"][1]
    p = jax.tree.map(lambda a: a[0], p)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    r = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.embed_dim))
    up = jax.random.normal(jax.random.PRNGKey(6), r.shape)

    def share(offset):
        held = dataclasses.replace(cfg, expert_offset=offset)
        mine = {**p, "wi": p["wi"][offset:offset + 4], "wo": p["wo"][offset:offset + 4]}
        return lambda r: lfm2_moe.expert_ffn(held, mine, bias, r)

    def uncut(r):
        keys = {**model_keys(whole), "expert_offset": 0}
        weights, chosen = reference.route(r, p["router"], bias, keys)
        return reference.held_experts(r, weights, chosen, p["wi"], p["wo"], 0, keys)

    (low, low_counts), (high, high_counts) = share(0)(r), share(4)(r)
    close(low + high, uncut(r), 1e-5)
    pairs = r.shape[0] * r.shape[1] * cfg.experts_per_token
    assert int(low_counts[1]) + int(high_counts[1]) == pairs and 0 < int(low_counts[1]) < pairs
    grads = [jax.grad(lambda r, f=f: (f(r)[0] * up).sum())(r) for f in (share(0), share(4))]
    close(grads[0] + grads[1], jax.grad(lambda r: (uncut(r) * up).sum())(r), 1e-5)


def test_no_pair_is_dropped_under_a_skewed_bias(nano):
    """A bias that sends most tokens to one held expert: its load passes twice
    the mean, every pair is still computed, and the loss is still the
    reference's (which a capacity of 1.25 x the mean would not give)."""
    cfg, params, tokens = nano
    skew = jnp.zeros((cfg.router_experts,)).at[1].set(2.0)
    biased = {**params, "expert_bias": jax.tree.map(
        lambda b: jnp.broadcast_to(skew, b.shape), params["expert_bias"])}
    loss, counters = program_loss(cfg, biased, tokens)
    n = BATCH[0] * BATCH[1]
    assert int(counters["moe_load_max"]) == 4 * n        # every token, in all four layers
    assert int(counters["moe_load_max"]) > 2 * int(counters["moe_assignments"]) / cfg.num_experts
    keys, ref_params = model_keys(cfg), reference.from_program_params(biased)
    assert float(loss) == pytest.approx(float(reference.loss(ref_params, tokens, keys)), rel=2e-6)
    dropped = float(reference.loss(ref_params, tokens, keys, wrong="capacity"))
    assert abs(dropped - float(loss)) > 1e-4 * float(loss)


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_left_out_mechanism_moves_the_references_loss(nano, wrong):
    cfg, params, tokens = nano
    keys, ref_params = model_keys(cfg), reference.from_program_params(params)
    if wrong == "capacity":      # an even load drops nothing: skew it
        skew = jnp.zeros((cfg.router_experts,)).at[1].set(2.0)
        ref_params["layers"] = [
            (p, None if b is None else skew) for p, b in ref_params["layers"]]
    right = float(reference.loss(ref_params, tokens, keys))
    # by at least twice what the comparison above allows a float32 rounding
    assert abs(float(reference.loss(ref_params, tokens, keys, wrong=wrong)) - right) > 4e-6 * right


def poisoned_grouped_matmul(poison):
    """A grouped matmul that treats the rows of no group as the TPU's kernel may:
    ``poison`` (NaN, or a number whose sum with itself is not finite) in its result
    and in the gradient of its rows; its gradient of the weights reads the rows of a
    group alone, as ``tgmm`` does."""
    def grouped(rows, w, sizes, **_):
        in_a_group = (jnp.arange(rows.shape[0]) < sizes.sum())[:, None]

        @jax.custom_vjp
        def dot(rows, w):
            return jnp.where(in_a_group, jax.lax.ragged_dot(rows, w, sizes), poison)

        def fwd(rows, w):
            return dot(rows, w), (rows, w)

        def bwd(kept, g):
            rows, w = kept
            _, vjp = jax.vjp(
                lambda rows, w: jax.lax.ragged_dot(rows, w, sizes),
                jnp.where(in_a_group, rows, 0), w)
            d_rows, d_w = vjp(jnp.where(in_a_group, g, 0))
            return jnp.where(in_a_group, d_rows, poison), d_w

        dot.defvjp(fwd, bwd)
        return dot(rows, w)

    return grouped


def poison_the_rows_of_no_group(m, poison):
    """Under the patch ``m``: every row that lies in no group holds ``poison`` in every
    intermediate of the trained layer before a pass could read it: both grouped
    matmuls' results and the gradients of their rows (:func:`poisoned_grouped_matmul`),
    and every row of a buffer that a loop over the held blocks has not filled
    (``moe._unfilled``: the gathered tokens, the activation's result, the kept results'
    bits and what is cast back from them)."""
    m.setattr(moe, "grouped_matmul", poisoned_grouped_matmul(poison))
    m.setattr(moe, "_unfilled", lambda shape, dtype: (
        jnp.full(shape, poison, dtype) if jnp.issubdtype(dtype, jnp.floating)
        else jnp.full(shape, jnp.iinfo(dtype).max, dtype)))      # bits that are a NaN


def interpret_the_kernels(m):
    """Under the patch ``m``: the TPU's kernels themselves, interpreted: megablox's
    grouped matmul and the pass over the held blocks' row tiles."""
    grouped, held_rows = moe.grouped_matmul, moe._held_rows
    m.setattr(moe, "grouped_matmul", lambda *a, **kw: grouped(*a, interpret=True, **kw))
    m.setattr(moe, "_held_rows", lambda *a: held_rows(*a, interpret=True))


def loop_over_experts(x, weights, experts, wi, wo, offset=0, activation=moe.gated_silu):
    """The held experts' part of the layer, one expert at a time over every token."""
    y = jnp.zeros((x.shape[0], wo.shape[-1]), jnp.float32)
    for e in range(wi.shape[0]):
        mask = (weights * (experts == offset + e)).sum(-1)
        y = y + mask[:, None] * (activation(x @ wi[e]) @ wo[e])
    return y


@pytest.mark.parametrize("form", ["whole-forward", "held-blocks"])
@pytest.mark.parametrize("kernel", ["interpreted", "poisoned", "poisoned-3e38"])
def test_rows_of_no_group_reach_no_token_through_the_kernels_path(kernel, form, monkeypatch):
    """Half the pairs are an absent expert's and lie in no group, where the TPU's
    grouped matmul leaves its result and the gradient of its rows undefined. The
    expert layer through the kernels themselves (interpreted, a train tile) and with
    every row of no group poisoned in every intermediate (NaN; 3e38, whose sums are
    not finite) gives the ragged dot's result and its gradients of the tokens, the
    weights and both expert stacks."""
    n, k, d, f, held = 40, 2, 32, 64, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    weights = jax.random.uniform(jax.random.PRNGKey(1), (n, k), minval=0.2)
    experts = jax.random.randint(jax.random.PRNGKey(2), (n, k), 0, 2 * held)
    experts = jnp.where(experts == 1, 0, experts)            # a held expert with no pair
    wi = jax.random.normal(jax.random.PRNGKey(3), (held, d, 2 * f)) * 0.1
    wo = jax.random.normal(jax.random.PRNGKey(4), (held, f, d)) * 0.1
    up = jax.random.normal(jax.random.PRNGKey(5), (n, d))

    def through():
        return jax.value_and_grad(
            lambda x, weights, wi, wo: (moe.trained_experts_ffn(
                x, weights, experts, wi, wo, tiling=(16, 32, 128), routed=FORMS[form])[0] * up).sum(),
            (0, 1, 2, 3))(x, weights, wi, wo)

    want = through()
    KERNELS[kernel](monkeypatch)
    got = through()
    assert 0 < int(((experts >= held).sum())) < n * k
    close(got[0], want[0], 1e-5)
    for g, w in zip(got[1], want[1]):
        assert np.isfinite(np.asarray(g)).all()
        close(g, w, 1e-5)


# -- the work follows the pairs held: sorted rows walked block by block -----------


PAIRS_N, PAIRS_K, HELD_EXPERTS, TILE = 32, 2, 3, (16, 32, 128)
BLOCK, BLOCK_ENDS = 16, (16, 32, 48, 64)
# ``routed`` for each of the layer's two forms: not said (every routed expert may be held
# here: the forward's passes are whole passes, LFM2's cell), and a quarter of them held
# (every pass walks the held blocks alone, Nemotron-3-Nano's cell)
FORMS = {"whole-forward": None, "held-blocks": 4 * HELD_EXPERTS}
KERNELS = {
    "ragged": lambda m: None,
    "interpreted": interpret_the_kernels,
    "poisoned": lambda m: poison_the_rows_of_no_group(m, jnp.nan),
    "poisoned-3e38": lambda m: poison_the_rows_of_no_group(m, 3e38),
}


def routing_that_holds(pairs):
    """``experts`` [n, k] of which exactly ``pairs`` choices, scattered over the
    tokens, are held experts' (one held expert has none), the rest absent ones'."""
    total = PAIRS_N * PAIRS_K
    at = np.random.RandomState(pairs).permutation(total)
    experts = HELD_EXPERTS + np.arange(total) % HELD_EXPERTS          # absent: 3, 4, 5
    experts[at[:pairs]] = np.where(np.arange(pairs) % 3, 2, 0)       # held: 0 and 2
    return jnp.asarray(experts.reshape(PAIRS_N, PAIRS_K), jnp.int32)


def blocked_layers_operands():
    d, f = 32, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (PAIRS_N, d))
    weights = jax.random.uniform(jax.random.PRNGKey(1), (PAIRS_N, PAIRS_K), minval=0.2)
    wi = jax.random.normal(jax.random.PRNGKey(3), (HELD_EXPERTS, d, 2 * f)) * 0.1
    wo = jax.random.normal(jax.random.PRNGKey(4), (HELD_EXPERTS, f, d)) * 0.1
    up = jax.random.normal(jax.random.PRNGKey(5), (PAIRS_N, d))
    return (x, weights, wi, wo), up


def dense_layer(experts):
    """Value and the four gradients of :func:`blocked_layer`'s layer, one expert at a
    time over every token: no sort, no block, no row of no group."""
    operands, up = blocked_layers_operands()
    (_, y), grads = jax.value_and_grad(
        lambda x, weights, wi, wo: (lambda y: ((y * up).sum(), y))(
            loop_over_experts(x, weights, experts, wi, wo)), (0, 1, 2, 3), has_aux=True)(*operands)
    return y, grads


def blocked_layer(experts, kernel, monkeypatch, blocks=None, form="whole-forward"):
    """Value, counters and the four gradients of one trained layer of ``form`` through
    ``kernel``'s grouped matmul, its rows in ``blocks`` blocks (None: the program's own)."""
    (x, weights, wi, wo), up = blocked_layers_operands()

    def scalar(x, weights, wi, wo):
        y, counters = moe.trained_experts_ffn(
            x, weights, experts, wi, wo, tiling=TILE, routed=FORMS[form])
        return (y * up).sum(), (y, counters)

    with monkeypatch.context() as m:
        KERNELS[kernel](m)
        if blocks:
            m.setattr(moe, "ROW_BLOCKS", blocks)
        (_, (y, counters)), grads = jax.value_and_grad(scalar, (0, 1, 2, 3), has_aux=True)(
            x, weights, wi, wo)
    return y, dict(zip(moe.TRAINED_COUNTERS, map(int, counters))), grads


def test_a_block_is_whole_row_tiles_and_never_more_than_every_pair():
    assert moe.row_block(PAIRS_N * PAIRS_K, TILE[0]) == BLOCK
    assert moe.row_block(4 * 16384, moe.GMM_TRAIN_TILING[0]) == 4096      # the cell's: sixteenths
    assert moe.row_block(80, 16) == 16
    assert moe.row_block(24, 512) == 24            # fewer rows than a tile: one block


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("kernel", list(KERNELS))
@pytest.mark.parametrize("pairs", sorted(
    {0, PAIRS_N * PAIRS_K // 8} | {b + by for b in BLOCK_ENDS for by in (-1, 0, 1)}
    - {PAIRS_N * PAIRS_K + 1}))
def test_a_blocks_edge_gives_what_every_row_gives(pairs, kernel, form, monkeypatch):
    """No pair held, an eighth of them, then one pair less than whole blocks, whole
    blocks (a half and all among them), and one pair more: the result, the four
    counters and the gradients of the tokens, the weights and both expert stacks are
    those of the layer that walks all ``k n`` rows in one block, and those of one
    expert at a time over every token. ``poisoned``: with every row of no group
    poisoned in every intermediate, so a pass that read one would not be finite."""
    experts = routing_that_holds(pairs)
    y, counters, grads = blocked_layer(experts, kernel, monkeypatch, form=form)
    want_y, want_counters, want_grads = blocked_layer(
        experts, kernel, monkeypatch, blocks=1, form=form)
    dense_y, dense_grads = dense_layer(experts)
    close(y, dense_y, 1e-5)
    for g, w in zip(grads, dense_grads):
        close(g, w, 1e-5)
    assert want_counters["moe_rows_visited"] == (PAIRS_N * PAIRS_K if pairs else 0)
    assert counters["moe_rows_visited"] == next(b for b in (0,) + BLOCK_ENDS if b >= pairs)
    assert counters["moe_assignments"] == pairs
    assert {k: counters[k] for k in moe.COUNTERS} == {k: want_counters[k] for k in moe.COUNTERS}
    # a row meets its own values alone, whatever the rows beside it; the CPU's
    # matmul rounds a row's sums by the number of rows, so not bit for bit
    close(y, want_y, 1e-5)
    for g, w in zip(grads, want_grads):
        assert np.isfinite(np.asarray(g)).all()
        close(g, w, 1e-5)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_every_pair_held_walks_every_row_and_drops_nothing(kernel, form, monkeypatch):
    """The worst case the shapes are made for: all chosen experts live here."""
    experts = routing_that_holds(PAIRS_N * PAIRS_K)
    assert int((experts < HELD_EXPERTS).sum()) == PAIRS_N * PAIRS_K
    y, counters, grads = blocked_layer(experts, kernel, monkeypatch, form=form)
    assert counters["moe_assignments"] == counters["moe_rows_visited"] == PAIRS_N * PAIRS_K
    want_y, _, want_grads = blocked_layer(experts, "ragged", monkeypatch, blocks=1)
    close(y, want_y, 1e-5)
    for g, w in zip(grads, want_grads):
        close(g, w, 1e-5)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in grads)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("pairs", [0, 1, 15, 16, 17, 31, 33, 40, 48, 49, 63, 64])
def test_rows_visited_are_the_blocks_that_hold_a_pair(pairs, form, monkeypatch):
    y, counters, grads = blocked_layer(
        routing_that_holds(pairs), "ragged", monkeypatch, form=form)
    visited = counters["moe_rows_visited"]
    assert visited % BLOCK == 0 and visited >= counters["moe_assignments"] == pairs
    assert visited - pairs < BLOCK                      # under a block's rows in vain
    if not pairs:
        assert not np.asarray(y).any() and not any(np.asarray(g).any() for g in grads)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("pairs", [70, 80])
def test_a_last_block_that_starts_early_leaves_the_rows_before_it_alone(pairs, form, monkeypatch):
    """80 pairs in blocks of 32 (``ROW_BLOCKS`` 3): the third block is rows 48..79."""
    n, k, d, f = 40, 2, 32, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    weights = jax.random.uniform(jax.random.PRNGKey(1), (n, k), minval=0.2)
    experts = jnp.where(jnp.arange(n * k).reshape(n, k) < pairs, 0, 3) + jnp.arange(k) % 2
    wi = jax.random.normal(jax.random.PRNGKey(3), (HELD_EXPERTS, d, 2 * f)) * 0.1
    wo = jax.random.normal(jax.random.PRNGKey(4), (HELD_EXPERTS, f, d)) * 0.1

    def through(blocks):
        monkeypatch.setattr(moe, "ROW_BLOCKS", blocks)
        assert moe.row_block(n * k, TILE[0]) == {3: 32, 1: 80}[blocks]
        return jax.value_and_grad(
            lambda *a: (moe.trained_experts_ffn(
                a[0], a[1], experts, a[2], a[3], tiling=TILE, routed=FORMS[form])[0]
                        ** 2).sum(), (0, 1, 2, 3))(x, weights, wi, wo)

    got, want = through(3), through(1)
    close(got[0], want[0], 1e-5)
    for g, w in zip(got[1], want[1]):
        close(g, w, 1e-5)


def test_the_step_sums_the_layers_rows_visited(nano):
    cfg, params, tokens = nano
    _, counters = program_loss(cfg, params, tokens)
    pairs = BATCH[0] * BATCH[1] * cfg.experts_per_token
    block = moe.row_block(pairs, moe.GMM_TRAIN_TILING[0])
    visited, held = int(counters["moe_rows_visited"]), int(counters["moe_assignments"])
    # four expert layers, each under a block's rows in vain
    assert held <= visited < held + 4 * block and visited <= 4 * pairs


# -- what a layer's remat keeps: the kernels' results, by name --------------------


def _eqns(jaxpr, primitive):
    """The equations of ``primitive`` in ``jaxpr`` and in every jaxpr under it."""
    return sum(
        (eqn.primitive.name == primitive)
        + sum(_eqns(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


@pytest.fixture
def replayed(monkeypatch):
    """``replayed()``: from here on the expert layer names nothing, so a layer's remat
    keeps ``FLASH_RESIDUALS`` alone and replays both grouped matmuls (the program
    before the names). ``jax.checkpoint`` keeps the layer's trace: dropped, both ways."""
    def answer():
        jax.clear_caches()
        monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)

    yield answer
    jax.clear_caches()


@pytest.mark.parametrize("name", FLASH_RESIDUALS + moe.TRAINED_RESIDUALS)
def test_a_layers_remat_keeps_each_kernels_result_by_its_name(name, built_for_tpu, capsys):
    """An attention layer with experts, alone behind the scan (a broken period's), with
    the TPU's kernels, under ``forward``'s own policy: the flash kernel's two results
    and both grouped matmuls' are kept, the grouped matmuls' as their bits."""
    built_for_tpu(True)
    cfg = lfm2_moe.lfm2_moe_nano(
        head_dim=64, dtype=jnp.bfloat16,
        layer_types=(lfm2_moe.CONV, lfm2_moe.ATTENTION, lfm2_moe.CONV, lfm2_moe.CONV,
                     lfm2_moe.ATTENTION))
    assert (cfg.periods, len(cfg.period)) == (1, 3)               # the fifth layer runs alone
    params = jax.eval_shape(lambda: lfm2_moe.init_params(cfg, jax.random.PRNGKey(0)))
    jax.ad_checkpoint.print_saved_residuals(
        lambda params, tokens: program_loss(cfg, params, tokens)[0],
        params, jax.ShapeDtypeStruct(BATCH, jnp.int32))
    pairs = BATCH[0] * BATCH[1] * cfg.experts_per_token
    heads = f"{BATCH[0]},{cfg.num_heads},{BATCH[1]}"
    shape, origin = {     # the flash kernel's are named inside ``dot_product_attention``'s own jit
        "flash_out": (f"bf16[{heads},64]", "(attention_mixer)"),
        "flash_lse": (f"f32[{heads}]", "(attention_mixer)"),
        "moe_gate_up": (f"u16[{pairs},{2 * cfg.expert_dim}]", "named 'moe_gate_up'"),
        "moe_out": (f"u16[{pairs},{cfg.embed_dim}]", "named 'moe_out'")}[name]
    kept = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith(shape + " ") and origin in line]
    assert len(kept) == 1, kept


def _loss_and_gradients(cfg, params, tokens):
    return jax.jit(jax.value_and_grad(lambda p: program_loss(cfg, p, tokens)[0]))(params)


@pytest.mark.parametrize("offset", [0, 4], ids=["first-share", "second-share"])
def test_kept_or_replayed_the_loss_and_every_gradient_are_the_same_bits(offset, nano, replayed):
    """In float32, where the CPU computes a value as it stores it (in bfloat16 its
    fusions carry excess precision from a replay's matmul into the gate: what JAX's
    ``reduce_precision`` is for, and what a kernel's stored result has none of)."""
    _, params, tokens = nano
    cfg = lfm2_moe.lfm2_moe_nano(expert_offset=offset)
    kept = _loss_and_gradients(cfg, params, tokens)
    replayed()
    again = _loss_and_gradients(cfg, params, tokens)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(kept))
    assert all(np.asarray(layer[w]).any() for layer in kept[1]["periods"] for w in ("wi", "wo"))
    for got, want in zip(jax.tree.leaves(kept), jax.tree.leaves(again), strict=True):
        assert got.dtype == want.dtype and np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("keeps", ["both results", "neither"])
def test_the_backward_of_a_layer_that_keeps_both_results_runs_no_grouped_matmul_again(
        keeps, nano, replayed):
    """Four expert layers: a forward pair, the rows' two gradients and the weights' two
    each, 6; a remat that keeps neither result makes the forward pair again, 8. No
    kept value goes through ``reduce_precision``: they are integers to the remat."""
    cfg, params, tokens = nano
    if keeps == "neither":
        replayed()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: program_loss(cfg, p, tokens)[0]))(params).jaxpr
    assert _eqns(jaxpr, "ragged_dot_general") == 4 * (6 if keeps == "both results" else 8)
    assert _eqns(jaxpr, "reduce_precision") == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_a_value_named_by_its_bits_is_the_value_and_so_is_its_gradient(dtype):
    x = jnp.asarray([1.5, -0.0, 0.0, jnp.nan, -jnp.nan, jnp.inf, -jnp.inf, 1e-39, 3.0], dtype)
    named = jax.jit(lambda x: moe._named(x, "a_name"))(x)
    assert named.dtype == x.dtype and np.asarray(named).tobytes() == np.asarray(x).tobytes()
    up = jnp.asarray([2.0, -0.0, jnp.nan, 1.0, 1.0, jnp.inf, 1.0, 1e-39, -3.0], dtype)
    _, vjp = jax.vjp(lambda x: moe._named(x, "a_name"), x)
    (handed,) = vjp(up)
    assert handed.dtype == up.dtype and np.asarray(handed).tobytes() == np.asarray(up).tobytes()
    assert "a_name" in str(jax.make_jaxpr(jax.grad(lambda x: moe._named(x, "a_name").sum()))(x))


@pytest.mark.parametrize("kernel", ["loop", "interpreted"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_a_value_kept_as_its_bits_is_the_value_and_its_gradient_passes(dtype, kernel, monkeypatch):
    """Over the blocks that hold a pair (two of three here: the cast walks no other),
    bit for bit; the name is in the gradient's jaxpr, and the gradient passes the
    integers (the activation's rule reads the kept bits back: the identity's is ``g``)."""
    if kernel == "interpreted":
        interpret_the_kernels(monkeypatch)
    # (the CPU moves a bfloat16 as a float32 and flushes what is denormal there)
    tiny = 1e-39 if dtype == jnp.float32 else 1e-30
    row = [1.5, -0.0, 0.0, jnp.nan, -jnp.nan, jnp.inf, -jnp.inf, tiny, 3.0]
    x = jnp.asarray([row] * 48, dtype).reshape(48, 9)
    held_rows, block, held = jnp.int32(17), 16, 32
    kept = jax.jit(lambda x: moe._kept_bits(x, "a_name", held_rows, block))(x)
    assert kept.dtype == {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    back = jax.lax.bitcast_convert_type(kept, x.dtype)
    assert np.asarray(back[:held]).tobytes() == np.asarray(x[:held]).tobytes()

    def through(x):
        return moe._activated(lambda rows: rows, block, x, held_rows)

    assert np.asarray(through(x)[:held]).tobytes() == np.asarray(x[:held]).tobytes()
    up = jnp.asarray([[2.0, -0.0, jnp.nan, 1.0, 1.0, jnp.inf, 1.0, 1e-30, -3.0]] * 48, dtype)
    (handed,) = jax.vjp(through, x)[1](up)
    assert handed.dtype == up.dtype
    assert np.asarray(handed[:held]).tobytes() == np.asarray(up[:held]).tobytes()
    assert moe.TRAINED_RESIDUALS[0] in str(jax.make_jaxpr(jax.grad(lambda x: through(x).sum()))(x))


def test_the_published_layout_scans_nine_periods_and_runs_two_layers_behind_them():
    cfg = lfm2_moe.Lfm2MoeConfig()
    assert cfg.num_layers == 40 and cfg.period == (
        "full_attention", "conv", "conv", "conv") and cfg.periods == 9
    assert cfg.num_params() == 23_843_661_440
    small = lfm2_moe.lfm2_moe_nano(
        layer_types=("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2 + (
            "full_attention", "conv"), dense_layers=2)
    params = jax.jit(lambda rng: lfm2_moe.init_params(small, rng))(jax.random.PRNGKey(0))
    assert len(params["first"]) == 2 and len(params["tail"]) == 2
    assert params["periods"][0]["q"].shape[0] == 2
    assert sum(x.size for x in jax.tree.leaves(params)) == small.num_params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, small.vocab_size)
    loss, counters = program_loss(small, params, tokens)
    keys = model_keys(small)
    want = reference.loss(reference.from_program_params(params), tokens, keys)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert int(counters["moe_tokens"]) == 10 * 16


# -- what the generalisation may not move -----------------------------------------


def gpt_only_step(cfg, optimizer):
    """The train step as it was written for ``GPT`` alone, before a configuration
    was asked for its model: the same scopes, the same order of operations."""
    from ray_tpu.models.gpt import GPT
    from ray_tpu.models.training import TrainState

    model = GPT(cfg, return_hidden=True, mesh=None)

    @jax.named_scope("train.forward")
    def _apply(params, tokens):
        if cfg.moe_num_experts > 0:
            out, mut = model.apply({"params": params}, tokens, mutable=["losses"])
            aux = sum(jnp.sum(v) for v in jax.tree.leaves(mut["losses"]))
            return out, aux / cfg.num_layers
        return model.apply({"params": params}, tokens), jnp.zeros((), jnp.float32)

    def loss_fn(params, tokens):
        (hidden, kernel, bias), aux = _apply(params, tokens)
        with jax.named_scope("train.loss"):
            loss = blockwise_next_token_loss(hidden, kernel, bias, tokens)
        return loss + cfg.moe_aux_weight * aux

    def step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
        with jax.named_scope("train.optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss, "grad_norm": optax.global_norm(grads), "step": state.step + 1}
        return TrainState(step=state.step + 1, params=new_params, opt_state=new_opt), metrics

    return jax.jit(step, donate_argnums=(0,))


@pytest.mark.parametrize("experts", [0, 4], ids=["dense", "capacity-moe"])
def test_gpts_step_lowers_to_the_text_of_a_step_written_for_gpt_alone(experts):
    """Asking the configuration for its model costs GPT nothing: the step
    ``make_train_step`` builds for a ``GPTConfig`` (dense, and with the
    capacity-based ``MoeMlp`` and its auxiliary loss) lowers to the very text
    of the step that names ``GPT``, and reports what that step reports."""
    import flax.linen as nn

    from ray_tpu.models.gpt import gpt_nano
    from ray_tpu.models.training import abstract_state

    cfg = gpt_nano(moe_num_experts=experts)
    opt = default_optimizer(1e-3)
    _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct((2, 32), jnp.int32))
    state = nn.meta.unbox(abstract)
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    ours = make_train_step(cfg, opt).lower(state, tokens)
    assert ours.as_text() == gpt_only_step(cfg, opt).lower(state, tokens).as_text()
    assert set(ours.out_info[1]) == {"loss", "grad_norm", "step"}


PARENT = "09fadc28c1b85e218da54b57f3ca0e3a0043ae0a"


@pytest.mark.parametrize("served", ["cohere2_moe", "keye_vl2", "kimi_k2"])
def test_a_served_expert_layer_lowers_to_the_parents_text(served, built_for_tpu, monkeypatch):
    """The train tiling is an argument whose default leaves the
    serve programs as they are: ``extend`` of each served configuration with
    experts, lowered for the TPU (the megablox kernel) with this tree's
    ``models/moe.py`` and with the parent commit's, is one text. (At the
    published widths and every shape of the cells: ``CHANGES.md``, PR 43.)"""
    import importlib
    import os
    import re
    import subprocess
    import sys
    import types

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shown = subprocess.run(
        ["git", "show", f"{PARENT}:ray_tpu/models/moe.py"], cwd=repo, capture_output=True, text=True)
    if shown.returncode:
        pytest.skip(f"no git history with the parent commit here: {shown.stderr.strip()[:200]}")
    parents = types.ModuleType("parents_moe")
    monkeypatch.setitem(sys.modules, "parents_moe", parents)     # flax's dataclasses look it up
    exec(compile(shown.stdout, "parents_moe.py", "exec"), parents.__dict__)
    module = importlib.import_module("ray_tpu.models." + served)
    cfg = getattr(module, served + "_nano")()
    params = jax.eval_shape(lambda: cfg.init_params(0))
    body = re.compile(r'(body\\22: \\22)[A-Za-z0-9+/=]+')    # a kernel's serialized body names call sites

    def text(moe_module, lanes, tc):
        built_for_tpu(True)
        monkeypatch.setattr(module, "moe", moe_module)
        caches = [
            jax.ShapeDtypeStruct((cfg.num_layers, lanes, 64) + tuple(each), cfg.dtype)
            for each in cfg.cache_arrays]
        traced = cfg.make_extend_fn().trace(
            params, jax.ShapeDtypeStruct((lanes, tc), jnp.int32),
            jax.ShapeDtypeStruct((lanes,), jnp.int32), *caches)
        return body.sub(r"\1", traced.lower(lowering_platforms=("tpu",)).as_text())

    for lanes, tc in ((2, 1), (1, 32)):
        ours = text(moe, lanes, tc)
        assert "tpu_custom_call" in ours and ours == text(parents, lanes, tc)
