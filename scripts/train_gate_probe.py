"""What a train cell's loss cannot tell, read on the chip outside a benchmark run
(the serve cells' probe is ``scripts/gate_probe.py``). For each seed, on the
weights and the batch the cell's run would draw from it:

* the program's loss of the whole batch (its forward and blockwise loss, no
  step) against the plain reference's, and the reference's loss with each
  omission it names (``WRONG``) and in the precision below (``LOWER``);
* the program's **final hidden rows** against the reference's, as
  ``yardstick.logits_error`` reads logits;
* the **norm of the gradient of each group of parameters** (``GROUPS``, by the
  configuration's ``model_type``) of the program's ``jax.grad``
  against the reference's, over the first ``--sequences`` sequences of the
  batch. The reference's float32 gradients of the whole model do not fit a
  chip beside their activations (it keeps every expert's products), so its
  gradient is taken **a layer at a time**: the layers' inputs are kept on the
  way up, and on the way down ``jax.vjp`` of the reference's own ``layer`` gives
  the layer's parameter gradients, whose squared norms are added up, and the
  gradient of its input.

    python3 scripts/train_gate_probe.py --cell lfm2-24b-a2b-train-1chip-fixed-batch \
        --seeds 1,2 [--wrong-seeds 1] [--sequences 1] [--tokens 1024] [--tiny] [--out chiprun_out/x.jsonl]

``--tiny`` runs the tests' tiny model (``tests/benchmark/tiny/<model_type>.json``)
on whatever backend is there; without it the configuration's file at its
published widths, which needs the chip."""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the groups a model's gradient is reported in, by the names its arrays have in a
# layer's tree (LFM2's dense MLP and experts share ``wi`` / ``wo``: ``group_of``) or at
# the top of the parameters
GROUPS = {
    "lfm2_moe": {
        "router": ("router",), "experts": ("expert_wi", "expert_wo"),
        "conv": ("in", "conv", "out"), "attention": ("q", "k", "v", "o", "q_norm", "k_norm"),
        "dense_mlp": ("dense_wi", "dense_wo"), "embedding": ("wte",),
        "norms": ("ln_1", "ln_2", "ln_f"),
    },
    "nemotron_h": {
        "router": ("router",), "experts": ("expert_wi", "expert_wo"),
        "shared_expert": ("shared_wi", "shared_wo"), "mamba_projections": ("in", "out"),
        "mamba_own": ("conv", "conv_bias", "dt_bias", "A_log", "D", "norm"),
        "attention": ("q", "k", "v", "o"), "embedding": ("wte",), "head": ("head",),
        "norms": ("ln", "ln_f"),
    },
}


def say(msg: str) -> None:
    print(f"[gate] {msg}", flush=True)


def group_of(groups, name: str, dense: bool) -> str:
    if name in ("wi", "wo"):
        name = ("dense_" if dense else "expert_") + name
    return next(group for group, names in groups.items() if name in names)


def by_group(groups, layers, top):
    """``{group: sum of squares}`` from the squared norms of gradients laid out a
    layer at a time: ``layers`` is ``[({name: squared norm}, dense?)]``, ``top``
    ``{name: squared norm}`` of the arrays outside the layers."""
    total = {group: 0.0 for group in groups}
    for squares, dense in layers:
        for name, square in squares.items():
            total[group_of(groups, name, dense)] += float(square)
    for name, square in top.items():
        total[group_of(groups, name, False)] += float(square)
    return total


def _is_dense(p) -> bool:
    """A layer whose ``wi`` is a dense MLP's and no stack of experts."""
    return "wi" in p and "router" not in p


def program_gradient_norms(groups, cfg, params, tokens, reference):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import blockwise_next_token_loss

    model = cfg.train_model()
    trained = {k: v for k, v in params.items() if k not in model.buffers}
    buffers = {k: params[k] for k in model.buffers}

    @jax.jit
    def gradient(trained):
        def loss(trained):
            (hidden, kernel, bias), aux, _ = model.apply({**trained, **buffers}, tokens)
            return blockwise_next_token_loss(hidden, kernel, bias, tokens) + model.aux_weight * aux

        return jax.grad(loss)(trained)

    def square(g):
        return jnp.sum(jnp.square(g.astype(jnp.float32)))

    grads = gradient(trained)
    layers = [
        (jax.tree.map(square, g), _is_dense(p)) for (g, _), (p, _) in zip(
            reference.program_layers({**grads, "expert_bias": params["expert_bias"]}),
            reference.program_layers(params))]
    return by_group(groups, layers, {
        name: square(g) for name, g in grads.items() if not isinstance(g, (list, tuple, dict))})


def reference_gradient_norms(groups, params, tokens, config, reference):
    """The reference's gradient norms for ``tokens`` [sequences, seq], a layer
    at a time: float32 at the highest matmul precision, by ``jax.vjp`` of the
    reference's own ``layer``; a layer's gradients are reduced to their squared
    norms in the call that makes them."""
    import jax
    import jax.numpy as jnp

    model = reference._model(config)
    f32 = functools.partial(jax.tree.map, lambda a: jnp.asarray(a, jnp.float32))
    count = tokens.shape[0] * (tokens.shape[1] - 1)

    @functools.partial(jax.jit, static_argnums=(3,))
    def up(x, p, bias, at):
        with jax.default_matmul_precision("highest"):
            return reference.layer(x, f32(p), bias, at, model)

    @functools.partial(jax.jit, static_argnums=(4,))
    def down(x, p, bias, g, at):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda x, p: reference.layer(x, p, bias, at, model), x, f32(p))
            g_x, g_p = vjp(g)
            return g_x, jax.tree.map(lambda a: jnp.sum(jnp.square(a)), g_p)

    tied = "head" not in params

    @jax.jit
    def top(x, head, ln_f):
        def loss(x, head, ln_f):
            logits = reference.rms_norm(x, ln_f, model["norm_eps"]) @ (head.T if tied else head)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).sum() / count

        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, (0, 1, 2))(x, f32(head), f32(ln_f))

    layers = list(reference.program_layers(params))
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    inputs = []
    for at, (p, bias) in enumerate(layers):
        inputs.append(x)
        x = up(x, p, bias, at)
    g, g_head, g_ln_f = top(x, params["wte" if tied else "head"], params["ln_f"])
    squares = []
    for at in reversed(range(len(layers))):
        p, bias = layers[at]
        g, norms = down(inputs.pop(), p, bias, g, at)
        squares.append((norms, _is_dense(p)))
    # the embedding's gradient: the gathered rows', and under it a tied head's
    g_wte = (g_head if tied else jnp.zeros(params["wte"].shape, jnp.float32)).at[
        tokens.reshape(-1)].add(g.reshape(-1, g.shape[-1]))
    def square(a):
        return jnp.sum(jnp.square(a))

    return by_group(groups, squares, {
        "wte": square(g_wte), "ln_f": square(g_ln_f), **({} if tied else {"head": square(g_head)})})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--wrong-seeds", default="")
    ap.add_argument("--sequences", type=int, default=1)
    ap.add_argument(
        "--tokens", type=int, default=0,
        help="compare hidden rows and gradients on the first so many tokens of a sequence "
             "(0: all; a reference whose backward keeps a state a token needs it cut)")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest, yardstick
    from benchmark.manifest import published_keys
    from ray_tpu.models.gpt import blockwise_next_token_loss

    cell = manifest.Manifest(ROOT).cell(args.cell)
    file = cell.config
    if args.tiny:
        model_type = file["model_type"]
        with open(os.path.join(ROOT, "tests", "benchmark", "tiny", model_type + ".json")) as f:
            tiny = json.load(f)
        file = {**tiny["model"], "reference": tiny["reference"], "job": tiny["cells"][0]["job"]}
    architecture = importlib.import_module(cell.architecture)
    reference = importlib.import_module(cell.reference)
    cfg = architecture.program_config(published_keys(file))
    batch = tuple(file["job"]["batch"])
    device = jax.devices()[0]
    say(f"{args.cell}{' (tiny)' if args.tiny else ''} on {device.platform} {device.device_kind}: "
        f"{architecture.describe(cfg)}; batch {batch}")
    model = cfg.train_model()
    groups = GROUPS[file["model_type"]]

    @jax.jit
    def program(params, tokens):
        (hidden, kernel, bias), aux, counters = model.apply(params, tokens)
        return blockwise_next_token_loss(hidden, kernel, bias, tokens) + aux, hidden, counters

    wrong_seeds = {int(s) for s in args.wrong_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params = architecture.seeded_params(cfg, seed)
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), batch, 0, cfg.vocab_size)
        loss, hidden, counters = program(params, tokens)
        loss = float(loss)
        want = reference.program_loss(params, tokens, file)
        out = {
            "seed": seed, "loss": loss, "reference_loss": want,
            "loss_error": abs(loss - want) / want,
            "counters": {k: int(v) for k, v in counters.items()},
        }
        rows = tokens[:args.sequences, :args.tokens or None]
        # causal: a sequence's first tokens give the same rows whatever follows them
        got_hidden = np.asarray(hidden[:args.sequences, :args.tokens or None], np.float32)
        want_hidden = np.asarray(reference.program_hidden(params, rows, file))
        out["hidden_error"] = yardstick.logits_error(got_hidden, want_hidden)
        del hidden
        got = program_gradient_norms(groups, cfg, params, rows, reference)
        ref = reference_gradient_norms(groups, params, rows, file, reference)
        out["gradient_norms"] = {
            group: {"program": got[group] ** 0.5, "reference": ref[group] ** 0.5,
                    "ratio": (got[group] / ref[group]) ** 0.5 if ref[group] else None}
            for group in groups}
        if seed in wrong_seeds:
            out["wrong"] = {}
            for wrong in reference.WRONG:
                reads = reference.program_loss(params, tokens, file, wrong=wrong)
                out["wrong"][wrong] = {"loss": reads, "moves": abs(reads - want) / want}
            reads = reference.program_loss(params, tokens, file, lower=True)
            out["wrong"][reference.LOWER] = {"loss": reads, "moves": abs(reads - want) / want}
        out["seconds"] = time.perf_counter() - t0
        say(json.dumps(out))
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(out) + "\n")
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
