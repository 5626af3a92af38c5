"""What a change to the model code must leave as it is, as digests: ``python3
scripts/program_digest.py --cell <cell>`` lowers for the TPU (here, without the
chip) every program of the model that the cell of ``BENCHMARK.json`` runs and
prints one ``name sha256`` line a program:

* a serve cell: ``cfg.make_extend_fn()`` at every (lanes, tokens, cache) that
  ``LLMEngine.extend_shapes`` lists for the configuration file's engine sizes,
  over abstract parameters, caches and state arenas (no engine, no pool; the
  paging programs are ``serve/llm.py``'s and are not here); a decode call of a model
  whose ``extend`` reads pages (``llm.reads_pages``) as the engine calls it, over the
  pool's arenas and a block table;
* a train cell: the step at the cell's batch and mesh, on as many virtual
  devices as the cell has chips;
* ``init_params``: the leaves that the architecture's tiny preset draws from
  ``--seed``, bit for bit.

The text keeps each operation's name with its scopes (``extend.attention/mul``:
what the readers of a device trace key on) and drops source files and lines; a
Mosaic kernel's serialized body, which names its callers' lines, is blanked. Run
it in two checkouts (``git archive <parent>``, then this file copied into its
``scripts/``) and compare the lines: equal lines are equal programs.
"""

import argparse
import hashlib
import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BODY = re.compile(r'(body\\22: \\22)[A-Za-z0-9+/=]+')
NAMED = re.compile(r'(#loc\d*) = loc\("([^"]*)"(?!:)')     # a name, not "file":line:column


def say(name, *parts):
    digest = hashlib.sha256()
    for part in parts:
        digest.update(part if isinstance(part, bytes) else part.encode())
    print(name, digest.hexdigest(), flush=True)


def lowered(traced):
    lines = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True).splitlines()
    names = dict(m.groups() for m in map(NAMED.match, lines) if m)
    text = "\n".join(line for line in lines if not line.startswith("#loc"))
    text = re.sub(r"loc\((#loc\d*)\)", lambda m: f'loc("{names.get(m[1], "")}")', text)
    return BODY.sub(r"\1", re.sub(r"#loc\d+", "#loc", text))   # what is left are call sites


def serve_programs(cell, cfg, engine):
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve import batching, llm

    def abstract(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    params = jax.eval_shape(lambda: cfg.init_params(0))
    extend = cfg.make_extend_fn()
    layers = getattr(cfg, "cache_layers", cfg.num_layers)
    states = [
        abstract((n, engine["state_slots"]) + tuple(shape), dtype)
        for n, shape, dtype in getattr(cfg, "state_arrays", ())]
    prefill_lanes = batching.bucket_pad_size(engine["prefill_lanes"], engine["lane_buckets"])
    # (a checkout from before PR 61 has no such question: nothing reads pages there)
    reads_pages = getattr(llm, "reads_pages", lambda extend: False)(extend)
    for lanes in sorted(engine["lane_buckets"]):
        for tc in [1] + sorted(engine["prefill_token_buckets"]):
            if tc > 1 and lanes > prefill_lanes:
                continue
            for cap in sorted(engine["cache_buckets"]):
                # an array with a third element holds a row for every so many tokens
                paged = tc == 1 and reads_pages
                block = engine["block_size"]
                caches = [
                    abstract(
                        (layers, engine["num_blocks"], block) + tuple(each[:2]) if paged
                        else (layers, lanes, cap // llm.cache_grain(each)) + tuple(each[:2]),
                        cfg.dtype)
                    for each in cfg.cache_arrays]
                where = [abstract((lanes,))] * 3 if states else []
                table = dict(table=abstract((lanes, cap // block))) if paged else {}
                say(f"{cell} extend {lanes}x{tc}x{cap}", lowered(extend.trace(
                    params, abstract((lanes, tc)), abstract((lanes,)), *caches, *states, *where,
                    **table)))


def train_program(cell, cfg, job, chips):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.training import (
        abstract_state, default_optimizer, make_train_step, state_shardings)
    from ray_tpu.parallel import sharding as shd
    from ray_tpu.parallel.mesh import MeshSpec

    batch = tuple(job["batch"])
    mesh = MeshSpec(**job["mesh"]).build(jax.devices()[:chips])
    opt = default_optimizer(job["learning_rate"])
    _, abstract = abstract_state(cfg, opt, jax.ShapeDtypeStruct(batch, jnp.int32))
    shardings = nn.meta.unbox(state_shardings(mesh, abstract))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        nn.meta.unbox(abstract), shardings)
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=shd.batch_sharding(mesh))
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    with mesh:
        text = lowered(step.trace(state, tokens))
    say(f"{cell} train step {batch[0]}x{batch[1]} on {chips}", text)


def tiny_params(cell, cfg, seed):
    """The leaves the architecture's ``*_nano`` preset draws from ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    module = sys.modules[type(cfg).__module__]
    (preset,) = (name for name in vars(module) if name.endswith("_nano"))
    tiny = getattr(module, preset)()
    params = tiny.init_params(seed) if hasattr(tiny, "init_params") else tiny.train_model().init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    leaves = jax.tree_util.tree_leaves_with_path(jax.device_get(params))
    say(f"{cell} init_params {preset}({seed}) {len(leaves)} leaves", *(
        part for path, leaf in leaves
        for part in (jax.tree_util.keystr(path), str(leaf.dtype), np.asarray(leaf).tobytes())))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", required=True)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == args.cell)
    config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        file = json.load(f)

    # the cell's chips as virtual devices of the CPU, before jax starts
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={entry['chips']}"
    sys.path.insert(0, ROOT)
    from benchmark.manifest import published_keys
    from ray_tpu.ops import backend

    architecture = importlib.import_module("benchmark.models." + file["model_type"])
    cfg = architecture.program_config(published_keys(file))
    tiny_params(args.cell, cfg, args.seed)
    backend.on_tpu = lambda: True       # from here on the chip's kernels, as the cell runs them
    if "engine" in file:
        serve_programs(args.cell, cfg, file["engine"])
    else:
        train_program(args.cell, cfg, file["job"], entry["chips"])


if __name__ == "__main__":
    main()
