"""What a serve cell's gate reads on the chip, outside a benchmark run: for each
seed, the gate's prompt through the engine uncached and from the prefix cache
(bitwise equal?), the served logits against the plain reference's
(``yardstick.logits_error`` over all the new tokens and over the first 16, 32,
... of them, and the worst row), and against the reference with each omission
it names (``WRONG`` and ``LOWER``). The architecture and the reference are the
modules the cell's configuration names (``benchmark/manifest.py``), the tiny
model is ``tests/benchmark/tiny/<model_type>.json``. Where the reference tells
what each query selected (``program_selection``: learned sparse attention) and
the program has a probe for it (``make_probe_fn``), ``--overlap-seeds`` says
how far the two sets overlap; where the reference tells the *blocks* each query
read, a K/V head at a time (``program_blocks``: block-sparse attention), the
overlap is of those. The builder sets ``reference.max_logits_error``
in the configuration's file from these readings, by hand (PERF.md, section 6).

    python3 scripts/gate_probe.py --cell kimi-k2-serve-long-context --seeds 1,2,3 \
        --wrong-seeds 1 [--overlap-seeds 1] [--new-tokens 64] [--tiny] \
        [--out chiprun_out/gate.jsonl]

One engine serves every seed (its programs compile once); a seed draws the
weights and the prompt. ``--tiny`` runs the tests' tiny model on whatever
backend is there; without it the configuration's file at its published widths,
which needs the chip."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[gate] {msg}", flush=True)


def selection_of(probe, cfg, params, fed, chunk: int, cap: int):
    """What each query of ``fed`` selected, bool [layers, seq, seq], by the
    program: the prompt in chunks of ``chunk`` (the prefill form), what is left
    a token at a time (the decode form), over caches of ``cap`` slots."""
    import jax.numpy as jnp
    import numpy as np

    caches = [
        jnp.zeros((cfg.num_layers, 1, cap) + tuple(each), cfg.dtype) for each in cfg.cache_arrays]
    seq, whole = len(fed), len(fed) // chunk * chunk
    out = np.zeros((cfg.num_layers, seq, seq), bool)
    at = 0
    while at < seq:
        n = chunk if at < whole else 1
        tokens = jnp.asarray([fed[at:at + n]], jnp.int32)
        # (logits, hidden, a new row for every cache, counters, selected)
        _, _, *news, _, selected = probe(params, tokens, jnp.full((1,), at, jnp.int32), *caches)
        caches = [c.at[:, :, at:at + n].set(new) for c, new in zip(caches, news)]
        out[:, at:at + n] = np.asarray(selected)[:, 0, :, :seq]
        at += n
    return out


def blocks_of(probe, cfg, params, fed, chunk: int, cap: int):
    """The blocks each query of ``fed`` read, bool [sparse layers, seq, kv, blocks],
    by a program whose sparse layers choose blocks from compressed keys cached at
    their own grain beside a per-sequence state (``models/minicpm_sala.py``): the
    prompt in chunks of ``chunk`` (the prefill form), what is left a token at a time
    (the decode form), over caches of ``cap`` slots and one state slot."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import cache_grain

    caches = [
        jnp.zeros((cfg.cache_layers, 1, cap // cache_grain(each)) + tuple(each[:2]), cfg.dtype)
        for each in cfg.cache_arrays]
    states = [
        jnp.zeros((layers, 2) + tuple(shape), dtype) for layers, shape, dtype in cfg.state_arrays]
    seq, whole = len(fed), len(fed) // chunk * chunk
    size, stride = cfg.select_block, cfg.kernel_stride
    out = np.zeros((cfg.cache_layers, seq, cfg.kv_heads, cap // size), bool)
    one, none = jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
    at = 0
    while at < seq:
        n = chunk if at < whole else 1
        tokens = jnp.asarray([fed[at:at + n]], jnp.int32)
        _, _, k, v, c, *states, _, selected = probe(
            params, tokens, jnp.full((1,), at, jnp.int32), *caches, *states, one, none, none)
        caches[:2] = [x.at[:, :, at:at + n].set(new) for x, new in zip(caches, (k, v))]
        # row r of the compressed keys is the one whose last key is the r-th token
        # of the call that ends a group of ``stride``
        for r in range(c.shape[2]):
            row = at // stride + r
            if (row + 1) * stride <= at + n and (row + 1) * stride >= cfg.kernel_size:
                caches[2] = caches[2].at[:, :, row].set(c[:, :, r])
        read = np.asarray(selected)[:, 0]                     # [layers, kv, n, cap]
        out[:, at:at + n] = read.reshape(read.shape[:3] + (-1, size)).any(-1).transpose(0, 2, 1, 3)
        at += n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--wrong-seeds", default="")
    ap.add_argument("--overlap-seeds", default="")
    ap.add_argument("--new-tokens", type=int, default=None, help="in place of the cell's own")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import importlib

    import jax
    import numpy as np

    from benchmark import manifest, yardstick
    from ray_tpu.serve import batching, llm

    cell = manifest.Manifest(ROOT).cell(args.cell)
    arch = importlib.import_module(cell.architecture)
    ref = importlib.import_module(cell.reference)
    if args.tiny:
        with open(os.path.join(
                ROOT, "tests", "benchmark", "tiny", cell.config["model_type"] + ".json")) as f:
            tiny = json.load(f)
        config, prompt_tokens, new = {**tiny["model"], "reference": tiny["reference"]}, 96, 4
        sizes = dict(num_blocks=32, block_size=16, prefill_chunk=32, lane_buckets=(1,),
                     prefill_token_buckets=(32,), cache_buckets=(128,))
    else:
        config = cell.config
        prompt_tokens, new = cell.traffic["gate_prompt_tokens"], cell.traffic["gate_new_tokens"]
        sizes = dict(num_blocks=64, block_size=256, prefill_chunk=512, lane_buckets=(1,),
                     prefill_token_buckets=(512,), cache_buckets=(8192,))
    new = args.new_tokens or new
    if prompt_tokens + new > sizes["cache_buckets"][0]:
        # a gate past the usual bucket: the cell's own smallest bucket that holds it,
        # and blocks for the prompt twice
        cap = min(b for b in config["engine"]["cache_buckets"] if b >= prompt_tokens + new)
        sizes.update(cache_buckets=(cap,), num_blocks=2 * cap // sizes["block_size"])
    cfg = arch.program_config(manifest.published_keys(config))
    seeds = [int(s) for s in args.seeds.split(",")]
    wrong_seeds = {int(s) for s in args.wrong_seeds.split(",") if s}
    overlap_seeds = {int(s) for s in args.overlap_seeds.split(",") if s}
    say(f"{jax.devices()[0].device_kind}; {arch.describe(cfg)}; engine {sizes}")
    params = cfg.init_params(seeds[0])
    engine = llm.LLMEngine(cfg, params, **sizes)
    if overlap_seeds:
        probe = importlib.import_module(type(cfg).__module__).make_probe_fn(cfg)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        if seed != seeds[0]:
            engine._params = params = None         # one set of weights on the device at a time
            engine._params = params = cfg.init_params(seed)
        rng = np.random.default_rng(seed + 1)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=prompt_tokens)]
        results = []
        for _ in range(2):
            seq = batching._Sequence(
                {"prompt": prompt, "max_new_tokens": new, "return_logits": True})
            while not seq.done:
                engine.step([seq])
            if seq._error is not None:
                raise seq._error
            results.append(seq._result)
        first, again = results
        served_s = time.perf_counter() - t0
        fed = prompt + first["tokens"][:-1]
        want = np.asarray(ref.program_logits(params, fed, config, new))
        per_row = [yardstick.logits_error(first["logits"][r], want[r]) for r in range(new)]
        firsts = [n for n in (16, 32, 64, 128) if n < new]

        def by_rows(other):
            return {n: yardstick.logits_error(first["logits"][:n], other[:n]) for n in firsts}

        row = {
            "seed": seed,
            "cached_tokens": [first["prefix_cached_tokens"], again["prefix_cached_tokens"]],
            "bitwise": bool(
                again["tokens"] == first["tokens"]
                and np.array_equal(again["logits"], first["logits"])),
            "error": yardstick.logits_error(first["logits"], want), "error_of_first": by_rows(want),
            "worst_row": max(per_row), "median_row": float(np.median(per_row)),
            "max_abs": float(np.abs(first["logits"] - want).max()),
            "std": float(np.std(want)),
            "argmax_agree": int((want.argmax(-1) == np.asarray(first["tokens"])).sum()),
            "served_s": served_s, "reference_s": time.perf_counter() - t0 - served_s,
        }
        if seed in wrong_seeds:
            row["wrong"], row["wrong_of_first"] = {}, {}
            for w in ref.WRONG + (ref.LOWER,):
                other = np.asarray(ref.program_logits(params, fed, config, new, w))
                row["wrong"][w] = yardstick.logits_error(first["logits"], other)
                row["wrong_of_first"][w] = by_rows(other)
        if seed in overlap_seeds and hasattr(ref, "program_blocks"):
            theirs = ref.program_blocks(params, fed, config)      # [layers, seq, kv, blocks]
            ours = blocks_of(
                probe, cfg, params, fed, sizes["prefill_chunk"], sizes["cache_buckets"][-1]
            )[..., :theirs.shape[-1]]
            choosing = np.arange(len(fed)) >= cfg.dense_len       # queries with a choice to make
            shared = (ours & theirs)[:, choosing].sum(-1) / theirs[:, choosing].sum(-1)
            row["overlap"] = {
                "queries": int(choosing.sum()),
                "sizes_equal": bool((ours.sum(-1) == theirs.sum(-1)).all()),
                "dense_equal": bool((ours == theirs)[:, ~choosing].all()),
                "blocks_a_query": float(theirs[:, choosing].sum(-1).mean()),
                "mean_by_layer": [float(x) for x in shared.mean((1, 2))],
                "min_by_layer": [float(x) for x in shared.min((1, 2))],
                "decode_form_mean": float(shared[:, -(len(fed) - prompt_tokens):].mean())
                if len(fed) > prompt_tokens else None,
            }
        elif seed in overlap_seeds:
            theirs = ref.program_selection(params, fed, config)
            ours = selection_of(
                probe, cfg, params, fed, sizes["prefill_chunk"], sizes["cache_buckets"][-1])
            choosing = np.arange(len(fed)) >= cfg.topk         # queries with a choice to make
            shared = (ours & theirs)[:, choosing].sum(-1) / theirs[:, choosing].sum(-1)
            row["overlap"] = {
                "queries": int(choosing.sum()), "sizes_equal": bool(
                    (ours.sum(-1) == theirs.sum(-1)).all()),
                "mean_by_layer": [float(x) for x in shared.mean(-1)],
                "min_by_layer": [float(x) for x in shared.min(-1)],
                "decode_form_mean": float(shared[:, prompt_tokens - cfg.topk:].mean())
                if len(fed) > prompt_tokens else None,
            }
        row["seconds"] = time.perf_counter() - t0
        say(json.dumps(row))
        rows.append(row)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    errors = [r["error"] for r in rows]
    say(f"errors over {len(rows)} seeds: min {min(errors):.5f} max {max(errors):.5f}; "
        f"bitwise {all(r['bitwise'] for r in rows)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
