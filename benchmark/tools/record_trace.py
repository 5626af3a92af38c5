"""Record a short device trace of a train cell's step for the tests: what
``trace_reduce.load_xplane`` and ``load_scopes`` read from it, as JSON.

    chiprun -- python3 benchmark/tools/record_trace.py \\
        --workload gptj-train-1chip-fixed-batch --steps 1 \\
        --out chiprun_out/recorded_trace_kernels.json

Holds the chip(s) itself, with no cluster around it: the cell's model from the
seed, its job's batch and mesh, the step compiled once and warmed, then
``--steps`` steps under ``SubWindowTrace`` as the train loop takes them. Kept
are the first device's operations and the host lines that carry the step's
annotation; ``tests/benchmark/test_bench_trace_reduce.py`` reduces them again.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce  # noqa: E402
from benchmark.tracing import SubWindowTrace  # noqa: E402
from benchmark.traffic.train_fixed_batch import ANNOTATION  # noqa: E402


def record(cell, seed: int, steps: int):
    """``(planes, scopes, reduced)`` of ``steps`` traced steps of ``cell``."""
    import jax
    import numpy as np

    from ray_tpu.models.training import (
        default_optimizer,
        init_sharded_state,
        make_train_step,
    )
    from ray_tpu.parallel.mesh import MeshSpec

    job = cell.config["job"]
    cfg = importlib.import_module(cell.architecture).program_config(
        manifest.published_keys(cell.config)
    )
    batch = tuple(job["batch"])
    mesh = MeshSpec(**job["mesh"]).build(jax.devices()[:cell.chips])
    opt = default_optimizer(learning_rate=job["learning_rate"])
    state, shardings = init_sharded_state(cfg, mesh, opt, jax.random.PRNGKey(seed), batch)
    step = make_train_step(cfg, opt, mesh, state_shardings_tree=shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), batch, 0, cfg.vocab_size)
    with mesh:
        compiled = step.lower(state, tokens).compile()
        state, m = compiled(state, tokens)
        print("[record] warm step, loss", float(np.asarray(m["loss"])), flush=True)
        trace = SubWindowTrace(ANNOTATION)
        trace.start()
        for _ in range(steps):
            with trace.unit():
                state, m = compiled(state, tokens)
                float(np.asarray(m["loss"]))
        trace.stop()
    (xplane,) = glob.glob(os.path.join(trace.dir, "plugins", "profile", "*", "*.xplane.pb"))
    planes, scopes = trace_reduce.load_xplane(xplane), trace_reduce.load_scopes(xplane)
    return planes, scopes, trace.result()


def trimmed(planes, scopes):
    """The first device plane's operations and module runs, the host lines
    with the step's annotation, and the scopes of the operations kept."""
    device = min(p for p in planes if trace_reduce.DEVICE_PLANE.match(p))
    ops = planes[device][trace_reduce.OPS_LINE]
    runs = planes[device].get(trace_reduce.MODULES_LINE, [])
    host = {
        line: events for line, events in planes[trace_reduce.HOST_PLANE].items()
        if any(name == ANNOTATION for name, _, _ in events)
    }
    names = {name for name, _, _ in ops}
    modules = {trace_reduce.module_of(name) for name, _, _ in runs} | {""}
    return {
        "planes": {
            device: {trace_reduce.OPS_LINE: ops, trace_reduce.MODULES_LINE: runs},
            trace_reduce.HOST_PLANE: host,
        },
        "scopes": {
            module: {n: s for n, s in of.items() if n in names}
            for module, of in scopes.items() if module in modules
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = manifest.Manifest(ROOT).cell(args.workload)
    planes, scopes, reduced = record(cell, args.seed, args.steps)
    kept = trimmed(planes, scopes)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(kept, f, separators=(",", ":"))
    for key in ("window_s", "busy_s", "ops_by_scope"):
        print(f"[record] {key}: {json.dumps(reduced[key])}", flush=True)
    print(f"[record] ops_by_kernel: {json.dumps(reduced['ops_by_kernel'][:16])}", flush=True)
    print(
        f"[record] modules {({m: len(of) for m, of in kept['scopes'].items()})} of {len(scopes)} and "
        f"{sum(len(e) for p in kept['planes'].values() for e in p.values())} events kept in "
        f"{args.out} ({os.path.getsize(args.out)} B)", flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
