"""What a device trace calls the kernels and the scoped steps, and the device's
self time by scope: the numbers of PERF.md section 6, "What the trace calls
things". ``jax.profiler.ProfileData`` (and so ``benchmark/trace_reduce``)
shows an event's name and its own statistics; a ``jax.named_scope`` reaches
neither. It sits in the statistics of the event's *metadata* (``tf_op``) and
in each instruction's ``op_name`` inside the ``Hlo Proto`` of the
``/host:metadata`` plane. No protobuf schema for either is installed here, so
this reads the file's wire format itself (field numbers from tsl's
``xplane.proto`` and xla's ``hlo.proto``).

    chiprun -- python3 scripts/xplane_scopes.py --record chiprun_out/scopes
    python3 scripts/xplane_scopes.py some.xplane.pb ...

``--record`` holds the chip: a GPT-J-width train step at depth 2 (2 x 2048)
and ``extend`` at 4 lanes x 32 tokens x cache 256, three calls each under a
profiler session (``--tiny``: ``gpt_nano`` sizes, for a CPU try).
"""

import collections
import glob
import os
import shutil
import struct
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMED = (
    "train.forward", "train.loss", "train.optimizer",
    "extend.embed", "extend.attention", "extend.mlp", "extend.logits",
)
# as an op_name holds them, most specific first: a scope's backward is
# transpose(jvp(scope)), its forward under grad jvp(scope)
SCOPES = tuple(
    form.format(s) for s in NAMED for form in ("transpose(jvp({}))", "jvp({})", "{}")
)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


# -- the wire format ---------------------------------------------------------


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return x, i


def fields(b):
    """``(field number, wire type, value)`` of one message; a length-delimited
    value is the bytes, to be read on as a string or a message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def _text(v):
    return bytes(v).decode("utf8", "replace")


def _map_entry(b):
    key = value = None
    for number, _, v in fields(b):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(b, stat_names):
    """One XStat: its name and its value (a ``ref_value`` through the names)."""
    key = value = None
    for number, _, v in fields(b):
        if number == 1:
            key = stat_names.get(v, v)
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, v)
    return key, value


def _plane(b):
    """An XPlane: its name, its lines' events as (metadata id, offset ps,
    duration ps), and its event metadata with their statistics."""
    plane = {"name": "", "lines": [], "metadata": {}}
    lines, metadata, stat_names = [], [], {}
    for number, _, v in fields(b):
        if number == 2:
            plane["name"] = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            metadata.append(v)
        elif number == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for n, _, x in fields(value) if n == 2), "")
    for entry in metadata:
        key, value = _map_entry(entry)
        md = {"name": "", "stats": {}}
        for number, _, v in fields(value):
            if number == 2:
                md["name"] = _text(v)
            elif number == 5:
                k, x = _stat(v, stat_names)
                md["stats"][k] = x
        plane["metadata"][key] = md
    for raw in lines:
        line = {"name": "", "events": []}
        for number, _, v in fields(raw):
            if number == 2:
                line["name"] = _text(v)
            elif number == 4:
                event = dict.fromkeys((1, 2, 3), 0)
                event.update((n, x) for n, w, x in fields(v) if w == 0)
                line["events"].append((event[1], event[2], event[3]))
        plane["lines"].append(line)
    return plane


def load(path):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for number, _, v in fields(space) if number == 1]


def hlo_op_names(planes):
    """``{instruction name: op_name}`` over every HLO module that the trace
    carries as a serialized ``HloProto`` statistic (``/host:metadata``)."""
    names = {}
    for plane in planes:
        for md in plane["metadata"].values():
            for blob in md["stats"].values():
                if not isinstance(blob, bytes):
                    continue
                for number, wire, module in fields(memoryview(blob)):
                    if number != 1 or wire != 2:            # HloProto.hlo_module
                        continue
                    for n2, w2, computation in fields(module):
                        if n2 != 3 or w2 != 2:              # HloModuleProto.computations
                            continue
                        for n3, w3, instruction in fields(computation):
                            if n3 != 2 or w3 != 2:          # .instructions
                                continue
                            name = op = ""
                            for n4, w4, v in fields(instruction):
                                if n4 == 1 and w4 == 2:
                                    name = _text(v)
                                elif n4 == 7 and w4 == 2:   # OpMetadata.op_name
                                    op = next((_text(x) for n, w, x in fields(v) if n == 2 and w == 2), "")
                            names[name] = op
    return names


# -- the report ---------------------------------------------------------------


def report(path, say=print):
    from benchmark import trace_reduce

    planes = load(path)
    op_names = hlo_op_names(planes)
    say(f"== {path}: {len(op_names)} instructions in the trace's HLO protos; op_names under a scope:",
        {s: sum(s in op for op in op_names.values()) for s in NAMED})
    for plane in planes:
        ops = [line for line in plane["lines"] if line["name"] == trace_reduce.OPS_LINE]
        if not plane["name"].startswith("/device:") or not ops:
            continue
        short = {k: trace_reduce.short_name(md["name"]) for k, md in plane["metadata"].items()}
        tf_op = {short[k]: md["stats"].get("tf_op", "") for k, md in plane["metadata"].items()}
        with_tf_op = [n for n, op in tf_op.items() if op]
        say(f"  {plane['name']}: {len(with_tf_op)} of {len(tf_op)} event metadata carry tf_op; it is a "
            f"prefix of the HLO proto's op_name for "
            f"{sum(1 for n in with_tf_op if op_names.get(n, '').startswith(tf_op[n].rstrip(':')))}")
        say("  scope in an event's name:",
            sorted({s for s in SCOPES for n in tf_op if s in n}) or "never",
            "; kernels by event name:",
            sorted({n for n in tf_op if n.startswith(KERNELS)}))
        by_scope, kernels, unscoped = (collections.Counter() for _ in range(3))
        events = [(short[mid], float(off), float(dur)) for mid, off, dur in ops[0]["events"]]
        for name, start, end in trace_reduce.self_segments(events):
            op = tf_op.get(name) or op_names.get(name, "")
            label = next((s for s in SCOPES if s in op), "(no scope)")
            by_scope[label] += end - start
            if name.startswith(KERNELS):
                kernels[f"{name.rsplit('.', 1)[0]} under {label}"] += end - start
            if label == "(no scope)":
                unscoped[name.rsplit(".", 1)[0]] += end - start
        total = sum(by_scope.values())
        say(f"  device self time {total / 1e9:.3f} ms, by scope:")
        for label, t in by_scope.most_common():
            say(f"     {label:34s} {t / 1e9:10.3f} ms {100 * t / total:5.1f} %")
        for label, t in kernels.most_common():
            say(f"     kernel {label:48s} {t / 1e9:10.3f} ms {100 * t / total:5.1f} %")
        say("     largest without a scope (ms):",
            [(n, round(t / 1e9, 3)) for n, t in unscoped.most_common(5)])
        return by_scope
    say("  no device plane with an", trace_reduce.OPS_LINE, "line (a CPU trace has none)")
    return None


# -- recording ---------------------------------------------------------------


def _profile(call, n=3):
    import jax

    where = tempfile.mkdtemp(prefix="scopes_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(where, profiler_options=options)
    try:
        for _ in range(n):
            jax.block_until_ready(call())
    finally:
        jax.profiler.stop_trace()
    return glob.glob(os.path.join(where, "plugins", "profile", "*", "*.xplane.pb"))[0]


def record(out_dir, tiny=False):
    """Traces of the train step and of ``extend``; returns their paths."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.models.training import abstract_state, default_optimizer, make_train_step
    from ray_tpu.serve import llm

    os.makedirs(out_dir, exist_ok=True)
    print("devices:", jax.devices())
    if tiny:
        cfg, batch, (b, tc, cap) = gpt.gpt_nano(), (2, 64), (1, 8, 64)
    else:
        cfg = gpt.gpt_j_6b(num_layers=2, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        batch, (b, tc, cap) = (2, 2048), (4, 32, 256)
    optimizer = default_optimizer()
    init, _ = abstract_state(cfg, optimizer, jax.ShapeDtypeStruct(batch, jnp.int32))
    held = {"state": jax.jit(lambda key: nn.meta.unbox(init(key)))(jax.random.PRNGKey(0))}
    step = make_train_step(cfg, optimizer, donate=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), batch, 0, cfg.vocab_size)

    def train():
        held["state"], metrics = step(held["state"], tokens)
        return metrics["loss"]

    print("train loss", float(train()))                     # compiles
    paths = [shutil.copy(_profile(train), os.path.join(out_dir, "train.xplane.pb"))]
    held.clear()
    extend = gpt.make_extend_fn(cfg)
    kv = jnp.zeros((cfg.num_layers, b, cap, cfg.num_heads, cfg.head_dim), cfg.dtype)
    args = (llm.make_params(cfg, 0), jnp.zeros((b, tc), jnp.int32), jnp.zeros((b,), jnp.int32), kv, kv)
    jax.block_until_ready(extend(*args))
    paths.append(shutil.copy(_profile(lambda: extend(*args)), os.path.join(out_dir, "extend.xplane.pb")))
    return paths


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--record" in argv:
        where = argv[argv.index("--record") + 1]
        argv = record(where, tiny="--tiny" in argv)
    for trace in argv:
        if not trace.startswith("--"):
            report(trace)
