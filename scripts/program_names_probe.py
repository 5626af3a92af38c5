"""Whose device seconds a profile of a serve cell holds: one cycle of the cell's
requests through its engine, in this process, under a ``jax.profiler`` session
(the host spans alone from annotations, as the benchmark traces), then the
trace read back:

* every ``llm.dispatch`` span's ``program`` against the module the device ran
  for that call: the ``XLA Modules`` events named ``jit_extend_*`` in launch
  order, which is the order the device runs them in (without a device plane,
  on the CPU: the ``PjitFunction(<name>)`` event inside the span);
* the distinct module names of the session with the compiled programs (the
  ``<id>`` in ``jit_<name>(<id>)``) and the device seconds under each: a name
  that two programs share is what misfiles a ``fusion.N`` in
  ``benchmark/trace_reduce.py``, which keys an instruction's scope by its
  module's name;
* ``ops_by_scope`` of the session as the benchmark's reducer files it, what
  the engine counted (``programs``, ``programs_cold``), and **how** each
  device second found its scope (:func:`filing`): in the table of the module
  whose event covers it, by its instruction's name alone, or not at all, with
  the modules and instructions behind ``(no scope)``.

``--sessions`` repeats the cycle under a session each in the one engine (the
same compiled programs: what differs between two sessions is the profile, not
the compile); ``--requests`` cuts the cycle short.

    chiprun -- python3 scripts/program_names_probe.py --cell kimi-k2-serve-long-context --seed 2147484227

Exits 1 where a span's program is not the module its call ran as, or a name is
shared. ``--root`` names another checkout to read the cell from (a copy with
tiny cells, for a rehearsal off the chip).
"""

import argparse
import bisect
import glob
import json
import os
import re
import sys
import tempfile
import time

STEP = "probe.engine_step"
MODULE = re.compile(r"^(jit_\w+)\((\d+)\)$")


def run_cycle(eng, warm, cycle, where):
    """The engine stepped through the cycle (``kv_stats_probe``'s fixed schedule:
    prefill chunks beside decode lanes) under a profiler session that writes to
    ``where``; what it counted."""
    import jax

    from kv_stats_probe import delta, step_through

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    before, t0 = eng.stats(), time.perf_counter()
    jax.profiler.start_trace(where, profiler_options=options)
    try:
        seqs = step_through(eng, cycle, around=lambda: jax.profiler.TraceAnnotation(STEP))
    finally:
        jax.profiler.stop_trace()
    after = eng.stats()
    counted = delta(after, before)
    return {
        "wall_s": time.perf_counter() - t0, "warm": warm,
        "errors": [repr(s._error) for s in seqs if s._error is not None],
        # a program first called in the cycle is in no earlier read: it counts from zero
        "programs": {**after["programs"], **counted["programs"]},
        "programs_cold": counted["programs_cold"],
        "programs_cold_s": counted["programs_cold_s"],
        "device": after["device"],
    }


def read_back(path):
    """The dispatch spans, the module events and the reduced trace of one xplane."""
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    dispatched, ran_on_host, modules = [], [], []
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        if not device and plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns, e.start_ns + e.duration_ns)
                if device and line.name == trace_reduce.MODULES_LINE:
                    modules.append(span + (e.name,))
                elif not device and e.name == "llm.dispatch":
                    dispatched.append(span + (dict(e.stats),))
                elif not device and e.name.startswith("PjitFunction(extend_"):
                    ran_on_host.append(span + (e.name[len("PjitFunction("):-1],))
    planes, scopes = trace_reduce.load_xplane(path), trace_reduce.load_scopes(path)
    reduced = trace_reduce.reduce(planes, STEP, top=10, scopes=scopes)
    filed = filing(planes, scopes) if modules else None
    return sorted(dispatched), sorted(ran_on_host), sorted(modules), reduced, filed


def filing(planes, scopes):
    """How the benchmark's reducer finds the scope of each device second of the
    session, step by step as ``trace_reduce.reduce`` does it: the module whose
    ``XLA Modules`` event covers the operation's start, then that module's
    table, then the table by instruction name alone."""
    from benchmark import trace_reduce as tr

    window = tr.annotation_window(planes, STEP)
    how_s, unscoped, borrowed, overlaps, untabled = {}, {}, {}, 0, set()
    for name in sorted(p for p in planes if tr.DEVICE_PLANE.match(p)):
        lines = planes[name]
        ops = tr.clip(tr.self_segments(lines.get(tr.OPS_LINE, [])), *window)
        runs = sorted((s, s + d, tr.module_of(n)) for n, s, d in lines.get(tr.MODULES_LINE, []))
        starts = [r[0] for r in runs]
        overlaps += sum(b[0] < a[1] for a, b in zip(runs, runs[1:]))
        untabled |= {r[2] for r in runs if r[2] not in scopes}
        for n, a, b in ops:
            at = bisect.bisect_right(starts, a) - 1
            module = runs[at][2] if at >= 0 and a < runs[at][1] else ""
            table, by_name = scopes.get(module, {}), scopes.get("", {})
            # ``reduce`` falls back on the table by name alone, which every program of
            # the session shares, wherever its module's table gives nothing: also for
            # an instruction the compiler made, whose own op_name is empty
            how = (
                "outside every module's event" if not module
                else "in its module's table" if table.get(n)
                else ("no op_name in its module" if n in table else "not in its module's table")
                + (": by its name alone, from whichever program's was written last"
                   if by_name.get(n) else ": in no table"))
            how_s[how] = how_s.get(how, 0.0) + (b - a) / 1e9
            scope = tr.scope_of(table.get(n) or by_name.get(n, ""))
            if scope == tr.NO_SCOPE:
                key = f"{module or '(none)'} {n} [{how}]"
                unscoped[key] = unscoped.get(key, 0.0) + (b - a) / 1e9
            elif not table.get(n):
                key = f"{module or '(none)'} {n} -> {scope}"
                borrowed[key] = borrowed.get(key, 0.0) + (b - a) / 1e9
    return {
        "how_s": how_s, "module_events_that_overlap": overlaps,
        "modules_without_a_table": sorted(untabled),
        "unscoped": sorted(unscoped.items(), key=lambda kv: -kv[1])[:16],
        "scope_from_another_program": sorted(borrowed.items(), key=lambda kv: -kv[1])[:8],
    }


def check(dispatched, ran_on_host, modules):
    """Problems, the seconds and program ids under each module name, and the
    ``(program, module)`` pair of each call."""
    problems, by_name = [], {}
    for start, end, name in modules:
        m = MODULE.match(name)
        entry = by_name.setdefault(m[1] if m else name, {"ids": set(), "runs": 0, "seconds": 0.0})
        entry["ids"].add(m[2] if m else "")
        entry["runs"] += 1
        entry["seconds"] += (end - start) / 1e9
    for name, entry in by_name.items():
        if len(entry["ids"]) > 1:
            problems.append(f"{len(entry['ids'])} programs run as {name}: {sorted(entry['ids'])}")
    if modules:
        ran = [MODULE.match(n)[1] for _, _, n in modules if n.startswith("jit_extend_")]
        ran = [name[len("jit_"):] for name in ran]
    else:
        # no device plane: the runtime's own event of the call, inside the span
        ran = [
            next((name for a, b, name in ran_on_host if start <= a and b <= end), None)
            for start, end, _ in dispatched]
    pairs = [(what.get("program"), module) for (_, _, what), module in zip(dispatched, ran)]
    if len(ran) != len(dispatched):
        problems.append(f"{len(dispatched)} llm.dispatch spans, {len(ran)} runs of an extend module")
    for i, (program, module) in enumerate(pairs):
        if program != module:
            problems.append(f"call {i}: the span says {program!r}, the device ran {module!r}")
    return problems, by_name, pairs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--cell", default="kimi-k2-serve-long-context")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--sessions", type=int, default=1)
    ap.add_argument("--requests", type=int, default=None, help="of the cycle; all by default")
    args = ap.parse_args()
    from kv_stats_probe import cell_engine      # beside this file: ``sys.path[0]``

    eng, warm, cycle = cell_engine(args.root, args.cell, args.seed)
    return max(
        session(args, i, eng, warm, cycle[:args.requests]) for i in range(args.sessions))


def session(args, index, eng, warm, cycle):
    import shutil

    say = lambda *a: print(f"[names] {index}:", *a, flush=True)   # noqa: E731
    where = tempfile.mkdtemp(prefix="program_names_")
    try:
        counted = run_cycle(eng, warm, cycle, where)
        (path,) = glob.glob(os.path.join(where, "plugins", "profile", "*", "*.xplane.pb"))
        dispatched, ran_on_host, modules, reduced, filed = read_back(path)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    problems, by_name, pairs = check(dispatched, ran_on_host, modules)
    problems += counted["errors"]
    say(f"{args.cell} seed {args.seed}: {len(dispatched)} calls in {counted['wall_s']:.2f} s, "
        f"device {counted['device']}")
    say(f"{len(by_name)} module names on the device's '{'XLA Modules'}' line "
        f"({sum(e['runs'] for e in by_name.values())} runs):")
    for name, entry in sorted(by_name.items(), key=lambda kv: -kv[1]["seconds"]):
        say(f"  {name}: {entry['runs']} runs, {entry['seconds']:.6f} s, "
            f"program ids {sorted(entry['ids'])}")
    called = sorted({program for program, _ in pairs if program})
    say(f"{len(called)} extend programs called: {called}")
    say(f"programs_cold {counted['programs_cold']} ({counted['programs_cold_s']:.3f} s); "
        f"calls by program: "
        + json.dumps({n: c for n, c in counted["programs"].items() if c["n"]}))
    cold = [what for _, _, what in dispatched if what.get("cold")]
    say(f"spans with cold=1: {[(w['call'], w['program']) for w in cold]}")
    if reduced:
        say(f"busy {reduced['busy_s']:.4f} s of {reduced['window_s']:.4f}; ops_by_scope: "
            + json.dumps(reduced["ops_by_scope"][:24]))
    if filed:
        say(f"device seconds by how the reducer found their scope: {json.dumps(filed['how_s'])}; "
            f"{filed['module_events_that_overlap']} module events start before the one before "
            f"ends; modules without a table: {filed['modules_without_a_table']}")
        say("(no scope), by module and instruction: " + json.dumps(filed["unscoped"]))
        say("given a scope by name alone: " + json.dumps(filed["scope_from_another_program"]))
    for p in problems:
        say("PROBLEM:", p)
    say("every llm.dispatch span names the module its call ran as, and no name is shared"
        if not problems else f"{len(problems)} problems")
    os.makedirs(args.out, exist_ok=True)
    kept = os.path.join(args.out, f"program_names_{args.cell}_{args.seed}_{index}.json")
    with open(kept, "w") as f:
        json.dump({
            "cell": args.cell, "seed": args.seed, "problems": problems, "pairs": pairs,
            "modules": {n: {**e, "ids": sorted(e["ids"])} for n, e in by_name.items()},
            "counted": counted, "reduced": reduced, "filing": filed,
        }, f, default=str)
    say("kept in", kept)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
