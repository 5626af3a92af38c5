"""Of the engine's loaded time, what it stood still: 100 x ``held.excess_s`` over
``phase_s["step"]`` + ``host["llm.between"].wall_s`` (the steps and the time
between two steps while a sequence was active), as deltas of ``kv_stats`` over
lead-in, window and drain. ``held.excess_s`` sums, over the steps and the
times between that ``accelerator.HostWatch`` found held, what nothing explains
of each: its wall less its landings' usual waits, past 50 ms. A program
without the groups, or an engine that ran no step: nothing."""


def read(run):
    counters = run.get("counters") or {}
    held, spent = counters.get("held"), counters.get("phase_s") or {}
    between = (counters.get("host") or {}).get("llm.between")
    if held is None or between is None or not spent.get("step"):
        return None
    return 100.0 * held["excess_s"] / (spent["step"] + between["wall_s"])
