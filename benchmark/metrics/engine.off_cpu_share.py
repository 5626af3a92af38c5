"""Of the host's own part of its steps, the share its thread was not on a CPU:
100 x (1 - ``host["llm.step"].cpu_s`` / (``phase_s["step"]`` -
``phase_s["fetch"]``)), as deltas of ``kv_stats``: the engine thread's CPU
seconds (``getrusage(RUSAGE_THREAD)`` round every step) against the wall of
everything in a step but the wait for the device. Beside
``engine.host_share``, which says how large that part is. A thread that spins
while it waits for the device reads under 0. A program without the group, or
a step that is all wait: nothing."""


def read(run):
    counters = run.get("counters") or {}
    step, spent = (counters.get("host") or {}).get("llm.step"), counters.get("phase_s") or {}
    own = spent.get("step", 0.0) - spent.get("fetch", 0.0)
    if step is None or own <= 0:
        return None
    return 100.0 * (1.0 - step["cpu_s"] / own)
