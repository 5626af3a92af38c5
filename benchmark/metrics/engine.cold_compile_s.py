"""Wall seconds of the first calls ``engine.cold_programs`` counts:
``programs_cold_s`` of ``kv_stats`` as a delta over lead-in, window and
drain: what the dispatches that met a program nothing had run yet took,
trace and compile or cache load together. A stall of seconds in a step is
this, or it is not a compile. A program that keeps no such count: nothing."""


def read(run):
    spent = (run.get("counters") or {}).get("programs_cold_s")
    return None if spent is None else float(spent)
