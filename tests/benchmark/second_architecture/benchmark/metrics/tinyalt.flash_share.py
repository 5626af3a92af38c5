"""Share of the traced sub-window's device time spent in the flash attention
kernels (forward and both backward kernels), from ``ops_by_kernel``."""


def read(run):
    ops = dict(map(tuple, (run.get("trace") or {}).get("ops_by_kernel") or []))
    total = sum(ops.values())
    flash = sum(t for name, t in ops.items() if name.startswith("flash_"))
    return 100.0 * flash / total if flash else None
