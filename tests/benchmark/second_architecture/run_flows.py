"""``python run_flows.py <root> <cell> ...``: each cell's traced run through
``run.run_cell`` on CPU workers, with ``<root>``'s copy of the benchmark first
on the path (so that the workers import it too) and the chip check stubbed."""

import json
import os
import sys

root = sys.argv[1]
sys.path[:0] = [root, os.path.join(root, "tests", "benchmark")]

from benchmark import chip, contract, run as run_mod, yardstick  # noqa: E402

chip.PLATFORM = "cpu"
yardstick.PEAKS["cpu"] = {"bf16_flops": 1e12}
for name in sys.argv[2:]:
    line, cell, run = run_mod.run_cell(root, name, 2**31 + 9, 1.5, True)
    print("FLOW " + json.dumps({
        "cell": name, "line": line, "architecture": cell.architecture,
        "reference": cell.reference, "module_file": sys.modules[cell.architecture].__file__,
        "problems": contract.violations(line, cell.metrics(True), True),
        "flops_per_step": run.get("flops_per_step"),
    }), flush=True)
