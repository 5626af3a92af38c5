"""What the benchmark's tests share: a temporary copy of the manifest and the
benchmark's own directories with tiny configurations and cells added to it — by
new files and new entries alone, the way a later PR adds them — and the checks
every manifest has to pass, the real one and such a copy alike.

``tiny/<model_type>.json`` holds an architecture's tiny model and the twins
built from it; the tests run their flows once per file there."""

import importlib
import importlib.util
import json
import os
import re
import shutil

from benchmark import models

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join("tests", "benchmark", "tiny")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

TINY_CHAT = {
    "generator": "serve_open_loop",
    "prompt_tokens": [20, 50, 9, 70, 33], "output_tokens": [4, 3, 6, 2, 5],
    "rate_rps": 8.0, "due_offsets": [0.1, -0.2, 0.3, -0.1, 0.2], "lead_in_requests": 2,
    "lead_out_requests": 2,
    "drain_limit_s": 20.0, "gate_prompt_tokens": 40, "gate_new_tokens": 3,
    "trace_from": 0.1, "trace_seconds": 0.5,
}


def tiny(model_type: str, root: str = REPO) -> dict:
    with open(os.path.join(root, TINY, model_type + ".json")) as f:
        return json.load(f)


def tiny_architectures(root: str = REPO) -> list:
    return sorted(f[:-5] for f in os.listdir(os.path.join(root, TINY)) if f.endswith(".json"))


def twins(kind: str, chips: int, root: str = REPO) -> list:
    """``(model_type, twin cell)`` of every tiny cell with a ``job``
    (``kind`` "train") or an ``engine`` ("serve") on ``chips`` chips."""
    key = {"train": "job", "serve": "engine"}[kind]
    return [
        (arch, cell["name"]) for arch in tiny_architectures(root)
        for cell in tiny(arch, root)["cells"] if key in cell and cell["chips"] == chips
    ]


def files_under(root: str, folders=("benchmark", os.path.join("tests", "benchmark"))) -> dict:
    """``{relative path: bytes}`` of every file of the benchmark's directories."""
    out = {}
    for folder in folders:
        for d, _, files in os.walk(os.path.join(root, folder)):
            if "__pycache__" in d:
                continue
            for p in files:
                path = os.path.join(d, p)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = f.read()
    return out


def copy_benchmark(tmp_path) -> str:
    """The manifest and the benchmark's own directories, copied; returns the root."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for folder in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(
            os.path.join(REPO, folder), os.path.join(root, folder),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return root


def edit_manifest(root: str, edit) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        book = json.load(f)
    edit(book)
    with open(path, "w") as f:
        json.dump(book, f)


def add_tiny_cells(root: str) -> None:
    """The twins of every ``tiny/<model_type>.json`` of ``root``: a
    configuration file and an entry each, and a place beside the cell each
    mirrors in every metric that cell reports."""
    def write(rel, data):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)

    write("benchmark/traffic/tiny-chat.json", TINY_CHAT)
    cells = [
        (tiny(arch, root), cell) for arch in tiny_architectures(root)
        for cell in tiny(arch, root)["cells"]
    ]
    for spec, cell in cells:
        sizes = {k: cell[k] for k in ("job", "engine") if k in cell}
        write(f"benchmark/configs/{cell['config']}.json",
              {**spec["model"], "reference": spec["reference"], **sizes})

    def edit(book):
        twin = {}
        for _, cell in cells:
            book["configs"].append({
                "name": cell["config"], "source": "tests", "reduced": [], "why": "tests",
                "file": f"benchmark/configs/{cell['config']}.json",
            })
            book["workloads"].append({
                "name": cell["name"], "config": cell["config"], "traffic": cell["traffic"],
                "chips": cell["chips"], "why": "tests",
            })
            twin.setdefault(cell["mirrors"], []).append(cell["name"])
        for m in book["end_to_end"] + book["per_layer"]:
            # a tiny cell reports what the real cell it mirrors reports
            m.get("workloads", []).extend(
                t for w in list(m.get("workloads", [])) for t in twin.get(w, [])
            )

    edit_manifest(root, edit)


# -- what every manifest has to pass ------------------------------------------


def check_manifest(book) -> None:
    data = book.data
    assert set(data) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
        "per_layer",
    }
    assert 1 <= data["run_seconds"] <= 51 and data["paths"][0] == "benchmark"
    e2e = {m["name"] for m in data["end_to_end"]}
    assert "setup_s" in e2e
    for m in data["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in data["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e and "bound" not in m
    for c in data["configs"]:
        assert c["file"].startswith("benchmark/configs/") and len(c["why"]) <= 200
    assert len(json.dumps(data)) < 64 * 1024


def check_cell(book, name: str) -> None:
    """The cell loads with its architecture, its reference, its generator and
    a reader for every metric, and a reader with nothing to read gives nothing."""
    cell = book.cell(name)
    architecture = importlib.import_module(cell.architecture)
    for what in models.REQUIRED:
        assert hasattr(architecture, what), (cell.architecture, what)
    reference = importlib.import_module(cell.reference)
    assert callable(reference.program_loss) and callable(reference.program_logits)
    assert callable(book.generator(cell).run)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert book.reader(m["name"])({}) is None          # nothing to read: nothing
    for m in cell.per_layer:
        assert m["moves"] in e2e


def check_configuration(root: str, entry: dict) -> None:
    """Widths never cut, whatever the architecture: every key of its ``WIDTHS``
    is as published, and the keys that differ from ``published`` are exactly
    ``reduced``, in the manifest and in the file."""
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "widths_of_" + config["model_type"],
        os.path.join(root, "benchmark", "models", config["model_type"] + ".py"),
    )
    architecture = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(architecture)        # by path: ``root`` may be a copy
    published = config["published"]
    for key in architecture.WIDTHS:
        assert config[key] == published[key], f"{entry['name']}: width {key} is cut"
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(entry["reduced"]) == set(config["reduced"])
    assert config["source"] == entry["source"] and config["assumed"]
