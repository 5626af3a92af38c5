"""What the benchmark's tests share: a temporary copy of the manifest and its
data files with a tiny configuration and two tiny cells added to it — by new
files and new entries alone, the way a later PR adds them."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_REFERENCE = {
    # float32 on both sides: a step's loss and the server's logits agree with
    # the reference's to some float32 roundings
    "module": "gptj_reference", "program_layer_norm_epsilon": 1e-6,
    "max_loss_error": 1e-5, "max_logits_error": 1e-3,
}
TINY_MODEL = {
    "model_type": "gptj", "layer_norm_epsilon": 1e-5, "n_embd": 64, "n_head": 4, "n_inner": 256, "n_layer": 2,
    "n_positions": 128, "rotary_dim": 16, "vocab_size": 256,
    "tie_word_embeddings": False, "param_dtype": "float32", "compute_dtype": "float32",
}
TINY_JOB = {
    "batch": [2, 64], "mesh": {}, "learning_rate": 1e-3, "warmup_steps": 3,
    "min_flash_kernels": 0, "trace_from": 0.2, "trace_steps": 2,
}
TINY_ENGINE = {
    "num_blocks": 64, "block_size": 16, "prefill_chunk": 32, "prefill_lanes": 2,
    "lane_buckets": [1, 4], "prefill_token_buckets": [8, 32], "cache_buckets": [128],
}
TINY_CHAT = {
    "generator": "serve_open_loop",
    "prompt_tokens": [20, 50, 9, 70, 33], "output_tokens": [4, 3, 6, 2, 5],
    "rate_rps": 8.0, "due_offsets": [0.1, -0.2, 0.3, -0.1, 0.2], "lead_in_requests": 2,
    "lead_out_requests": 2,
    "drain_limit_s": 20.0, "gate_prompt_tokens": 40, "gate_new_tokens": 3,
    "trace_from": 0.1, "trace_seconds": 0.5,
}


TWIN = {
    "gptj-train-1chip-fixed-batch": "tiny-train-cell",
    "gptj-train-4chip-full-depth": "tiny-train4-cell",
    "gptj-serve-chat-steady": "tiny-serve-cell",
}


def copy_benchmark(tmp_path) -> str:
    """The manifest and the benchmark's own directory, copied; returns the root."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    return root


def add_tiny_cells(root: str) -> None:
    def write(rel, data):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)

    write("benchmark/configs/tiny-train.json", {**TINY_MODEL, "reference": TINY_REFERENCE, "job": TINY_JOB})
    write("benchmark/configs/tiny-train4.json", {
        **TINY_MODEL, "reference": TINY_REFERENCE, "job": {**TINY_JOB, "mesh": {"dp": -1, "fsdp": 2, "tp": 2}},
    })
    write("benchmark/configs/tiny-serve.json", {**TINY_MODEL, "reference": TINY_REFERENCE, "engine": TINY_ENGINE})
    write("benchmark/traffic/tiny-chat.json", TINY_CHAT)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        book = json.load(f)
    for kind in ("train", "train4", "serve"):
        book["configs"].append({
            "name": f"tiny-{kind}", "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmark/configs/tiny-{kind}.json",
        })
    book["workloads"] += [
        {"name": "tiny-train-cell", "config": "tiny-train", "traffic": "fixed-batch",
         "chips": 1, "why": "tests"},
        {"name": "tiny-train4-cell", "config": "tiny-train4", "traffic": "fixed-batch",
         "chips": 4, "why": "tests"},
        {"name": "tiny-serve-cell", "config": "tiny-serve", "traffic": "tiny-chat",
         "chips": 1, "why": "tests"},
    ]
    for m in book["end_to_end"] + book["per_layer"]:
        # a tiny cell reports what the real cell of its kind reports
        m.get("workloads", []).extend(
            TWIN[w] for w in list(m.get("workloads", [])) if w in TWIN
        )
    with open(path, "w") as f:
        json.dump(book, f)
