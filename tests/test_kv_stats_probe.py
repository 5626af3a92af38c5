"""``scripts/kv_stats_probe.py`` against the benchmark's tiny serve cell on CPU
workers: the tool that PERF.md's serve breakdown is read with must keep
working as the engine's counters and the benchmark's flow change."""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

import bench_helpers  # noqa: E402
from benchmark import chip, yardstick  # noqa: E402
from ray_tpu.serve import llm  # noqa: E402


def _load():
    spec = importlib.util.spec_from_file_location(
        "kv_stats_probe", os.path.join(REPO, "scripts", "kv_stats_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_probe_keeps_the_reads_the_benchmark_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    monkeypatch.setitem(yardstick.PEAKS, "cpu", {"bf16_flops": 1e12})
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    probe = _load()
    assert probe.LEAF == llm.LEAF_PHASES
    kept = probe.probe(root, "tiny-serve-cell", 2**31 + 5, 1.5, True, poll_s=0.2)
    assert kept["line"]["correct"] and kept["line"]["failed"] == 0
    stats0, stats1 = (s["stats"] for s in kept["snaps"][-2:])
    d = probe.delta(stats1, stats0)
    lead = bench_helpers.TINY_CHAT["lead_in_requests"] + bench_helpers.TINY_CHAT["lead_out_requests"]
    assert d["steps"] > 0 and d["admitted"] == len(kept["records"]) + lead
    assert sum(d["phase_s"][p] for p in probe.LEAF) == pytest.approx(d["phase_s"]["step"], rel=0.1)
    assert d["phase_n"]["dispatch"] == d["phase_n"]["prefill"] + d["phase_n"]["decode"]
    # every result's queue_s arrives: the window's requests, lead-in and lead-out
    assert len(kept["queue_s"]) == len(kept["records"]) + lead
    assert all(q >= 0 for q in kept["queue_s"])
    assert any("stats" in p for p in kept["polled"])
    said = []
    probe.report(kept, say=lambda *a: said.append(" ".join(map(str, a))))
    text = "\n".join(said)
    assert "kv_gather" in text and "lane_fill" in text and "slowest_step" in text
    # the tiny cell asks for no logits and no adapter: one buffer up, one array down
    calls = d["phase_n"]["dispatch"]
    assert d["h2d_transfers"] == d["d2h_transfers"] == d["ids_only_calls"] == calls
    assert f"transfers a call: 1.00 up, 1.00 down; ids-only calls {calls}/{calls}" in text
    # ... and every call is landed, most of them behind their successor
    assert d["phase_n"]["fetch"] == d["phase_n"]["sample"] == calls
    assert 0 < d["calls_ahead"] < calls and 0 < d["tokens_fed_on_device"] <= d["decode_tokens"]
    assert (f"run-ahead share {d['calls_ahead']}/{calls} = {d['calls_ahead'] / calls:.3f}" in text
            and f"tokens fed on the device {d['tokens_fed_on_device']}/{d['lanes_used']}" in text)
    # the two forms of call, from the same two reads, and the split of the recorded steps
    by_form = d["calls"]
    assert [by_form[form]["n"] for form in llm.FORMS] == [d["phase_n"]["prefill"], d["phase_n"]["decode"]]
    for form in llm.FORMS:
        c = by_form[form]
        assert f"{form} calls: n={c['n']}, {1e3 * c['busy_s'] / c['n']:.2f} ms each" in text
        assert f"lanes {c['lanes_used']}/{c['lane_slots']}" in text
    recorded = d["traced"]["steps"]
    assert 0 < recorded == kept["trace"]["engine"]["steps"] < d["steps"]
    assert f"of them, the {recorded} steps a profiler session recorded: {recorded} steps" in text
    assert f"decode calls: n={d['traced']['calls']['decode']['n']}," in text


@pytest.mark.parametrize("cell", [c for _, c in bench_helpers.twins("serve", 1)])
def test_fixed_schedule_makes_the_same_calls_and_ids_every_time(tmp_path, monkeypatch, cell):
    """One cycle of the cell's requests, stepped in this process on a schedule
    counted in steps: the ids and the engine's counts are the same in two
    runs, every request gets its length, and nothing is left in flight."""
    monkeypatch.setattr(chip, "PLATFORM", "cpu")
    root = bench_helpers.copy_benchmark(tmp_path)
    bench_helpers.add_tiny_cells(root)
    probe = _load()
    first, again = (probe.fixed_schedule(root, cell, 2**31 + 9) for _ in range(2))
    assert first["errors"] == [] and first["sha256"] == again["sha256"]
    assert [len(t) for t in first["ids"]] == bench_helpers.TINY_CHAT["output_tokens"]
    counted = ("steps", "decode_tokens", "prefill_tokens", "calls_ahead", "tokens_fed_on_device")
    assert [first["delta"][k] for k in counted] == [again["delta"][k] for k in counted]
    d = first["delta"]
    assert d["phase_n"]["fetch"] == d["phase_n"]["dispatch"]
    assert d["kv_blocks_in_use"] == d["prefix_cached_blocks"]       # nothing leaked
    # a call is launched behind every call but the first of a busy stretch
    assert d["phase_n"]["dispatch"] / 2 < d["calls_ahead"] < d["phase_n"]["dispatch"]
    said = []
    probe.report_fixed(first, say=lambda *a: said.append(" ".join(map(str, a))))
    assert f"ids sha256 {first['sha256']}" in "\n".join(said) and "run-ahead share" in "\n".join(said)
    # what the cell's model counts is printed under its own names: an expert
    # layer's pairs, an indexer's slots read of the slots gathered
    assert ("experts:" in "\n".join(said)) == ("moe_tokens" in d)
    assert ("indexer:" in "\n".join(said)) == ("sparse_queries" in d)
    if "sparse_queries" in d:
        assert 0 < d["sparse_slots_read"] <= d["sparse_slots_gathered"]
        assert f"slots read {d['sparse_slots_read']}/{d['sparse_slots_gathered']}" in "\n".join(said)
