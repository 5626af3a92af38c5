"""The open-loop generator offers every seed the same work, and the readers
of the serving tails count from when a request was due."""

import json
import os

import pytest

import bench_helpers
from benchmark import manifest
from benchmark.traffic import serve_open_loop

with open(os.path.join(bench_helpers.REPO, "benchmark", "traffic", "chat-steady.json")) as f:
    CHAT = {**json.load(f), "vocab_size": 50400}


def _measured(seed, seconds=51.0):
    return [r for r in serve_open_loop.schedule(CHAT, seed, seconds) if r["measured"]]


def test_every_seed_offers_the_same_work_at_the_same_times_with_its_own_tokens():
    a, b = _measured(7), _measured(2**31 + 11)
    shape = lambda rs: [(r["due"], len(r["prompt"]), r["n_out"]) for r in rs]  # noqa: E731
    assert shape(a) == shape(b)
    # a window of the run's length holds exactly one cycle of the ten pairs
    assert [(n, o) for _, n, o in shape(a)] == list(
        zip(CHAT["prompt_tokens"], CHAT["output_tokens"])
    )
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))   # token ids from the seed
    assert _measured(7) == a                                 # the same seed, the same inputs


def test_arrivals_keep_the_rate_and_stay_in_their_window():
    interval = 1.0 / CHAT["rate_rps"]
    rs = serve_open_loop.schedule(CHAT, 3, 51.0)
    before = [r for r in rs if r["index"] < 0]
    after = [r for r in rs if not r["measured"] and r["index"] >= 0]
    assert len(before) == CHAT["lead_in_requests"] and all(r["due"] < 0 for r in before)
    assert len(after) == CHAT["lead_out_requests"] and all(r["due"] >= 51.0 for r in after)
    assert [r["index"] for r in rs] == sorted(r["index"] for r in rs)
    for r in rs:
        if r["measured"]:
            assert 0 <= r["due"] < 51.0
        if 0 < r["index"] < 9:
            assert abs(r["due"] - r["index"] * interval) <= 0.3 * interval + 1e-9
        assert all(0 <= t < 50400 for t in r["prompt"])
    # no two prompts share a first block: the prefix cache has nothing to reuse
    assert len({tuple(r["prompt"][:16]) for r in rs}) == len(rs)


def test_tails_are_timed_from_due_and_a_failure_counts_as_the_drain_limit():
    book = manifest.Manifest(bench_helpers.REPO)
    ok = {"due": 1.0, "sent": 1.5, "done": 5.5, "ttft_s": 2.0, "n_out": 3, "ok": True}
    run = {"kind": "serve", "window_s": 10.0, "drain_limit_s": 40.0, "records": [ok]}
    assert book.reader("ttft_p95_s")(run) == pytest.approx(2.5)       # 0.5 late + 2.0
    assert book.reader("tpot_p95_s")(run) == pytest.approx(1.0)       # (5.5-1.5-2.0)/2
    assert book.reader("loadgen.late_p95_ms")(run) == pytest.approx(500.0)
    assert book.reader("request_latency_mean_s")(run) == pytest.approx(4.5)   # 5.5 - 1.0
    run["records"] = [ok] * 9 + [{"due": 2.0, "sent": 2.0, "n_out": 5, "ok": False}]
    assert book.reader("ttft_p95_s")(run) == 40.0
    assert book.reader("tpot_p95_s")(run) == 40.0
    assert book.reader("request_latency_mean_s")(run) == pytest.approx(0.9 * 4.5 + 4.0)


class _Answer:
    def __init__(self, value):
        self.value = value

    def result(self, timeout=None):
        return self.value


class _GateHandle:
    """A replica whose logits are ``served`` where its reference's are ``want``."""

    def __init__(self, served, want):
        self.asked, self.served, self.want = 0, served, want
        self.reference_logits = self

    def remote(self, *args):
        if len(args) == 2:                      # reference_logits(tokens, last)
            return _Answer(self.want)
        self.asked += 1
        cached = 0 if self.asked == 1 else 32
        return _Answer({
            "tokens": [1, 2, 3], "logits": self.served, "prefix_cached_tokens": cached,
        })


@pytest.mark.parametrize("off, passes", [(0.0, True), (0.04, True), (0.06, False)])
def test_the_gate_holds_the_servers_logits_to_the_reference(off, passes):
    import numpy as np

    want = np.random.default_rng(0).normal(size=(3, 256)).astype(np.float32)
    want /= want.std()
    served = want + np.float32(off)            # a constant shift: error = off / std = off
    problems = serve_open_loop._gate(
        _GateHandle(served, want),
        {"gate_prompt_tokens": 40, "gate_new_tokens": 3, "block_size": 16,
         "vocab_size": 256}, 5,
        {"module": "gptj_reference", "program_layer_norm_epsilon": 1e-6,
         "max_logits_error": 0.05},
    )
    assert (problems == []) == passes
    assert passes or "over the limit 0.05" in problems[0]


def test_counters_are_the_deltas_of_every_number_the_engine_reports():
    before = {
        "steps": 10, "queue_s": 0.5, "phase_s": {"step": 1.0, "fetch": 0.5}, "new_later": 1,
        "device": {"kind": "cpu", "peak_bytes_in_use": 100}, "adapters_resident": [],
        "slowest_step": None, "prefix_hits": 0, "flag": True,
    }
    after = {
        "steps": 25, "queue_s": 0.75, "phase_s": {"step": 2.5, "fetch": 1.0, "moe": 0.1},
        "new_later": 4, "device": {"kind": "cpu", "peak_bytes_in_use": 130},
        "adapters_resident": ["x"], "slowest_step": {"wall_s": 0.1}, "prefix_hits": 0,
        "flag": True, "only_after": 3,
    }
    assert serve_open_loop.counter_deltas(after, before) == {
        "steps": 15, "queue_s": 0.25, "phase_s": {"step": 1.5, "fetch": 0.5}, "new_later": 3,
        "device": {"peak_bytes_in_use": 30}, "prefix_hits": 0,
    }


def test_the_server_takes_the_engines_own_warm_up_where_it_has_one(monkeypatch):
    """Until ``LLMEngine`` owns its warm-up the benchmark builds ``extend``'s
    arguments itself; an engine with a ``warm()`` is asked instead."""
    from ray_tpu.serve import llm

    from benchmark import server

    tiny = bench_helpers.tiny("gptj")
    engine = next(c["engine"] for c in tiny["cells"] if "engine" in c)
    replica = server.BenchLLMServer(
        "benchmark.models.gptj", "benchmark.reference.gptj_reference",
        {**tiny["model"], "reference": tiny["reference"]}, seed=3, **engine,
    )
    warm = replica.warm()
    # lanes (1, 4) x tokens (1, 8, 32) x cache (128)
    assert warm["shapes"] == 6 and warm["warm_s"] > 0 and warm["weights_s"] > 0
    assert warm["vocab_size"] == tiny["model"]["vocab_size"] and "depth 2" in warm["model"]
    assert warm["compiled"] is None or warm["compiled"]["shape"] == [4, 32, 128]
    monkeypatch.setattr(
        llm.LLMEngine, "warm", lambda self: {"shapes": 99, "warm_s": 0.25, "compiled": None},
        raising=False,
    )
    monkeypatch.setattr(replica._engine, "_extend", None)             # not called
    own = replica.warm()
    assert (own["shapes"], own["warm_s"], own["compiled"]) == (99, 0.25, None)
    assert own["weights_s"] == warm["weights_s"] and own["vocab_size"] == warm["vocab_size"]
    # the reference reads the very weights the engine serves
    logits = replica.reference_logits([1, 2, 3, 4, 5], 2)
    assert logits.shape == (2, tiny["model"]["vocab_size"])
