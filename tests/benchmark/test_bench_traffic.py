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
        if len(args) == 3:                      # reference_logits(reference, tokens, last)
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
        {"gate_prompt_tokens": 40, "gate_new_tokens": 3, "block_size": 16}, 5,
        {"vocab_size": 256},
        {"module": "gptj_reference", "program_layer_norm_epsilon": 1e-6,
         "max_logits_error": 0.05},
    )
    assert (problems == []) == passes
    assert passes or "over the limit 0.05" in problems[0]
