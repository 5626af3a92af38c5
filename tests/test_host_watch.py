"""``accelerator.HostWatch``: what a unit of host work cost its thread, the four
causes a held unit can have, each planted and read back as itself with the stack
of the frame that held it, the watcher that samples nothing before a unit is
overdue, the collector's hook, and the train session's unit from report to report."""

import gc
import threading
import time

import pytest

from ray_tpu._private import accelerator
from ray_tpu._private.accelerator import HostBook

pytestmark = pytest.mark.limit(60)


@pytest.fixture
def watch():
    return accelerator.host_watch()


@pytest.fixture
def book():
    return HostBook("work")


def _spin(seconds):
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        pass


class _Node:
    pass


@pytest.fixture
def cycles():
    """A large graph of cycles, built with the collector off; dropping it leaves
    the collector, and nobody else, some 0.1 s of work."""
    gc.collect()
    gc.disable()
    try:
        graph = []
        for _ in range(500_000):
            a, b = _Node(), _Node()
            a.other, b.other = b, a
            graph.append(a)
        yield graph
    finally:
        gc.enable()


def _held_by(watch, book, plant):
    with watch.unit("work", book, where="the test") as unit:
        plant()
    assert unit.record is not None and unit.record is book.steps[-1]
    return unit.record


# -- the four causes, each planted and read as itself ----------------------------


def test_a_collection_inside_a_unit_reads_gc(watch, book, cycles):
    def plant():
        cycles.clear()
        gc.collect()

    collected = watch.gc_totals()
    held = _held_by(watch, book, plant)
    assert held["cause"] == "gc" and held["gc_s"] >= held["excess_s"] / 2 > 0.025
    after = watch.gc_totals()
    assert after["n"] > collected["n"] and after["longest_s"] >= held["gc_s"] * 0.9
    assert after["generations"]["2"]["n"] == collected["generations"]["2"]["n"] + 1
    assert after["s"] - collected["s"] == pytest.approx(held["gc_s"], rel=0.05)


def test_a_busy_loop_reads_python_and_its_stack_names_the_loop(watch, book):
    held = _held_by(watch, book, lambda: _spin(0.2))
    assert held["cause"] == "python" and held["cpu_s"] >= held["excess_s"] / 2
    assert 0.2 <= held["excess_s"] <= held["wall_s"] < 0.5
    assert any(frame.startswith("test_host_watch.py:_spin:") for frame in held["stack"])
    assert len(held["stack"]) <= 12 and held["where"] == "the test"


def test_a_wait_for_a_thread_that_spins_reads_threads_and_names_it(watch, book):
    done, over = threading.Event(), threading.Event()

    def spins():
        _spin(0.25)
        done.set()
        over.wait(5.0)          # still there when the unit ends: its clock can be read

    spinner = threading.Thread(target=spins, name="spinner", daemon=True)
    try:
        held = _held_by(watch, book, lambda: (spinner.start(), done.wait(5.0)))
    finally:
        over.set()
    assert held["cause"] == "threads" and held["others_cpu_s"] >= held["excess_s"] / 2
    assert held["cpu_s"] < 0.05
    assert held["busiest"][0][0] == "spinner" and held["busiest"][0][1] > 0.05
    assert any(":wait:" in frame for frame in held["stack"])


def test_a_sleep_reads_machine_and_its_stack_names_the_sleeping_frame(watch, book):
    def sleeps_here():
        time.sleep(0.2)

    held = _held_by(watch, book, sleeps_here)
    assert held["cause"] == "machine"
    assert max(held["cpu_s"], held["others_cpu_s"], held["gc_s"]) < held["excess_s"] / 2
    assert held["stack"][-1].startswith("test_host_watch.py:sleeps_here:")
    # the machine's own counters, where it keeps any: deltas from the moment it was seen
    assert all(v >= 0 for v in held["machine"].values())
    assert set(held) == {
        "at", "unit", "wall_s", "excess_s", "cpu_s", "others_cpu_s", "gc_s", "switched",
        "faults", "where", "cause", "stack", "busiest", "machine"}


# -- what is not held ------------------------------------------------------------


def test_a_thousand_unheld_units_record_none_and_sample_nothing(watch, book):
    for _ in range(1000):
        with watch.unit("work", book) as unit:
            sum(range(50))
    totals = book.host["work"]
    # (the kernel's per-thread CPU clock is good to a tick's adjustment: microseconds a unit)
    assert totals["n"] == 1000 and totals["cpu_s"] > 0 and 0 < totals["wall_s"] < 1.0
    assert HostBook().held == {
        "n": 0, "excess_s": 0.0, **{c: {"n": 0, "s": 0.0} for c in accelerator.CAUSES}}
    # (a loaded machine takes a CPU away for 20 ms now and then: the watch's to say so)
    assert book.held["n"] == book.held["machine"]["n"] == len(book.steps) <= 2


def test_a_steps_two_units_cost_less_than_their_budget(watch):
    """What the serving engine asks of the watch a step, as it asks it: a reading
    near the last ends the time between two steps and begins the step, a whole
    reading ends the step and begins the time to the next, both units its own
    again. By this thread's own clock, the least of five thousands."""
    from ray_tpu.serve import llm

    book = HostBook(*llm.HOST_UNITS)

    def a_thousand_steps():
        step = between = None
        ended = watch.read()
        t0 = time.thread_time_ns()
        for _ in range(1000):
            began = watch.read(ended)
            if between is not None:
                watch.close(between, began, "between")
            step = watch.open("llm.step", book, began, again=step)
            ended = watch.read()
            watch.close(step, ended, "step")
            between = watch.open("llm.between", book, ended, again=between)
        spent = time.thread_time_ns() - t0
        watch.drop(between)
        return spent / 1000

    cost_ns = min(a_thousand_steps() for _ in range(5))
    assert cost_ns < llm.HOST_BUDGET_NS
    assert book.host["llm.step"]["n"] == 5000 and book.host["llm.between"]["n"] == 5000 - 5
    # (a reading near the last reads the wall alone: the time between counts no CPU,
    # but where a loaded machine kept the thread away half a millisecond)
    assert book.host["llm.step"]["cpu_s"] > 0 and book.host["llm.between"]["cpu_s"] < 0.01
    assert book.held["n"] == book.held["machine"]["n"] <= 2


def test_a_wait_its_caller_explains_is_neither_held_nor_sampled(watch, book):
    with watch.unit("work", book) as unit:
        unit.explained_s += 0.3             # said before the wait, as a landing says it
        time.sleep(0.2)
    assert unit.record is None and unit.seen is None and not book.steps
    assert book.host["work"]["wall_s"] >= 0.2


def test_a_dropped_unit_counts_nowhere(watch, book):
    unit = watch.open("work", book)
    time.sleep(0.12)                        # the watcher sees it overdue meanwhile
    watch.drop(unit)
    assert book.host["work"]["n"] == 0 and not book.steps and book.held["n"] == 0
    with pytest.raises(ZeroDivisionError):
        with watch.unit("work", book):
            1 / 0
    assert book.host["work"]["n"] == 0


def test_held_totals_are_the_rings_sum(watch, book):
    for _ in range(8):                      # the unit's usual size: next to nothing
        with watch.unit("work", book):
            pass
    for seconds in (0.06, 0.08):
        _held_by(watch, book, lambda: time.sleep(seconds))
    _held_by(watch, book, lambda: _spin(0.07))
    held, ring = book.held, list(book.steps)
    assert held["n"] == len(ring) >= 3
    assert held["excess_s"] == pytest.approx(sum(r["excess_s"] for r in ring))
    for cause in accelerator.CAUSES:
        mine = [r["excess_s"] for r in ring if r["cause"] == cause]
        assert held[cause] == {"n": len(mine), "s": pytest.approx(sum(mine))}
    assert held["machine"]["n"] >= 2     # (the busy loop too, where a loaded machine took its CPU)
    # and a book keeps its newest 32
    for _ in range(40):
        book.steps.append({})
    assert len(book.steps) == 32


def test_a_unit_that_explains_itself_is_held_over_its_usual_size(watch):
    """A step of a user's loop: nobody can say what explains it, so its own
    median does, and the first has nothing to stand against."""
    book = HostBook()
    for seconds in (0.12, 0.01, 0.01, 0.01, 0.01, 0.13, 0.01):
        unit = watch.open("loop", book, usual=True)
        time.sleep(seconds)
        watch.close(unit, where="the loop")
    assert book.host["loop"]["n"] == 7 and 1 <= len(book.steps) <= 3
    held = max(book.steps, key=lambda r: r["excess_s"])
    assert 0.1 <= held["excess_s"] < held["wall_s"] < 0.2 and held["cause"] == "machine"


def test_the_hook_times_every_collection_by_generation(watch):
    before = watch.gc_totals()
    gc.collect(0)
    gc.collect(1)
    after = watch.gc_totals()
    for generation in "01":
        assert after["generations"][generation]["n"] >= before["generations"][generation]["n"] + 1
    assert after["n"] >= before["n"] + 2 and after["s"] > before["s"]
    assert sum(g["n"] for g in after["generations"].values()) == after["n"]


def test_the_watcher_is_one_thread_and_a_stack_is_rpc_profiles_form(watch):
    watch.open("work")                      # never closed: the watcher just looks
    assert [t.name for t in threading.enumerate()].count("host-watch") == 1
    assert accelerator.host_watch() is watch

    def inner():
        import sys

        return accelerator.fold_stack(sys._getframe()), accelerator.fold_stack(sys._getframe(), 2)

    whole, two = inner()
    assert whole[-2:] == two and len(two) == 2
    assert two[1].startswith("test_host_watch.py:inner:")
    assert two[0].startswith("test_host_watch.py:test_the_watcher_is_one_thread")
    assert all(len(frame.split(":")) == 3 for frame in whole)


# -- the train session: from one report to the next -----------------------------


def test_a_loop_that_stands_still_between_two_reports_is_one_held_record(ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop_that_sleeps(config):
        import time

        from ray_tpu import train

        for step in range(8):
            if step == 5:
                time.sleep(0.3)
            train.report({"step": step})

    result = JaxTrainer(
        loop_that_sleeps, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="held", storage_path=str(tmp_path)),
    ).fit()
    assert result.error is None and len(result.metrics_history) == 8
    host = result.host
    assert set(host) == {"host", "gc", "held", "held_steps"}
    assert host["host"]["train.report"]["n"] == 7           # from one report to the next
    assert 1 <= host["held"]["n"] == len(host["held_steps"]) <= 3
    held = max(host["held_steps"], key=lambda r: r["excess_s"])
    assert held["unit"] == "train.report" and held["cause"] == "machine"
    assert 0.25 <= held["excess_s"] <= held["wall_s"] < 0.6
    assert any(":loop_that_sleeps:" in frame for frame in held["stack"])
