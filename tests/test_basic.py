"""Core task/object API tests (modeled on reference python/ray/tests/test_basic.py)."""

import time

import numpy as np
import pytest

import ray_tpu


@ray_tpu.remote
def echo(x):
    return x


@ray_tpu.remote
def add(a, b):
    return a + b


def test_simple_task(ray_start_regular):
    assert ray_tpu.get(echo.remote(42)) == 42


def test_many_tasks(ray_start_regular):
    refs = [echo.remote(i) for i in range(100)]
    assert ray_tpu.get(refs) == list(range(100))


def test_task_dependencies(ray_start_regular):
    r = add.remote(echo.remote(1), echo.remote(2))
    assert ray_tpu.get(r) == 3


def test_deep_chain(ray_start_regular):
    ref = echo.remote(0)
    for _ in range(20):
        ref = add.remote(ref, 1)
    assert ray_tpu.get(ref) == 20


def _lease_actions_wait(core, monkeypatch):
    """Hold back every lease action until the returned event is set, as a
    submitter thread that the scheduler skips for a moment does: whatever
    the caller submits meanwhile is queued ahead of the first lease."""
    import threading

    gate = threading.Event()
    ensure = core._ensure_lease_requests

    def late(sig):
        gate.wait(30)
        ensure(sig)

    monkeypatch.setattr(core, "_ensure_lease_requests", late)
    return gate


def test_burst_of_dependent_tasks_cannot_starve_the_first_lease(
    ray_start_regular, monkeypatch
):
    # 20 calls that each take the one before, all queued before the first
    # task's lease action: more than the pool has submitters (this hung
    # test_deep_chain whenever the machine was busy)
    gate = _lease_actions_wait(ray_start_regular.core, monkeypatch)
    ref = echo.remote(0)
    for _ in range(20):
        ref = add.remote(ref, 1)
    gate.set()
    assert ray_tpu.get(ref, timeout=60) == 20


def test_actor_calls_waiting_for_a_task_cannot_starve_its_lease(
    ray_start_regular, monkeypatch
):
    @ray_tpu.remote(num_cpus=0)
    class Adder:
        def plus(self, a, b):
            return a + b

    actors = [Adder.remote() for _ in range(10)]
    assert ray_tpu.get([a.plus.remote(0, 0) for a in actors], timeout=60) == [0] * 10
    gate = _lease_actions_wait(ray_start_regular.core, monkeypatch)
    ref = echo.remote(5)
    outs = [a.plus.remote(ref, i) for i, a in enumerate(actors)]
    time.sleep(0.5)  # every actor's drain action is queued, and taken if it can be
    gate.set()
    assert ray_tpu.get(outs, timeout=60) == [5 + i for i in range(10)]


def test_put_get_roundtrip(ray_start_regular):
    for value in [1, "hello", {"a": [1, 2, 3]}, (None, True)]:
        assert ray_tpu.get(ray_tpu.put(value)) == value


def test_put_get_numpy_zero_copy(ray_start_regular):
    arr = np.arange(1 << 20, dtype=np.float32)
    out = ray_tpu.get(ray_tpu.put(arr))
    np.testing.assert_array_equal(arr, out)
    # large arrays come back as zero-copy views onto shared memory
    assert not out.flags.writeable or out.base is not None


def test_put_as_arg(ray_start_regular):
    ref = ray_tpu.put(np.ones(1000))
    assert ray_tpu.get(add.remote(ref, ref)).sum() == 2000


def test_nested_refs_in_structure(ray_start_regular):
    @ray_tpu.remote
    def total(lst):
        return sum(ray_tpu.get(lst))

    refs = [echo.remote(i) for i in range(5)]
    assert ray_tpu.get(total.remote(refs)) == 10


def test_nested_task_submission(ray_start_regular):
    @ray_tpu.remote
    def outer(n):
        return sum(ray_tpu.get([echo.remote(i) for i in range(n)]))

    assert ray_tpu.get(outer.remote(4), timeout=60) == 6


def test_task_exception_propagates(ray_start_regular):
    @ray_tpu.remote
    def boom():
        raise ValueError("boom")

    with pytest.raises(ray_tpu.TaskError) as info:
        ray_tpu.get(boom.remote())
    assert "boom" in str(info.value)


def test_exception_through_dependency(ray_start_regular):
    @ray_tpu.remote
    def boom():
        raise ValueError("boom")

    # the dependent task fails because its arg resolution raises
    r = add.remote(boom.remote(), 1)
    with pytest.raises(ray_tpu.RayTpuError):
        ray_tpu.get(r)


def test_num_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_options_override(ray_start_regular):
    f2 = echo.options(num_cpus=2)
    assert ray_tpu.get(f2.remote("ok")) == "ok"


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def slow(t):
        time.sleep(t)
        return t

    fast = [echo.remote(i) for i in range(3)]
    slow_ref = slow.remote(5)
    ready, not_ready = ray_tpu.wait(fast + [slow_ref], num_returns=3, timeout=10)
    assert len(ready) == 3
    assert slow_ref in not_ready


def test_wait_timeout(ray_start_regular):
    @ray_tpu.remote
    def never():
        time.sleep(60)

    ready, not_ready = ray_tpu.wait([never.remote()], num_returns=1, timeout=0.2)
    assert ready == []
    assert len(not_ready) == 1


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(30)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_kwargs(ray_start_regular):
    @ray_tpu.remote
    def kw(a, b=10, c=100):
        return a + b + c

    assert ray_tpu.get(kw.remote(1, c=2)) == 13


def test_large_arg_roundtrip(ray_start_regular):
    arr = np.random.rand(1 << 18)

    @ray_tpu.remote
    def norm(x):
        return float(np.sum(x))

    assert abs(ray_tpu.get(norm.remote(arr)) - arr.sum()) < 1e-6


def test_task_retry_on_worker_death(ray_start_regular):
    import os as _os

    @ray_tpu.remote(max_retries=2)
    def flaky(marker_dir):
        import os, sys
        marker = os.path.join(marker_dir, "attempt")
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)  # crash the worker on first attempt
        return "recovered"

    import tempfile

    d = tempfile.mkdtemp()
    assert ray_tpu.get(flaky.remote(d), timeout=60) == "recovered"


@ray_tpu.remote(num_returns="dynamic")
def _squares(n):
    for i in range(n):
        yield i * i


def test_dynamic_num_returns(ray_start_regular):
    """num_returns="dynamic": the task is a generator; its single static
    return resolves to an ObjectRefGenerator over one ref per yield
    (reference: _private/ray_option_utils.py:157-159)."""
    ref = _squares.remote(5)
    gen = ray_tpu.get(ref, timeout=30)
    assert isinstance(gen, ray_tpu.ObjectRefGenerator)
    assert len(gen) == 5
    assert [ray_tpu.get(r, timeout=30) for r in gen] == [0, 1, 4, 9, 16]


def test_dynamic_num_returns_large_items(ray_start_regular):
    @ray_tpu.remote(num_returns="dynamic")
    def chunks(n):
        for i in range(n):
            yield np.full((50_000,), i, np.float32)

    gen = ray_tpu.get(chunks.remote(3), timeout=30)
    for i, r in enumerate(gen):
        arr = ray_tpu.get(r, timeout=30)
        assert arr.shape == (50_000,)
        assert arr[0] == i


def test_dynamic_num_returns_generator_error(ray_start_regular):
    @ray_tpu.remote(num_returns="dynamic")
    def bad():
        yield 1
        raise ValueError("boom in generator")

    with pytest.raises(Exception, match="boom in generator"):
        ray_tpu.get(bad.remote(), timeout=30)
