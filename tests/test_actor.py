"""Actor tests (modeled on reference python/ray/tests/test_actor.py)."""

import time

import pytest

import ray_tpu


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, k=1):
        self.n += k
        return self.n

    def value(self):
        return self.n


def test_actor_basic(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    assert ray_tpu.get(c.incr.remote(10)) == 11


def test_actor_constructor_args(ray_start_regular):
    c = Counter.remote(start=100)
    assert ray_tpu.get(c.value.remote()) == 100


def test_actor_call_ordering(ray_start_regular):
    c = Counter.remote()
    refs = [c.incr.remote() for _ in range(20)]
    assert ray_tpu.get(refs) == list(range(1, 21))


def test_actor_method_exception(ray_start_regular):
    @ray_tpu.remote
    class Bad:
        def fail(self):
            raise RuntimeError("actor boom")

        def ok(self):
            return "fine"

    b = Bad.remote()
    with pytest.raises(ray_tpu.TaskError):
        ray_tpu.get(b.fail.remote())
    # actor stays alive after a method exception
    assert ray_tpu.get(b.ok.remote()) == "fine"


def test_two_actors_independent(ray_start_regular):
    a, b = Counter.remote(), Counter.remote()
    ray_tpu.get(a.incr.remote(5))
    assert ray_tpu.get(b.value.remote()) == 0


def test_pass_handle_to_task(ray_start_regular):
    @ray_tpu.remote
    def bump(counter):
        return ray_tpu.get(counter.incr.remote())

    c = Counter.remote()
    assert ray_tpu.get(bump.remote(c), timeout=60) == 1
    assert ray_tpu.get(c.value.remote()) == 1


def test_named_actor(ray_start_regular):
    Counter.options(name="counter_x").remote()
    h = ray_tpu.get_actor("counter_x")
    assert ray_tpu.get(h.incr.remote()) == 1


def test_actor_kill(ray_start_regular):
    c = Counter.remote()
    ray_tpu.get(c.incr.remote())
    ray_tpu.kill(c)
    time.sleep(0.5)
    with pytest.raises((ray_tpu.ActorDiedError, ray_tpu.GetTimeoutError)):
        ray_tpu.get(c.incr.remote(), timeout=10)


def test_actor_restart(ray_start_regular):
    @ray_tpu.remote(max_restarts=1)
    class Crasher:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def crash(self):
            import os

            os._exit(1)

    c = Crasher.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    try:
        ray_tpu.get(c.crash.remote(), timeout=30)
    except ray_tpu.RayTpuError:
        pass
    # restarted actor has fresh state
    deadline = time.time() + 60
    while True:
        try:
            assert ray_tpu.get(c.incr.remote(), timeout=30) == 1
            break
        except ray_tpu.RayTpuError:
            if time.time() > deadline:
                raise
            time.sleep(0.5)


def test_max_concurrency_parallel(ray_start_regular):
    @ray_tpu.remote(max_concurrency=4)
    class Sleeper:
        def nap(self, t):
            time.sleep(t)
            return t

    s = Sleeper.remote()
    t0 = time.time()
    ray_tpu.get([s.nap.remote(0.5) for _ in range(4)], timeout=30)
    elapsed = time.time() - t0
    assert elapsed < 1.6, f"calls did not overlap: {elapsed:.2f}s"


def test_a_call_outlasts_the_async_rpc_deadline(ray_start_regular, monkeypatch):
    """A pushed task's reply is its result, and a task runs as long as it runs: a
    train worker's ``run`` lasts the whole job. The deadline that frees the
    callback slot of a peer that hangs (``rpc_async_call_timeout_s``, 120 s) used
    to cut every task and actor call off: ``ActorDiedError ... timed out
    (reaped)`` for a job of two minutes. Shortened to 1 s here, it fails neither
    an actor call nor a plain task of 3 s (the reaper ticks once a second)."""
    from ray_tpu._private.config import GlobalConfig

    monkeypatch.setitem(GlobalConfig._values, "rpc_async_call_timeout_s", 1.0)

    @ray_tpu.remote
    class Slow:
        def nap(self, s):
            time.sleep(s)
            return s

    @ray_tpu.remote
    def nap(s):
        time.sleep(s)
        return s

    slow = Slow.remote()
    assert ray_tpu.get(slow.nap.remote(0.01), timeout=30) == 0.01     # the actor is up
    assert ray_tpu.get([slow.nap.remote(3.0), nap.remote(3.0)], timeout=60) == [3.0, 3.0]
