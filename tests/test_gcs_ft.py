"""GCS fault tolerance: persistence + restart replay + raylet reconnect.

(reference: gcs_table_storage.cc / store_client_kv.cc persistence,
NotifyGCSRestart reconnect at node_manager.proto:358)
"""

import os
import time

import pytest

from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.ids import ActorID, JobID
from ray_tpu._private.rpc import RpcClient


def test_kv_jobs_survive_restart(tmp_path):
    db = str(tmp_path / "gcs.db")
    gcs = GcsServer(persistence_path=db)
    addr = gcs.address
    client = RpcClient(addr)
    client.call("kv_put", ("ns", "k1", b"v1", True))
    client.call("kv_put", ("ns", "k2", b"v2", True))
    client.call("kv_del", ("ns", "k2"))
    client.call("add_job", {"job_id": JobID.from_int(7), "driver_pid": 123})
    client.close()
    gcs.stop()

    gcs2 = GcsServer(persistence_path=db)
    client = RpcClient(gcs2.address)
    assert client.call("kv_get", ("ns", "k1")) == b"v1"
    assert client.call("kv_get", ("ns", "k2")) is None
    jobs = client.call("get_jobs")
    assert len(jobs) == 1 and jobs[0]["driver_pid"] == 123
    client.close()
    gcs2.stop()


def test_actor_table_survives_restart(tmp_path):
    db = str(tmp_path / "gcs.db")
    gcs = GcsServer(persistence_path=db)
    client = RpcClient(gcs.address)
    aid = ActorID.from_random()
    spec = {
        "class_name": "Foo",
        "serialized_class": b"",
        "args": b"",
        "options": {"name": "my_actor", "max_restarts": 2, "resources": {"CPU": 1}},
    }
    client.call("register_actor", (aid, spec))
    client.close()
    gcs.stop()

    gcs2 = GcsServer(persistence_path=db)
    client = RpcClient(gcs2.address)
    actors = client.call("list_actors")
    assert len(actors) == 1
    assert actors[0]["actor_id"] == aid
    assert actors[0]["name"] == "my_actor"
    client.close()
    gcs2.stop()


def test_cluster_survives_gcs_restart(tmp_path):
    """Kill the GCS under a live raylet: the raylet re-registers against
    the restarted (persistence-reloaded) GCS and a fresh driver runs tasks
    and resolves the pre-restart named actor."""
    import ray_tpu
    from ray_tpu._private.node import Node

    db = str(tmp_path / "gcs.db")
    gcs = GcsServer(persistence_path=db)
    host, port = gcs.address
    node = Node(
        head=False, gcs_address=(host, port), num_cpus=2, detect_tpu=False,
        node_name="survivor",
    )
    try:
        ray_tpu.init(address=f"{host}:{port}", log_level="WARNING")

        @ray_tpu.remote
        class Keeper:
            def __init__(self):
                self.v = 41

            def bump(self):
                self.v += 1
                return self.v

        keeper = Keeper.options(name="keeper").remote()
        assert ray_tpu.get(keeper.bump.remote(), timeout=60) == 42
        ray_tpu.shutdown()

        # GCS dies and comes back at the same address
        gcs.stop()
        time.sleep(0.5)
        gcs2 = GcsServer(host=host, port=port, persistence_path=db)
        try:
            # raylet heartbeat reconnect re-registers the node
            client = RpcClient(gcs2.address)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                nodes = client.call("get_nodes")
                if any(n["alive"] for n in nodes):
                    break
                time.sleep(0.2)
            else:
                pytest.fail(f"raylet never re-registered: {nodes}")
            client.close()

            # a fresh driver joins and reaches both new tasks and the
            # pre-restart actor (address replayed from the actor table)
            ray_tpu.init(address=f"{host}:{port}", log_level="WARNING")

            @ray_tpu.remote
            def f(x):
                return x + 1

            assert ray_tpu.get(f.remote(1), timeout=60) == 2
            survivor = ray_tpu.get_actor("keeper")
            assert ray_tpu.get(survivor.bump.remote(), timeout=60) == 43
            ray_tpu.shutdown()
        finally:
            gcs2.stop()
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        node.stop()


def test_stalled_checker_is_not_a_silent_node():
    """Time in which the GCS's own process did not run is no silence from
    the nodes (their heartbeats are unread, and an in-process raylet could
    send none) — but a node that stays silent afterwards still dies."""
    import threading

    from ray_tpu._private.config import GlobalConfig
    from ray_tpu._private.ids import NodeID

    saved = dict(GlobalConfig._values)
    GlobalConfig.initialize(
        {"health_check_period_s": 0.1, "health_check_failure_threshold": 5}
    )
    gcs = GcsServer()
    client = RpcClient(gcs.address)
    try:
        node_id = NodeID.from_random()
        client.call("register_node", (node_id, ("127.0.0.1", 1), {"CPU": 1.0}, {}))

        def alive():
            (view,) = client.call("get_nodes")
            return view["alive"]

        # freeze the checker once for twice the failure window (0.5 s), as a
        # frozen process would; no heartbeat arrives meanwhile either
        real_wait, ticks, freeze = gcs._stopped.wait, [], threading.Event()

        def wait(timeout):
            if freeze.is_set() and not ticks:
                time.sleep(1.0)
            if freeze.is_set():
                ticks.append(time.monotonic())
            return real_wait(timeout)

        gcs._stopped.wait = wait
        assert client.call("heartbeat", (node_id, {"CPU": 1.0}))
        freeze.set()
        deadline = time.monotonic() + 10
        while len(ticks) < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        # two checks have run since the freeze, with no heartbeat in between
        assert len(ticks) >= 3 and alive()
        deadline = time.monotonic() + 10
        while alive() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not alive(), "a node that stays silent must still be declared dead"
    finally:
        client.close()
        gcs.stop()
        with GlobalConfig._lock:
            GlobalConfig._values = saved
