"""Worker fork-server: clone workers from a pre-imported template process.

Interpreter boot plus the framework imports cost seconds of CPU, which caps
cold worker/actor creation at <1/s per core. The reference's answer is a prestarted worker
pool (reference: src/ray/raylet/worker_pool.h:167-191 prestarted workers,
maximum_startup_concurrency); this is the same idea taken one step further,
CPython-forkserver style: one template process pays the import cost once,
then every worker is an ``os.fork()`` (~10 ms, copy-on-write) instead of an
interpreter+import boot.

Protocol (template side of the unix socket, single-threaded):
  request  = one pickled dict  {"env": {...}, "sys_path": [...],
                                "cwd": str|None, "log_path": str}
  response = one pickled dict  {"pid": int}
Frames are 4-byte length-prefixed. The template NEVER starts threads,
creates RPC objects, or runs jax computations — fork safety depends on it
staying single-threaded with no locks held by background threads.

The forked child closes the listener, redirects stdout/stderr to its log
file, applies env/sys.path/cwd, re-seeds randomness, and enters
``default_worker.main()`` exactly as a Popen'd worker would.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import sys

_LEN = struct.Struct(">I")


def _read_msg(conn: socket.socket):
    hdr = b""
    while len(hdr) < _LEN.size:
        chunk = conn.recv(_LEN.size - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (length,) = _LEN.unpack(hdr)
    body = b""
    while len(body) < length:
        chunk = conn.recv(length - len(body))
        if not chunk:
            return None
        body += chunk
    return pickle.loads(body)


def _send_msg(conn: socket.socket, obj) -> None:
    body = pickle.dumps(obj, protocol=5)
    conn.sendall(_LEN.pack(len(body)) + body)


def _child_main(req: dict) -> None:
    """Runs in the forked child: become a normal worker process."""
    if os.environ.get("RAYTPU_BOOT_TIMING") == "1":
        import time as _t

        sys.stderr.write(f"[boot-timing] child-start wall={_t.time():.3f}\n")
        sys.stderr.flush()
    os.setsid()  # own process group: raylet signals don't hit the template
    log_fd = os.open(
        req["log_path"], os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644
    )
    os.dup2(log_fd, 1)
    os.dup2(log_fd, 2)
    os.close(log_fd)
    # PYTHONUNBUFFERED only acts at interpreter start, which this child
    # skipped: re-arm line buffering so task prints reach the log monitor
    # promptly (the raylet tails this file to the driver's stdout)
    try:
        sys.stdout.reconfigure(line_buffering=True)
        sys.stderr.reconfigure(line_buffering=True)
    except (AttributeError, OSError):
        pass
    os.environ.update(req["env"])
    if os.environ.get("RAYTPU_BOOT_TIMING") == "1":
        import time as _t

        print(f"[boot-timing] child_main wall={_t.time():.3f}", flush=True)
    if req.get("cwd"):
        os.chdir(req["cwd"])
    sys_path = list(req.get("sys_path") or ())
    for p in reversed(sys_path):
        sys.path.insert(0, p)
    if sys_path:
        # keep parity with the Popen spawn path: a task that launches its
        # own python subprocess must see working_dir/py_modules roots too
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [*sys_path, os.environ.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
    _timing = os.environ.get("RAYTPU_BOOT_TIMING") == "1"

    def _mark(stage):
        if _timing:
            import time as _t

            print(f"[boot-timing] {stage} wall={_t.time():.3f}", flush=True)

    # fork shares the parent's PRNG state: re-seed everything that would
    # otherwise collide across siblings (ids are passed in, but user code
    # uses random/uuid too)
    import random

    random.seed()
    _mark("random_seed")
    try:
        import numpy as _np

        # explicit int seed: argless seed() walks SeedSequence's entropy
        # machinery, which cost ~220 ms in a fresh fork (measured); urandom
        # gives the same sibling-divergence guarantee for free
        _np.random.seed(int.from_bytes(os.urandom(4), "little"))
    except Exception:
        pass
    _mark("np_seed")

    # the template's GlobalConfig snapshotted env at import time; pick up
    # this worker's RAYTPU_* overrides (incl. runtime_env env_vars) so the
    # fork path honors the same knobs the Popen path does
    from ray_tpu._private.config import GlobalConfig

    GlobalConfig.refresh_from_env()

    from ray_tpu._private import default_worker

    _mark("dw_import")
    if os.environ.get("RAYTPU_BOOT_PROFILE") == "1":
        import cProfile
        import pstats

        prof = cProfile.Profile()
        prof.enable()
        import threading as _th

        def _dump():
            prof.disable()
            import io as _io

            s = _io.StringIO()
            pstats.Stats(prof, stream=s).sort_stats("cumulative").print_stats(30)
            print(s.getvalue(), flush=True)

        _th.Timer(2.0, _dump).start()
    default_worker.main()


def main() -> None:
    sock_path = os.environ["RAYTPU_FORKSERVER_SOCK"]
    # pre-import the worker's dependency closure (the whole point), so
    # forked children import nothing heavy
    import ray_tpu  # noqa: F401
    from ray_tpu._private import (  # noqa: F401
        core_worker,
        default_worker,
        serialization,
        task_executor,
    )
    import numpy.random  # noqa: F401  (lazy submodule: ~250ms if paid per fork)

    numpy.random.default_rng()  # touch the generator machinery too
    # stdlib modules the worker's first task would otherwise import lazily
    # (asyncio alone is ~30 submodules / ~100ms per fork)
    import asyncio  # noqa: F401
    import concurrent.futures  # noqa: F401
    import inspect  # noqa: F401
    import ray_tpu._private.worker  # noqa: F401
    import ray_tpu.cluster_utils  # noqa: F401

    # Freeze the post-import heap into gc's permanent generation: the first
    # collection in a forked child would otherwise touch every inherited
    # object header (refcounts/gc flags), copy-on-writing the whole template
    # heap (~230 ms per fork measured here). This is the documented
    # fork-server pattern gc.freeze() exists for.
    import gc

    gc.collect()
    gc.freeze()

    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    listener.bind(sock_path)
    os.chmod(sock_path, 0o600)
    listener.listen(8)
    listener.settimeout(0.5)
    conns: list[socket.socket] = []
    import selectors

    sel = selectors.DefaultSelector()
    sel.register(listener, selectors.EVENT_READ, "accept")
    ppid = os.getppid()
    while True:
        # reap any exited children so they don't accumulate as zombies
        try:
            while True:
                pid, _status = os.waitpid(-1, os.WNOHANG)
                if pid == 0:
                    break
        except ChildProcessError:
            pass
        if os.getppid() != ppid:
            break  # raylet (our parent) died: exit with it
        for key, _ in sel.select(timeout=0.5):
            if key.data == "accept":
                try:
                    conn, _ = listener.accept()
                except OSError:
                    continue
                conns.append(conn)
                sel.register(conn, selectors.EVENT_READ, "conn")
                continue
            conn = key.fileobj
            try:
                req = _read_msg(conn)
            except OSError:
                req = None
            if req is None:
                sel.unregister(conn)
                conns.remove(conn)
                conn.close()
                continue
            if req.get("op") == "shutdown":
                for c in conns:
                    c.close()
                listener.close()
                return
            if os.environ.get("RAYTPU_BOOT_TIMING") == "1":
                import time as _t

                sys.stderr.write(f"[boot-timing] pre-fork wall={_t.time():.3f}\n")
                sys.stderr.flush()
            pid = os.fork()
            if pid == 0:
                # child: drop every inherited server/conn fd, then become
                # the worker (never returns)
                sel.close()
                listener.close()
                for c in conns:
                    c.close()
                try:
                    _child_main(req)
                finally:
                    os._exit(0)
            _send_msg(conn, {"pid": pid})


if __name__ == "__main__":
    main()
