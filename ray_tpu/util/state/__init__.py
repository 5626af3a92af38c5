"""State API: cluster-wide listings and summaries.

The `ray list tasks/actors/objects/...` equivalent (reference:
python/ray/util/state/, dashboard/state_aggregator.py:141 StateAPIManager,
list_tasks:379). The head GCS already holds nodes/actors/jobs/PGs/task
events; object listings aggregate from every raylet's store
(node_manager.proto:413-415 GetTasksInfo/GetObjectsInfo analogue).

Every call accepts an explicit ``address="host:port"`` (CLI / external
tools) or defaults to the connected driver's GCS.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import Counter, defaultdict
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = [
    "profile_actor",
    "folded_to_text",
    "drain_node",
    "dump_stacks",
    "format_stack_report",
    "get_log",
    "list_actors",
    "list_alerts",
    "list_cluster_events",
    "list_jobs",
    "list_logs",
    "list_nodes",
    "list_objects",
    "list_placement_groups",
    "list_slo_rules",
    "list_tasks",
    "read_log_chunk",
    "list_trace_spans",
    "summarize_rpcs",
    "summarize_tasks",
    "timeline",
]

logger = logging.getLogger(__name__)


_client_cache: Dict[str, Any] = {}
_client_locks: Dict[str, threading.Lock] = {}
_client_lock = threading.Lock()


def _cached_client(address: str):
    """One persistent RpcClient per address: the dashboard polls these
    endpoints every 2s and must not churn TCP connects on the head.

    The connect happens under a per-address lock — RpcClient's constructor
    blocks retrying TCP for up to the connect timeout, and one dead node
    must not stall state queries against every other node."""
    from ray_tpu._private.rpc import RpcClient

    with _client_lock:
        client = _client_cache.get(address)
        if client is not None and not client.closed:
            return client
        addr_lock = _client_locks.setdefault(address, threading.Lock())
    with addr_lock:
        with _client_lock:
            client = _client_cache.get(address)
            if client is not None and not client.closed:
                return client
        host, port = address.rsplit(":", 1)
        client = RpcClient((host, int(port)))
        with _client_lock:
            _client_cache[address] = client
        return client


def _gcs_call(method: str, payload=None, *, address: Optional[str] = None):
    if address is not None:
        return _cached_client(address).call(method, payload, timeout=30.0)
    import ray_tpu._private.worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        raise RuntimeError(
            "not connected — call ray_tpu.init() or pass address='host:port'"
        )
    return w.core.gcs.call(method, payload, timeout=30.0)


def list_nodes(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    return _gcs_call("get_nodes", address=address)


def drain_node(
    node_id: str,
    deadline_s: float = 30.0,
    *,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """Initiate a graceful drain (ALIVE -> DRAINING -> DEAD) of one node,
    identified by node id hex prefix or node_name label. Returns the GCS
    status dict ({"status": "draining"|"dead"|"not_found", ...})."""
    return _gcs_call(
        "drain_node",
        {"node_id": node_id, "deadline_s": deadline_s},
        address=address,
    )


def profile_actor(
    actor_id,
    *,
    duration_s: float = 2.0,
    interval_s: float = 0.01,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """Sample a live actor's worker process and return folded stacks (the
    flamegraph text format) — the reference's on-demand py-spy profile
    (dashboard/modules/reporter/profile_manager.py:10-25), implemented as
    in-process stack sampling over the worker's RPC server.

    ``actor_id`` may be an ActorID, its hex string, or an ActorHandle."""
    from ray_tpu._private.ids import ActorID
    from ray_tpu._private.rpc import RpcClient

    if hasattr(actor_id, "_actor_id"):
        actor_id = actor_id._actor_id
    if isinstance(actor_id, str):
        actor_id = ActorID.from_hex(actor_id)
    actors = list_actors(address=address)
    row = next(
        (a for a in actors if a["actor_id"] == actor_id and a["state"] == "ALIVE"),
        None,
    )
    if row is None:
        raise ValueError(f"no ALIVE actor {actor_id.hex()[:16]}")
    client = RpcClient(tuple(row["address"]))
    try:
        return client.call(
            "profile",
            {"duration_s": duration_s, "interval_s": interval_s},
            timeout=duration_s + 30.0,
        )
    finally:
        client.close()


def folded_to_text(profile: Dict[str, Any]) -> str:
    """Render a profile result as flamegraph.pl-compatible folded lines."""
    return "\n".join(
        f"{stack} {count}"
        for stack, count in sorted(
            profile["folded"].items(), key=lambda kv: -kv[1]
        )
    )


def list_actors(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    return _gcs_call("list_actors", address=address)


def list_jobs(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    return _gcs_call("get_jobs", address=address)


def list_placement_groups(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    table = _gcs_call("placement_group_table", address=address)
    return list(table.values()) if isinstance(table, dict) else table


def _latest_task_rows(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Collapse raw task events into one row per task. Events arrive from
    different processes (RUNNING from the executor, FINISHED from the owner)
    so GCS arrival order is not lifecycle order: the furthest lifecycle
    stage wins, timestamp breaks ties."""
    rank = {
        "PENDING_ARGS_AVAIL": 0,
        "RUNNING": 1,
        "FAILED": 2,
        "CANCELLED": 2,
        "FINISHED": 2,
    }
    latest: Dict[str, Dict[str, Any]] = {}
    first_ts: Dict[str, float] = {}
    for ev in events:
        tid = ev["task_id"]
        first_ts.setdefault(tid, ev["ts"])
        cur = latest.get(tid)
        if cur is None or (
            rank.get(ev["state"], 1),
            ev["ts"],
        ) >= (rank.get(cur["state"], 1), cur["ts"]):
            latest[tid] = ev
    return [
        {
            "task_id": tid,
            "name": ev["name"],
            "state": ev["state"],
            "start_ts": first_ts[tid],
            "worker_id": ev.get("worker_id"),
            "last_ts": ev["ts"],
        }
        for tid, ev in latest.items()
    ]


def list_tasks(
    *,
    address: Optional[str] = None,
    detail: bool = False,
) -> List[Dict[str, Any]]:
    """One row per task, collapsed from the GCS task-event stream."""
    events = _gcs_call("get_task_events", address=address)
    rows = _latest_task_rows(events)
    if not detail:
        for row in rows:
            row.pop("last_ts", None)
    return rows


class StateListResult(list):
    """A plain list of rows plus an ``errors`` attribute: one entry per node
    whose raylet could not be reached, so callers can tell a partial listing
    from a genuinely empty one."""

    def __init__(self, *args):
        super().__init__(*args)
        self.errors: List[Dict[str, str]] = []


#: nodes already warned about once (avoid a log line per 2s dashboard poll)
_node_error_warned: set = set()


def _record_node_error(errors: List[Dict[str, str]], api: str,
                       node_hex: str, exc: Exception) -> None:
    errors.append({"node_id": node_hex, "error": repr(exc)})
    from ray_tpu._private import internal_metrics

    internal_metrics.inc("ray_tpu_state_api_node_errors", tags={"api": api})
    if node_hex not in _node_error_warned:
        _node_error_warned.add(node_hex)
        logger.warning(
            "%s: raylet on node %s unreachable (%r); results are partial",
            api, node_hex[:12], exc,
        )


def list_objects(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    """Aggregate every raylet's plasma inventory. Returns a list with an
    ``errors`` attribute naming nodes that failed mid-listing."""
    rows = StateListResult()
    for node in list_nodes(address=address):
        if not node.get("alive"):
            continue
        raylet_addr = "{}:{}".format(*node["address"])
        try:
            for obj in _cached_client(raylet_addr).call("store_list", timeout=10.0):
                obj["node_id"] = node["node_id"].hex()
                rows.append(obj)
        except Exception as e:  # noqa: BLE001 - node died mid-listing
            _record_node_error(rows.errors, "list_objects", node["node_id"].hex(), e)
    return rows


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _duration_stats(durs: List[float]) -> Dict[str, float]:
    return {
        "count": len(durs),
        "mean_s": sum(durs) / len(durs),
        "p50_s": _percentile(durs, 0.50),
        "p95_s": _percentile(durs, 0.95),
    }


def summarize_tasks(*, address: Optional[str] = None) -> Dict[str, Any]:
    """Counts by (name, state) — the `ray summary tasks` equivalent — plus
    per-name execution duration stats (count / mean / p50 / p95 seconds).
    RUNNING→FINISHED pairs land in ``duration``; RUNNING→FAILED/CANCELLED
    pairs get their own ``failed_duration`` column — folding them into one
    distribution would poison the success percentiles, dropping them (the
    old behavior) under-reported churn entirely."""
    events = _gcs_call("get_task_events", address=address)
    by_name: Dict[str, Counter] = defaultdict(Counter)
    for row in _latest_task_rows(events):
        by_name[row["name"]][row["state"]] += 1
    starts: Dict[str, Dict[str, Any]] = {}
    durations: Dict[str, List[float]] = defaultdict(list)
    failed_durations: Dict[str, List[float]] = defaultdict(list)
    for ev in sorted(events, key=lambda e: e["ts"]):
        if ev["state"] == "RUNNING":
            starts[ev["task_id"]] = ev
        elif (
            ev["state"] in ("FINISHED", "FAILED", "CANCELLED")
            and ev["task_id"] in starts
        ):
            start = starts.pop(ev["task_id"])
            dur = max(0.0, ev["ts"] - start["ts"])
            if ev["state"] == "FINISHED":
                durations[start["name"]].append(dur)
            else:
                failed_durations[start["name"]].append(dur)
    out: Dict[str, Any] = {}
    for name, states in sorted(by_name.items()):
        entry: Dict[str, Any] = dict(states)
        durs = sorted(durations.get(name, ()))
        if durs:
            entry["duration"] = _duration_stats(durs)
        failed = sorted(failed_durations.get(name, ()))
        if failed:
            entry["failed_duration"] = _duration_stats(failed)
        out[name] = entry
    return out


def _bucket_quantile(
    boundaries: List[float], buckets: List[int], q: float
) -> float:
    """Quantile estimate from histogram bins: linear interpolation inside
    the bin where the rank lands (Prometheus histogram_quantile style);
    the overflow bin clamps to the top boundary."""
    total = sum(buckets)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0
    for i, c in enumerate(buckets):
        cum += c
        if cum >= rank and c:
            if i >= len(boundaries):
                return float(boundaries[-1])
            lo = float(boundaries[i - 1]) if i > 0 else 0.0
            hi = float(boundaries[i])
            frac = (rank - (cum - c)) / c
            return lo + (hi - lo) * frac
    return float(boundaries[-1])


def summarize_rpcs(
    *,
    address: Optional[str] = None,
    method: Optional[str] = None,
) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Cluster-wide RPC phase latency summary, merged across every
    reporting process from the ``ray_tpu_rpc_phase_seconds`` histogram
    family: ``{method: {"client.serialize": {count, mean_s, p50_s,
    p95_s, p99_s}, ..., "server.handler": {...}}}``.

    Percentiles are bucket-interpolated (cluster-wide merge keeps only
    histogram buckets); for this process's exact ring-based numbers use
    ``ray_tpu._private.perf.local_rpc_stats()``."""
    if address is None:
        # fold this driver's not-yet-reported phase deltas in first —
        # the reporter loop only pushes every metrics_report_period_s
        try:
            from ray_tpu.util import metrics as user_metrics

            user_metrics.flush()
        except Exception:  # noqa: BLE001 — summary must not require flush
            pass
    records = _gcs_call(
        "get_metrics", "ray_tpu_rpc_phase_seconds", address=address
    )
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for rec in records or ():
        for key, val in rec["series"].items():
            tags = dict(key)
            m = tags.get("method", "?")
            if method is not None and m != method:
                continue
            boundaries = list(val.get("boundaries") or ())
            buckets = list(val.get("buckets") or ())
            count = int(val.get("count") or 0)
            if not count or not boundaries:
                continue
            row = {
                "count": count,
                "mean_s": float(val.get("sum") or 0.0) / count,
                "p50_s": _bucket_quantile(boundaries, buckets, 0.50),
                "p95_s": _bucket_quantile(boundaries, buckets, 0.95),
                "p99_s": _bucket_quantile(boundaries, buckets, 0.99),
            }
            out.setdefault(m, {})[
                f"{tags.get('side', '?')}.{tags.get('phase', '?')}"
            ] = row
    return out


def list_cluster_events(
    *,
    address: Optional[str] = None,
    type: Optional[str] = None,
    limit: Optional[int] = None,
) -> List[Dict[str, Any]]:
    """The structured cluster event log: node up/down, actor restarts,
    OOM kills, object spills, autoscaler decisions (reference:
    `ray list cluster-events` over gcs_event_manager). Each event is a dict
    with at least ``type``, ``severity``, ``message``, ``ts``."""
    payload: Dict[str, Any] = {}
    if type is not None:
        payload["type"] = type
    if limit is not None:
        payload["limit"] = limit
    return _gcs_call(
        "list_cluster_events", payload or None, address=address
    )


def list_alerts(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    """Current SLO alert states (one row per rule defined via
    ``ray_tpu.slo``): ``name``, ``state`` (ok/pending/firing/resolved),
    latest evaluated ``value`` vs ``threshold``, and any captured trace
    ``exemplars`` — the burn-rate evaluation happens inside the GCS each
    metrics report period."""
    return _gcs_call("alerts", address=address)


def list_slo_rules(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    """The SLO rules currently registered in the GCS (see
    ``ray_tpu.slo.define`` / ``ray_tpu.slo.load_rules``)."""
    return _gcs_call("slo_list", address=address)


def timeline(
    filename: Optional[str] = None, *, address: Optional[str] = None
) -> List[Dict[str, Any]]:
    """Chrome-tracing dump of ALL task execution (reference:
    _private/state.py:416 chrome_tracing_dump; view in ui.perfetto.dev).
    Always on — task events flow to the GCS with no opt-in, so this works
    on any live cluster.

    One ``pid`` lane per node, one ``tid`` row per worker.
    RUNNING→FINISHED/FAILED event pairs become complete ("X") slices on the
    executing worker's row; tasks still in flight become open ("B") begin
    events so a live cluster shows current work; other unpaired events
    become instants.
    """
    events = _gcs_call("get_task_events", address=address)
    # GCS arrival order mixes processes; wall-clock order (same host /
    # NTP-synced hosts) reconstructs the lifecycle for pairing
    events = sorted(events, key=lambda e: e["ts"])

    def _lanes(ev: Dict[str, Any]) -> Tuple[str, str]:
        nid = ev.get("node_id") or ""
        pid = f"node:{nid[:12]}" if nid else "raytpu"
        return pid, f"worker:{(ev.get('worker_id') or '?')[:12]}"

    running: Dict[str, Dict[str, Any]] = {}
    trace: List[Dict[str, Any]] = []
    lanes_seen: Dict[Tuple[str, str], None] = {}
    for ev in events:
        tid = ev["task_id"]
        if ev["state"] == "RUNNING":
            running[tid] = ev
        elif ev["state"] in ("FINISHED", "FAILED") and tid in running:
            start = running.pop(tid)
            pid, lane = _lanes(start)
            lanes_seen.setdefault((pid, lane))
            trace.append(
                {
                    "name": ev["name"],
                    "cat": "task",
                    "ph": "X",
                    "ts": start["ts"] * 1e6,
                    "dur": max(0.0, (ev["ts"] - start["ts"]) * 1e6),
                    "pid": pid,
                    "tid": lane,
                    "args": {"task_id": tid, "state": ev["state"]},
                }
            )
        else:
            pid, lane = _lanes(ev)
            lanes_seen.setdefault((pid, lane))
            trace.append(
                {
                    "name": f"{ev['name']}:{ev['state']}",
                    "cat": "task_state",
                    "ph": "i",
                    "ts": ev["ts"] * 1e6,
                    "pid": pid,
                    "tid": lane,
                    "s": "t",
                }
            )
    # still-RUNNING tasks (no FINISHED/FAILED yet): open "B" begin events on
    # their worker's lane — paired-only "X" slices would make a live
    # cluster's current work invisible
    for tid, start in running.items():
        pid, lane = _lanes(start)
        lanes_seen.setdefault((pid, lane))
        trace.append(
            {
                "name": start["name"],
                "cat": "task",
                "ph": "B",
                "ts": start["ts"] * 1e6,
                "pid": pid,
                "tid": lane,
                "args": {"task_id": tid, "state": "RUNNING"},
            }
        )
    # driver-side RPC slices from the perf plane share the task timebase
    # (wall clock), so control-plane latency lines up under the task rows
    try:
        from ray_tpu._private import perf as _perf_mod

        for (method, start_s, total_s, ser_s, send_s, wire_s,
             deser_s) in _perf_mod.recent_slices():
            pid, lane = "rpc (driver)", method
            lanes_seen.setdefault((pid, lane))
            trace.append(
                {
                    "name": method,
                    "cat": "rpc",
                    "ph": "X",
                    "ts": start_s * 1e6,
                    "dur": total_s * 1e6,
                    "pid": pid,
                    "tid": lane,
                    "args": {
                        "serialize_us": ser_s * 1e6,
                        "send_us": send_s * 1e6,
                        "wire_us": wire_s * 1e6,
                        "deserialize_us": deser_s * 1e6,
                    },
                }
            )
    except Exception:  # noqa: BLE001 — timeline must not require perf
        pass
    # metadata records name the lanes in trace viewers
    for pid, lane in lanes_seen:
        trace.append(
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": lane,
             "args": {"name": lane}}
        )
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


# ----------------------------------------------------------------------
# log plane: list_logs / get_log / dump_stacks (reference: `ray logs`,
# `ray stack`, python/ray/util/state/api.py get_log streaming from the
# agent on the owning node)
# ----------------------------------------------------------------------


def _id_hex(value: Any) -> str:
    """Accept an ID object, bytes, or hex string (full or prefix)."""
    if value is None:
        return ""
    if hasattr(value, "hex") and not isinstance(value, str):
        h = value.hex
        return h() if callable(h) else h
    return str(value)


def _find_node(node_id: Any, address: Optional[str]) -> Dict[str, Any]:
    """Resolve a node id (hex prefix ok) to its GCS node row."""
    want = _id_hex(node_id)
    for node in list_nodes(address=address):
        if node.get("alive") and node["node_id"].hex().startswith(want):
            return node
    raise ValueError(f"no alive node with id {want!r}")


def list_logs(
    *, node_id: Any = None, address: Optional[str] = None
) -> Dict[str, List[Dict[str, Any]]]:
    """Enumerate log files cluster-wide (or on one node): a dict of node id
    hex -> [{"filename", "size", "mtime"}, ...]. The result carries an
    ``errors`` attribute like :func:`list_objects`."""
    want = _id_hex(node_id) if node_id is not None else None
    out: Dict[str, List[Dict[str, Any]]] = {}
    errors: List[Dict[str, str]] = []
    for node in list_nodes(address=address):
        nid = node["node_id"].hex()
        if not node.get("alive"):
            continue
        if want is not None and not nid.startswith(want):
            continue
        raylet_addr = "{}:{}".format(*node["address"])
        try:
            listing = _cached_client(raylet_addr).call("list_logs", timeout=10.0)
            out[nid] = listing["files"]
        except Exception as e:  # noqa: BLE001
            _record_node_error(errors, "list_logs", nid, e)
    if want is not None and not out and not errors:
        raise ValueError(f"no alive node with id {want!r}")

    class _Listing(dict):
        pass

    result = _Listing(out)
    result.errors = errors
    return result


def read_log_chunk(
    *,
    node_id: Any,
    filename: str,
    offset: Optional[int] = None,
    max_bytes: int = 1 << 20,
    tail_lines: Optional[int] = None,
    follow: bool = False,
    timeout_s: float = 10.0,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """One byte-ranged read against the raylet owning ``filename``. The
    building block under :func:`get_log`; ``follow=True`` long-polls until
    bytes exist past ``offset``. Returns the raylet's reply dict
    (``data``/``next_offset``/``eof`` or ``error``)."""
    node = _find_node(node_id, address)
    raylet_addr = "{}:{}".format(*node["address"])
    payload: Dict[str, Any] = {
        "filename": filename,
        "max_bytes": max_bytes,
        "follow": follow,
        "timeout_s": timeout_s,
    }
    if offset is not None:
        payload["offset"] = offset
    if tail_lines is not None:
        payload["tail_lines"] = tail_lines
    return _cached_client(raylet_addr).call(
        "read_log", payload, timeout=timeout_s + 30.0
    )


def _locate_worker_log(
    task_id: Any, actor_id: Any, address: Optional[str]
) -> Tuple[str, str, Optional[str]]:
    """(node_id_hex, filename, task_id_hex_or_None) for a task/actor id."""
    if task_id is not None:
        loc = _gcs_call(
            "locate_worker", {"task_id": _id_hex(task_id)}, address=address
        )
        if loc is None:
            raise ValueError(
                f"task {_id_hex(task_id)!r} has not (yet) run on any worker "
                "— no RUNNING event in the GCS"
            )
        return (
            loc["node_id"],
            f"worker-{loc['worker_id'][:12]}.log",
            loc["task_id"],
        )
    loc = _gcs_call(
        "locate_worker", {"actor_id": _id_hex(actor_id)}, address=address
    )
    if loc is None:
        raise ValueError(f"actor {_id_hex(actor_id)!r} has no live worker")
    return loc["node_id"], f"worker-{loc['worker_id'][:12]}.log", None


def get_log(
    *,
    node_id: Any = None,
    filename: Optional[str] = None,
    task_id: Any = None,
    actor_id: Any = None,
    tail: int = 1000,
    follow: bool = False,
    timeout_s: float = 10.0,
    address: Optional[str] = None,
) -> Iterator[str]:
    """Stream a log file's lines from whichever node holds it.

    Exactly one target: ``node_id`` + ``filename``, or ``task_id`` (slices
    the lines between that task's ``::task_begin``/``::task_end`` markers in
    its worker's log), or ``actor_id`` (its worker's whole log). ``tail=N``
    starts N lines from the end (-1 = whole file); ``follow=True`` keeps the
    iterator open, yielding lines as they are appended (break to stop)."""
    task_filter: Optional[str] = None
    if task_id is not None or actor_id is not None:
        if filename is not None:
            raise ValueError("pass filename OR task_id/actor_id, not both")
        node_id, filename, task_filter = _locate_worker_log(
            task_id, actor_id, address
        )
    elif filename is None:
        raise ValueError("get_log needs node_id+filename, task_id, or actor_id")
    elif node_id is None:
        raise ValueError("get_log(filename=...) needs node_id")

    def _stream() -> Iterator[str]:
        # marker slicing needs the whole file; plain tail is served
        # server-side on the first chunk
        offset: Optional[int] = 0 if (task_filter or tail < 0) else None
        buf = b""
        in_task = False
        while True:
            chunk = read_log_chunk(
                node_id=node_id,
                filename=filename,
                offset=offset,
                tail_lines=tail if offset is None else None,
                follow=follow,
                timeout_s=timeout_s,
                address=address,
            )
            if chunk.get("error"):
                raise RuntimeError(chunk["error"])
            offset = chunk["next_offset"]
            buf += chunk["data"]
            while b"\n" in buf:
                raw, buf = buf.split(b"\n", 1)
                line = raw.decode("utf-8", errors="replace")
                if line.startswith("::task_"):
                    # boundary markers are machine-readable metadata: they
                    # drive task slicing but never surface as output
                    if task_filter is not None and f"task_id={task_filter} " in line:
                        in_task = line.startswith("::task_begin ")
                    continue
                if task_filter is not None and not in_task:
                    continue
                yield line
            if chunk.get("eof") and not follow:
                if buf:  # unterminated final line
                    line = buf.decode("utf-8", errors="replace")
                    if not line.startswith("::task_") and (
                        task_filter is None or in_task
                    ):
                        yield line
                return

    lines = _stream()
    if not follow and task_filter is not None and tail >= 0:
        return iter(list(lines)[-tail:])
    return lines


def dump_stacks(
    *,
    duration_s: float = 0.05,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """One-shot all-workers stack report (the `ray stack` equivalent): fan
    the per-worker ``profile`` RPC out through every alive raylet. Returns
    ``{node_id_hex: {worker_id_hex: {"pid", "folded"} | {"error"}}}`` plus
    an ``errors`` attribute for unreachable nodes."""
    report: Dict[str, Any] = {}
    errors: List[Dict[str, str]] = []
    for node in list_nodes(address=address):
        if not node.get("alive"):
            continue
        nid = node["node_id"].hex()
        raylet_addr = "{}:{}".format(*node["address"])
        try:
            res = _cached_client(raylet_addr).call(
                "dump_stacks", {"duration_s": duration_s},
                timeout=duration_s + 30.0,
            )
            report[nid] = res["workers"]
        except Exception as e:  # noqa: BLE001
            _record_node_error(errors, "dump_stacks", nid, e)

    class _Report(dict):
        pass

    result = _Report(report)
    result.errors = errors
    return result


def list_trace_spans(*, address: Optional[str] = None) -> List[Dict[str, Any]]:
    """Harvest every process's span ring: the connected driver's own, the
    GCS's, and — through each alive raylet — every registered worker's
    (the dump_stacks fan-out, pointed at ``trace_spans``). Returns a flat
    list of span dicts annotated with ``node_id``/``process``, plus an
    ``errors`` attribute for unreachable nodes — partial results beat no
    results when a node died mid-trace."""
    rows = StateListResult()

    def _extend(snapshot: Dict[str, Any], node_id: str, process: str):
        for span in (snapshot or {}).get("spans", ()):
            span = dict(span)
            span["node_id"] = node_id
            span["process"] = process
            rows.append(span)

    if address is None:
        # the driver's own ring first: root spans live here and the driver
        # serves no RPC endpoint the fan-out could reach
        import ray_tpu._private.worker as worker_mod

        from ray_tpu._private import trace as _trace

        w = worker_mod.global_worker
        drv_node = ""
        if w is not None and w.core.node_id is not None:
            drv_node = w.core.node_id.hex()
        _extend(_trace.snapshot(), drv_node, "driver")
    try:
        _extend(_gcs_call("trace_spans", address=address), "", "gcs")
    except Exception as e:  # noqa: BLE001
        _record_node_error(rows.errors, "list_trace_spans", "gcs", e)
    for node in list_nodes(address=address):
        if not node.get("alive"):
            continue
        nid = node["node_id"].hex()
        raylet_addr = "{}:{}".format(*node["address"])
        try:
            res = _cached_client(raylet_addr).call(
                "trace_spans", {}, timeout=30.0
            )
            for key, snap in (res.get("processes") or {}).items():
                if "error" in (snap or {}):
                    continue  # worker died mid-harvest: keep the rest
                _extend(snap, nid, key)
        except Exception as e:  # noqa: BLE001
            _record_node_error(rows.errors, "list_trace_spans", nid, e)
    return rows


def format_stack_report(report: Dict[str, Any]) -> str:
    """Render a :func:`dump_stacks` result for terminals: per node, per
    worker, each sampled stack (most frequent first) one frame per line."""
    out: List[str] = []
    for nid in sorted(report):
        out.append(f"=== node {nid[:12]} ===")
        workers = report[nid]
        if not workers:
            out.append("  (no registered workers)")
        for wid in sorted(workers):
            info = workers[wid]
            if "error" in info:
                out.append(f"-- worker {wid[:12]}: unreachable ({info['error']})")
                continue
            out.append(f"-- worker {wid[:12]} (pid {info.get('pid')}) --")
            folded = info.get("folded", {})
            if not folded:
                out.append("  (no samples)")
            for stack, count in sorted(folded.items(), key=lambda kv: -kv[1]):
                out.append(f"  [{count} sample{'s' if count != 1 else ''}]")
                for frame in stack.split(";"):
                    out.append(f"    {frame}")
    return "\n".join(out)
