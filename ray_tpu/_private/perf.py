"""Control-plane perf plane: RPC phase stats + sampling profiler core.

Three jobs, all process-local and allocation-light:

1. **RPC phase accumulators** — ``rpc.py`` stamps ``time.monotonic_ns()``
   at phase boundaries (client: serialize/send/wire/deserialize; server:
   deserialize/queue/handler/reply) and hands the deltas here. Each
   (side, method, phase) gets a fixed-size ring (exact recent samples)
   plus histogram buckets (cumulative, cheap to merge cluster-wide).
   The buckets are exported through the ordinary metrics registry as the
   ``ray_tpu_rpc_phase_seconds`` family via a snapshot adapter, so the
   reporter thread, GCS aggregation, and ``/metrics`` exposition all see
   them without any extra plumbing — and without the per-call tag-dict
   allocation of ``Metric.observe`` (reference: src/ray/rpc/ server/
   client call instrumentation feeding src/ray/stats/).

   Hot-path contract: recording is guarded by one module-attribute read
   (``_enabled``), mirrors the chaos hooks' "true no-op when off"
   invariant, takes no locks, and allocates nothing but the tuple-free
   ring/bucket writes. Races between recorder threads can drop a sample;
   that is deliberate — these are statistics, not ledgers.

2. **Sampling profiler** — ``sample_self()`` runs a
   ``sys._current_frames()`` sampler in THIS process (same folded-stack
   format as ``TaskExecutor.rpc_profile``, plus a thread-name root
   frame); raylet/GCS register it as a ``perf_profile`` handler and the
   public ``ray_tpu.perf.profile()`` fans it cluster-wide.

3. **Overhead attribution** — ``measure_overhead()`` times the actual
   hot-path patterns (unarmed chaos hook, metrics inc, retry
   classification, phase recording) in paired loops against an empty
   baseline, giving ns/op per subsystem for ``bench_core.py
   --attribute`` and the budget regression test.
"""

from __future__ import annotations

import bisect
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private import accelerator

# ---------------------------------------------------------------------------
# RPC phase accumulators
# ---------------------------------------------------------------------------

#: phase histogram boundaries (seconds) — finer than LATENCY_BUCKETS at
#: the microsecond end, where serialize/send phases actually live
PHASE_BUCKETS: Tuple[float, ...] = (
    1e-6, 2.5e-6, 5e-6, 1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
    1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)

CLIENT_PHASES = ("serialize", "send", "wire", "deserialize", "total")
SERVER_PHASES = ("deserialize", "queue", "handler", "reply")
#: same-process fast-path calls (rpc.py local transport) record under
#: their own side with client-shaped phases, so `perf rpcs` stays honest
#: about which calls never touched a socket ("wire" there is dispatch +
#: handler time, "send" is the enqueue cost)
LOCAL_PHASES = CLIENT_PHASES

RING_SIZE = 512        # exact recent samples per (side, method, phase)
SLICE_RING_SIZE = 2048  # recent per-call slices kept for timeline()

#: one attribute read guards every hot-path record (chaos-hook pattern)
_enabled = True


def set_enabled(on: bool) -> None:
    """Arm/disarm phase recording process-wide (attribution harness)."""
    global _enabled
    _enabled = bool(on)


class _PhaseStats:
    """Accumulator for one (side, method, phase): buckets + ring.

    Lock-free by design: every mutation is a single-element write or an
    int/float in-place add under the GIL; concurrent recorders can lose
    the odd sample, never corrupt structure."""

    __slots__ = ("buckets", "sum", "count", "ring", "ring_idx")

    def __init__(self):
        self.buckets = [0] * (len(PHASE_BUCKETS) + 1)
        self.sum = 0.0
        self.count = 0
        self.ring = [0.0] * RING_SIZE
        self.ring_idx = 0

    def add(self, seconds: float) -> None:
        self.buckets[bisect.bisect_left(PHASE_BUCKETS, seconds)] += 1
        self.sum += seconds
        self.count += 1
        i = self.ring_idx
        self.ring[i & (RING_SIZE - 1)] = seconds
        self.ring_idx = i + 1

    def recent(self) -> List[float]:
        n = min(self.count, self.ring_idx, RING_SIZE)
        return self.ring[:n] if self.ring_idx <= RING_SIZE else list(self.ring)


#: method -> tuple of _PhaseStats aligned with CLIENT_PHASES / SERVER_PHASES
_client: Dict[str, Tuple[_PhaseStats, ...]] = {}
_server: Dict[str, Tuple[_PhaseStats, ...]] = {}
_local: Dict[str, Tuple[_PhaseStats, ...]] = {}
_struct_lock = threading.Lock()
_registered = False

#: recent per-call client slices for timeline():
#: (method, wall_start_s, total_s, serialize_s, send_s, wire_s, deser_s)
_slices: deque = deque(maxlen=SLICE_RING_SIZE)


def _register_exporter() -> None:
    """Register the snapshot adapter with the user metrics registry (once,
    lazily — importing this module must stay free)."""
    global _registered
    if _registered:
        return
    with _struct_lock:
        if _registered:
            return
        _registered = True
    try:
        from ray_tpu.util import metrics as user_metrics

        class _PhaseExporter(user_metrics.Metric):
            TYPE = "histogram"

            def _snapshot(self) -> Dict[str, Any]:
                series: Dict[Tuple, Any] = {}
                for side, table, phases in (
                    ("client", _client, CLIENT_PHASES),
                    ("server", _server, SERVER_PHASES),
                    ("local", _local, LOCAL_PHASES),
                ):
                    for method, entry in list(table.items()):
                        for phase, st in zip(phases, entry):
                            if not st.count:
                                continue
                            key = (  # sorted tag order, like Metric._key
                                ("method", method),
                                ("phase", phase),
                                ("side", side),
                            )
                            series[key] = {
                                "buckets": list(st.buckets),
                                "sum": st.sum,
                                "count": st.count,
                                "boundaries": PHASE_BUCKETS,
                            }
                return {
                    "name": self.name,
                    "type": self.TYPE,
                    "description": self.description,
                    "series": series,
                }

        _PhaseExporter(
            "ray_tpu_rpc_phase_seconds",
            "per-phase RPC latency (client: serialize/send/wire/"
            "deserialize/total; server: deserialize/queue/handler/reply)",
            tag_keys=("method", "phase", "side"),
        )
    except Exception:
        pass  # metrics must never break the rpc path


def _stats_for(
    table: Dict[str, Tuple[_PhaseStats, ...]], method: str, nphases: int
) -> Tuple[_PhaseStats, ...]:
    entry = table.get(method)
    if entry is None:
        with _struct_lock:
            entry = table.get(method)
            if entry is None:
                entry = tuple(_PhaseStats() for _ in range(nphases))
                table[method] = entry
        _register_exporter()
    return entry


def record_client(
    method: str, t0: int, ser_ns: int, send_ns: int, td0: int, td1: int
) -> None:
    """One client-side RPC completed. ``t0`` is the pre-serialize stamp,
    ``ser_ns``/``send_ns`` the phase deltas stashed at send time, ``td0``/
    ``td1`` bracket the reply deserialize (all ``monotonic_ns``)."""
    total_ns = td1 - t0
    deser_ns = td1 - td0
    wire_ns = total_ns - ser_ns - send_ns - deser_ns
    if wire_ns < 0:
        wire_ns = 0
    entry = _stats_for(_client, method, len(CLIENT_PHASES))
    entry[0].add(ser_ns * 1e-9)
    entry[1].add(send_ns * 1e-9)
    entry[2].add(wire_ns * 1e-9)
    entry[3].add(deser_ns * 1e-9)
    entry[4].add(total_ns * 1e-9)
    total_s = total_ns * 1e-9
    _slices.append((
        method, time.time() - total_s, total_s,
        ser_ns * 1e-9, send_ns * 1e-9, wire_ns * 1e-9, deser_ns * 1e-9,
    ))


def record_local(
    method: str, t0: int, ser_ns: int, send_ns: int, td0: int, td1: int
) -> None:
    """One same-process fast-path RPC completed (rpc.py local transport).
    Same stamps as :func:`record_client`; "wire" covers dispatch + handler
    time since no socket is involved."""
    total_ns = td1 - t0
    deser_ns = td1 - td0
    wire_ns = total_ns - ser_ns - send_ns - deser_ns
    if wire_ns < 0:
        wire_ns = 0
    entry = _stats_for(_local, method, len(LOCAL_PHASES))
    entry[0].add(ser_ns * 1e-9)
    entry[1].add(send_ns * 1e-9)
    entry[2].add(wire_ns * 1e-9)
    entry[3].add(deser_ns * 1e-9)
    entry[4].add(total_ns * 1e-9)
    total_s = total_ns * 1e-9
    _slices.append((
        method, time.time() - total_s, total_s,
        ser_ns * 1e-9, send_ns * 1e-9, wire_ns * 1e-9, deser_ns * 1e-9,
    ))


def record_server(
    method: str,
    deser_ns: int = 0,
    queue_ns: Optional[int] = None,
    handler_ns: Optional[int] = None,
    reply_ns: Optional[int] = None,
) -> None:
    entry = _stats_for(_server, method, len(SERVER_PHASES))
    if deser_ns:
        entry[0].add(deser_ns * 1e-9)
    if queue_ns is not None:
        entry[1].add(queue_ns * 1e-9 if queue_ns > 0 else 0.0)
    if handler_ns is not None:
        entry[2].add(handler_ns * 1e-9)
    if reply_ns is not None:
        entry[3].add(reply_ns * 1e-9)


def local_rpc_stats() -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Exact per-phase stats for THIS process from the rings (the
    cluster-wide view is ``ray_tpu.util.state.summarize_rpcs``)."""
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for side, table, phases in (
        ("client", _client, CLIENT_PHASES),
        ("server", _server, SERVER_PHASES),
        ("local", _local, LOCAL_PHASES),
    ):
        for method, entry in list(table.items()):
            for phase, st in zip(phases, entry):
                if not st.count:
                    continue
                samples = sorted(st.recent())
                n = len(samples)
                row = out.setdefault(method, {}).setdefault(
                    f"{side}.{phase}", {}
                )
                row["count"] = st.count
                row["mean_s"] = st.sum / st.count
                if n:
                    row["p50_s"] = samples[max(0, int(0.50 * n) - 1)]
                    row["p95_s"] = samples[max(0, int(0.95 * n) - 1)]
                    row["p99_s"] = samples[max(0, int(0.99 * n) - 1)]
    return out


def recent_slices(limit: int = SLICE_RING_SIZE) -> List[Tuple]:
    """Most recent client-side RPC slices (for timeline() lanes)."""
    sl = list(_slices)
    return sl[-limit:]


def reset_stats() -> None:
    """Drop accumulated phase stats (tests / attribution harness)."""
    with _struct_lock:
        _client.clear()
        _server.clear()
        _local.clear()
    _slices.clear()


# ---------------------------------------------------------------------------
# sampling profiler
# ---------------------------------------------------------------------------


def sample_self(
    duration_s: float = 2.0, hz: float = 100.0, role: str = ""
) -> Dict[str, Any]:
    """Sample every thread's stack in THIS process for ``duration_s`` at
    ``hz``, returning folded stacks rooted at the thread name (merge-
    compatible with ``TaskExecutor.rpc_profile`` output)."""
    duration_s = min(float(duration_s), 30.0)
    interval = 1.0 / max(1.0, min(float(hz), 1000.0))
    folded: Dict[str, int] = {}
    samples = 0
    me = threading.get_ident()
    names = {t.ident: t.name for t in threading.enumerate()}
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue  # don't profile the profiler
            name = names.get(tid)
            if name is None:
                names = {t.ident: t.name for t in threading.enumerate()}
                name = names.get(tid, f"tid-{tid}")
            stack = f"{name};" + ";".join(accelerator.fold_stack(frame))
            folded[stack] = folded.get(stack, 0) + 1
        samples += 1
        time.sleep(interval)
    try:
        from ray_tpu._private import internal_metrics

        internal_metrics.inc("ray_tpu_perf_profile_runs_total")
        internal_metrics.inc(
            "ray_tpu_perf_profile_samples_total", float(samples)
        )
    except Exception:
        pass
    return {
        "pid": os.getpid(),
        "role": role,
        "samples": samples,
        "duration_s": duration_s,
        "hz": hz,
        "folded": folded,
    }


def merge_reports(
    processes: Dict[str, Dict[str, Any]]
) -> Dict[str, int]:
    """Merge per-process folded stacks into one cluster-wide folded dict,
    rooting each stack at its process key."""
    merged: Dict[str, int] = {}
    for proc_key, report in sorted(processes.items()):
        for stack, count in (report.get("folded") or {}).items():
            key = f"{proc_key};{stack}"
            merged[key] = merged.get(key, 0) + count
    return merged


def to_speedscope(
    processes: Dict[str, Dict[str, Any]], name: str = "ray_tpu profile"
) -> Dict[str, Any]:
    """Render per-process folded stacks as a speedscope JSON document —
    one "sampled" profile per process over a shared frame table."""
    frames: List[Dict[str, str]] = []
    frame_idx: Dict[str, int] = {}

    def _frame(token: str) -> int:
        i = frame_idx.get(token)
        if i is None:
            i = len(frames)
            frame_idx[token] = i
            frames.append({"name": token})
        return i

    profiles = []
    for proc_key, report in sorted(processes.items()):
        samples: List[List[int]] = []
        weights: List[float] = []
        for stack, count in (report.get("folded") or {}).items():
            samples.append([_frame(tok) for tok in stack.split(";")])
            weights.append(float(count))
        total = sum(weights)
        profiles.append({
            "type": "sampled",
            "name": f"{proc_key} (pid {report.get('pid', '?')})",
            "unit": "none",
            "startValue": 0,
            "endValue": total,
            "samples": samples,
            "weights": weights,
        })
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "shared": {"frames": frames},
        "profiles": profiles,
        "name": name,
        "exporter": "ray_tpu",
    }


# ---------------------------------------------------------------------------
# overhead attribution
# ---------------------------------------------------------------------------


def _ns_per_op(loop: Callable[[int], None], iters: int, repeats: int) -> float:
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter_ns()
        loop(iters)
        dt = time.perf_counter_ns() - t0
        if best is None or dt < best:
            best = dt
    return best / iters


def measure_overhead(
    iters: int = 200_000, repeats: int = 5
) -> Dict[str, float]:
    """ns/op of each always-on subsystem's hot-path pattern, measured as
    the paired difference against an empty loop (min-of-``repeats`` to
    shed scheduler noise). Keys are stable: the attribution artifact and
    the budget regression test both consume them."""
    from ray_tpu._private import fault_injection as _fi
    from ray_tpu._private.rpc import IDEMPOTENT_METHODS

    def loop_baseline(n):
        for _ in range(n):
            pass

    def loop_chaos(n):
        for _ in range(n):
            if _fi._armed is not None:
                pass

    def loop_retry(n):
        m = "store_put"
        for _ in range(n):
            if m in IDEMPOTENT_METHODS:
                pass

    # scratch counter with the same shape as the real hot-path families;
    # deregistered afterwards so a live process's metrics stay clean
    from ray_tpu.util import metrics as user_metrics

    scratch = user_metrics.Counter(
        "ray_tpu_bench_attribution_scratch", "attribution harness scratch",
        tag_keys=("method",),
    )
    bound = scratch.bind({"method": "x"})

    def loop_inc_bound(n):
        inc = bound.inc
        for _ in range(n):
            inc()

    def loop_inc_tagged(n):
        inc = scratch.inc
        for _ in range(n):
            inc(tags={"method": "x"})

    def loop_phase_record(n):
        ns = time.monotonic_ns
        for _ in range(n):
            t0 = ns()
            t1 = ns()
            record_client("_attribution", t0, t1 - t0, 0, t1, t1)

    def loop_phase_gate(n):
        # the cost a disabled perf plane adds to every rpc: one attr read
        for _ in range(n):
            if _enabled:
                pass

    from ray_tpu._private import trace as _trace_mod

    def loop_trace_gate(n):
        # the cost a disabled tracing plane adds to every hook site: one
        # module-attribute read (the _private/trace.py gated-no-op contract)
        for _ in range(n):
            if _trace_mod._active:
                pass

    hist = user_metrics.Histogram(
        "ray_tpu_bench_attribution_scratch_hist", "attribution scratch",
    )
    bound_hist = hist.bind()

    def loop_exemplar_gate(n):
        # Histogram.observe with tracing disabled: the exemplar hook must
        # collapse to the same one-attribute-read gate, i.e. a full
        # observe() stays within its budget with the hook compiled in
        observe = bound_hist.observe
        for _ in range(n):
            observe(0.01)

    try:
        base = _ns_per_op(loop_baseline, iters, repeats)
        raw = {
            "chaos_hook_unarmed": _ns_per_op(loop_chaos, iters, repeats),
            "retry_classification": _ns_per_op(loop_retry, iters, repeats),
            "metrics_inc_bound": _ns_per_op(loop_inc_bound, iters, repeats),
            "metrics_inc_tagged": _ns_per_op(loop_inc_tagged, iters, repeats),
            "rpc_phase_record": _ns_per_op(
                loop_phase_record, max(iters // 4, 1), repeats
            ),
            "rpc_phase_gate": _ns_per_op(loop_phase_gate, iters, repeats),
            "trace_hook_disabled": _ns_per_op(loop_trace_gate, iters, repeats),
            "exemplar_hook_disabled": _ns_per_op(
                loop_exemplar_gate, iters, repeats
            ),
        }
    finally:
        with user_metrics._registry_lock:
            if scratch in user_metrics._registry:
                user_metrics._registry.remove(scratch)
            if hist in user_metrics._registry:
                user_metrics._registry.remove(hist)
        # phase record fills rings for "_attribution"; drop them again
        _client.pop("_attribution", None)
    out = {"loop_baseline": base}
    for k, v in raw.items():
        out[k] = max(v - base, 0.0)
    return out


#: per-call ns budgets enforced by the regression test — the "no-ops when
#: unarmed must be true no-ops" invariant, as numbers. Generous vs the
#: ~30 ns an attribute read costs, to survive noisy shared boxes.
OVERHEAD_BUDGET_NS = {
    # tightened after the control-plane hot-path rebuild (measured 21.5 /
    # 286.7 / 9.8 ns/op on a 2.1 GHz shared core, BENCH_ATTRIBUTION.json)
    # — still ~15-20x headroom for box noise
    "chaos_hook_unarmed": 400.0,
    "metrics_inc_bound": 4000.0,
    "rpc_phase_gate": 400.0,
    "trace_hook_disabled": 400.0,
    # a full BoundHistogram.observe with the trace-exemplar hook gated
    # off — same ceiling as the bound counter path it rides next to
    "exemplar_hook_disabled": 4000.0,
}
