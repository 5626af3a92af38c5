"""From a profiler trace to numbers: device busy time, time per operation,
per kernel and per scope, exposed collective time, and idle gaps by what the
host was doing.

The process that holds the chip writes an ``.xplane.pb`` (``jax.profiler``);
:func:`load_xplane` keeps the lines the reduction reads as plain lists,
:func:`load_scopes` the scope each instruction ran under, and :func:`reduce`
works on those alone, so it is checked in the tests on recorded traces kept as
JSON beside them.

What a TPU trace holds (seen on a v5e, jax 0.9.0): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per HLO instruction
run by the core — nested where an instruction (``while``) runs others — and
whose line ``Async XLA Ops`` has one event from each ``*-start`` to its
``*-done``, and whose line ``XLA Modules`` has one event per run of a compiled
program (``jit_step(<id>)``), which says whose instruction an event is; one plane ``/host:CPU`` with a line per host thread that carries
``TraceAnnotation`` spans and the runtime's own (transfers, dispatch). All
share one clock, nanoseconds from the trace's start. An event's name is its
HLO instruction (``fusion.12``; a Pallas kernel's is the ``name=`` of its
``pallas_call`` with a number, ``flash_fwd.18``); the ``jax.named_scope`` it
ran under is in its metadata's ``tf_op`` and in the ``op_name`` of its module's
HLO proto (``jit(step)/jvp(train.forward)/GPT/.../dot_general``), see
``xplane_wire``. An instruction's name is unique in its module only: two
programs of one trace both have a ``fusion.5``.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
)
# host events that only say "the profiler is running" or "python is running"
_HOST_NOISE = re.compile(r"^\$|ThreadpoolListener|PythonRefManager")

# a component of an op_name that is a named scope of the program: a dotted name
# (``train.forward``, ``extend.mlp``, ``paging.gather``), perhaps inside the
# transformations it was traced under (``transpose(jvp(train.forward))``)
_SCOPE = re.compile(r"^(?:\w+\()*[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+\)*$")
NO_SCOPE = "(no scope)"

Event = Tuple[str, float, float]          # name, start ns, duration ns
Segment = Tuple[str, float, float]        # name, start ns, end ns


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, duration_ns), ...]}}`` for the device
    planes' operation and module lines and every host line."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                continue
            events = [
                (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                for e in line.events
                if device or not _HOST_NOISE.search(e.name)
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def load_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: the op_name it was traced under}}`` from the
    HLO protos a trace carries. Under the module ``""``, for a module whose
    proto the trace lacks: the ``tf_op`` of the device planes' event metadata
    by instruction name alone."""
    from benchmark import xplane_wire

    planes = xplane_wire.planes_metadata(path)
    out: Dict[str, Dict[str, str]] = {}
    for plane, metadata in planes.items():
        for name, stats in metadata.items():
            for blob in stats.values():
                if isinstance(blob, bytes):
                    for module, names in xplane_wire.hlo_op_names(blob).items():
                        out.setdefault(module, {}).update(names)
            op = stats.get("tf_op")
            if DEVICE_PLANE.match(plane) and isinstance(op, str) and op:
                out.setdefault("", {})[short_name(name)] = op
    return out


def module_of(event: str) -> str:
    """``jit_step(8392355915514388644)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event)


def kernel_of(instruction: str) -> str:
    """``flash_fwd.18`` -> ``flash_fwd``: the instruction without the number
    the compiler gave this copy of it."""
    return re.sub(r"\.\d+$", "", instruction)


def scope_of(op_name: str) -> str:
    """The innermost named scope of an ``op_name``, as it stands there (a
    scope's backward pass is ``transpose(jvp(<scope>))``), or ``NO_SCOPE``."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if _SCOPE.match(part):
            return part
    return NO_SCOPE


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def subtract(
    intervals: Sequence[Tuple[float, float]], holes: Sequence[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """The parts of ``intervals`` (merged) that no hole (merged) covers."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(segments: Iterable[Segment], lo: float, hi: float) -> List[Segment]:
    return [
        (n, max(a, lo), min(b, hi)) for n, a, b in segments if min(b, hi) > max(a, lo)
    ]


def self_segments(events: Iterable[Event]) -> List[Segment]:
    """Each event's own time: its interval less the events nested in it. One
    core runs one instruction at a time, so these never overlap."""
    out: List[Segment] = []
    stack: List[List[Any]] = []           # name, start, end, cursor

    def pop():
        name, _, end, cursor = stack.pop()
        if cursor < end:
            out.append((name, cursor, end))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][2] <= start:
            pop()
        if stack:
            parent = stack[-1]
            if parent[3] < start:
                out.append((parent[0], parent[3], start))
            end = min(end, parent[2])
            parent[3] = max(parent[3], end)
        stack.append([name, start, end, start])
    while stack:
        pop()
    return out


def annotation_window(
    planes: Dict[str, Dict[str, List[Event]]], annotation: str
) -> Optional[Tuple[float, float]]:
    """From the start of the first ``annotation`` span to the end of the last."""
    spans = [
        (s, s + d)
        for line in planes.get(HOST_PLANE, {}).values()
        for n, s, d in line if n == annotation
    ]
    if not spans:
        return None
    return min(a for a, _ in spans), max(b for _, b in spans)


def _host_label(host: Dict[str, List[Event]], annotation: str, at: float) -> str:
    """What the host was doing at time ``at``: the benchmark's span that
    covers it, and the innermost other span on that thread."""
    for events in host.values():
        covering = [(d, n) for n, s, d in events if s <= at < s + d]
        if any(n == annotation for _, n in covering):
            inner = min((c for c in covering if c[1] != annotation), default=None)
            return f"{annotation}:{inner[1]}" if inner else f"{annotation}:python"
    return f"between {annotation} spans"


def reduce(
    planes: Dict[str, Dict[str, List[Event]]], annotation: str, top: int = 10,
    scopes: Optional[Dict[str, Dict[str, str]]] = None,
) -> Optional[Dict[str, Any]]:
    """Reduce the traced sub-window (see :func:`annotation_window`).

    Returns None where the trace has no such span or no device plane. Times
    are seconds. ``busy_s`` is the mean over the devices of the union of
    their operations' intervals; ``per_device`` lists each device's own.
    ``collective_exposed_s`` is, per device, the time inside collective
    operations (from ``-start`` to ``-done`` when asynchronous) during which
    no other operation ran on that device. ``device_ops`` and ``idle_gaps``
    hold the ``top`` largest; ``ops_by_kernel`` (:func:`kernel_of`) and
    ``ops_by_scope`` (:func:`scope_of` of what ``scopes``, which
    :func:`load_scopes` gives, holds for the instruction in the module that was
    running) hold every one, largest first, the same self time summed."""
    window = annotation_window(planes, annotation)
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if window is None or not devices:
        return None
    lo, hi = window
    host = planes.get(HOST_PLANE, {})
    scopes = scopes or {}
    per_device, busy_of, op_totals, scope_totals, gap_totals = [], {}, {}, {}, {}
    for name in devices:
        lines = planes[name]
        ops = clip(self_segments(lines.get(OPS_LINE, [])), lo, hi)
        busy_of[name] = merge((a, b) for _, a, b in ops)
        runs = sorted((s, s + d, module_of(n)) for n, s, d in lines.get(MODULES_LINE, []))
        starts = [r[0] for r in runs]
        for n, a, b in ops:
            op_totals[n] = op_totals.get(n, 0.0) + (b - a)
            at = bisect.bisect_right(starts, a) - 1
            module = runs[at][2] if at >= 0 and a < runs[at][1] else ""
            scope = scope_of(
                scopes.get(module, {}).get(n) or scopes.get("", {}).get(n, "")
            )
            scope_totals[scope] = scope_totals.get(scope, 0.0) + (b - a)
        collective = merge(
            [(a, b) for n, a, b in ops if COLLECTIVE.search(n)]
            + [
                (a, b) for n, a, b in clip(
                    ((n, s, s + d) for n, s, d in lines.get(ASYNC_LINE, [])), lo, hi
                ) if COLLECTIVE.search(n)
            ]
        )
        others = merge((a, b) for n, a, b in ops if not COLLECTIVE.search(n))
        per_device.append({
            "device": name,
            "busy_s": union_length(busy_of[name]) / 1e9,
            "collective_s": union_length(collective) / 1e9,
            "collective_exposed_s": union_length(subtract(collective, others)) / 1e9,
        })
    # gaps of the busiest device: where the host holds the chip back least
    busiest = max(per_device, key=lambda d: d["busy_s"])
    for a, b in subtract([(lo, hi)], busy_of[busiest["device"]]):
        label = _host_label(host, annotation, (a + b) / 2)
        gap_totals[label] = gap_totals.get(label, 0.0) + (b - a)

    def ranked(totals: Dict[str, float], scale: float, most=top) -> List[List[Any]]:
        return [
            [n, t / scale] for n, t in sorted(totals.items(), key=lambda kv: -kv[1])[:most]
        ]

    by_kernel: Dict[str, float] = {}
    for instruction, t in op_totals.items():
        by_kernel[kernel_of(instruction)] = by_kernel.get(kernel_of(instruction), 0.0) + t

    n = len(devices)
    return {
        "annotation": annotation,
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "busiest_busy_s": busiest["busy_s"],
        "collective_exposed_s": max(d["collective_exposed_s"] for d in per_device),
        "per_device": per_device,
        "device_ops": ranked(op_totals, 1e9 * n),
        "ops_by_kernel": ranked(by_kernel, 1e9 * n, None),
        "ops_by_scope": ranked(scope_totals, 1e9 * n, None),
        "idle_gaps": ranked(gap_totals, 1e9),
    }
