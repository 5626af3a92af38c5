"""What ``jax.profiler.ProfileData`` does not show of an ``.xplane.pb``: the
scope each device instruction ran under. A ``jax.named_scope`` reaches neither
an event's name nor its own statistics; it sits in the statistics of the
event's *metadata* (``tf_op``) and in each instruction's ``op_name`` inside the
``Hlo Proto`` that the ``/host:metadata`` plane carries. No protobuf schema for
either is installed, so this reads the file's wire format itself (field
numbers from tsl's ``xplane.proto`` and xla's ``hlo.proto``); events are left
to ``ProfileData``. Copied from ``scripts/xplane_scopes.py`` (PR 24), which
still reports a whole trace by a fixed list of scopes."""

from __future__ import annotations

import struct
from typing import Any, Dict, Iterator, Tuple


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return x, i


def fields(b) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message; a length-delimited
    value is the bytes, to be read on as a string or a message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def _text(v) -> str:
    return bytes(v).decode("utf8", "replace")


def _map_entry(b):
    key = value = None
    for number, _, v in fields(b):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(b, stat_names: Dict[int, str]):
    """One XStat: its name and its value (a ``ref_value`` through the names)."""
    key = value = None
    for number, _, v in fields(b):
        if number == 1:
            key = stat_names.get(v, v)
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, v)
    return key, value


def plane_metadata(b) -> Tuple[str, Dict[str, Dict[str, Any]]]:
    """An XPlane's name and ``{event metadata name: its statistics}``; the
    plane's lines and their events are skipped unread."""
    name, entries, stat_names = "", [], {}
    for number, _, v in fields(b):
        if number == 2:
            name = _text(v)
        elif number == 4:
            entries.append(v)
        elif number == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for n, _, x in fields(value) if n == 2), "")
    metadata: Dict[str, Dict[str, Any]] = {}
    for entry in entries:
        _, value = _map_entry(entry)
        md_name, stats = "", {}
        for number, _, v in fields(value):
            if number == 2:
                md_name = _text(v)
            elif number == 5:
                k, x = _stat(v, stat_names)
                stats[k] = x
        metadata[md_name] = stats
    return name, metadata


def hlo_op_names(blob: bytes) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` of one serialized
    ``HloProto``. An instruction's name is unique in its module only."""
    modules: Dict[str, Dict[str, str]] = {}
    for number, wire, module in fields(memoryview(blob)):
        if number != 1 or wire != 2:                        # HloProto.hlo_module
            continue
        module_name, names = "", {}
        for n2, w2, computation in fields(module):
            if n2 == 1 and w2 == 2:                         # HloModuleProto.name
                module_name = _text(computation)
            if n2 != 3 or w2 != 2:                          # .computations
                continue
            for n3, w3, instruction in fields(computation):
                if n3 != 2 or w3 != 2:                      # .instructions
                    continue
                name = op = ""
                for n4, w4, v in fields(instruction):
                    if n4 == 1 and w4 == 2:
                        name = _text(v)
                    elif n4 == 7 and w4 == 2:               # OpMetadata.op_name
                        op = next(
                            (_text(x) for n, w, x in fields(v) if n == 2 and w == 2), ""
                        )
                names[name] = op
        modules.setdefault(module_name, {}).update(names)
    return modules


def planes_metadata(path: str) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """``{plane name: {event metadata name: statistics}}`` of a trace file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return dict(plane_metadata(v) for number, _, v in fields(space) if number == 1)
