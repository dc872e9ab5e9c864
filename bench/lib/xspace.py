"""What ``jax.profiler.ProfileData`` does not give of an ``.xplane.pb``:
each device operation's name-scope path, read from the ``tf_op`` stat of
its event metadata (``jit(run)/while/body/gal.weight_fit/...:``), with a
minimal reader of the protobuf wire format (no TensorFlow, no xprof).

The fields read, from the XSpace protos:

  XSpace          planes = 1
  XPlane          name = 2, event_metadata = 4, stat_metadata = 5
                  (maps from an int64 id: entries have key = 1, value = 2)
  XEventMetadata  name = 2, stats = 5
  XStatMetadata   name = 2
  XStat           metadata_id = 1, str_value = 5, ref_value = 7 (the id
                  of a stat metadata whose name is the string), and any
                  integer value as a varint (uint64 = 3, int64 = 4)

Every other field is skipped without being decoded.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple, Union

SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"

Value = Union[int, memoryview]


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, Value]]:
    """(field number, value) of each field of one message: an int for a
    varint, a view of the bytes for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(value: Value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_values(plane: memoryview, number: int) -> Iterator[memoryview]:
    for num, entry in fields(plane):
        if num == number:
            for k, value in fields(entry):
                if k == 2:
                    yield value


def _stat_names(plane: memoryview) -> Dict[int, str]:
    names = {}
    for meta in _map_values(plane, 5):
        f = dict(fields(meta))
        names[f.get(1, 0)] = _text(f.get(2, b""))
    return names


def _plane_scopes(plane: memoryview) -> Dict[Tuple[Optional[int], str], str]:
    stat_names = _stat_names(plane)
    ids = {v: k for k, v in stat_names.items()}
    scope_id, program_id = ids.get(SCOPE_STAT), ids.get(PROGRAM_STAT)
    out: Dict[Tuple[Optional[int], str], str] = {}
    if scope_id is None:
        return out
    for meta in _map_values(plane, 4):
        name, scope, program = "", None, None
        for num, value in fields(meta):
            if num == 2:
                name = _text(value)
            elif num == 5:
                stat = dict(fields(value))
                if stat.get(1) == scope_id:
                    scope = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
                elif stat.get(1) == program_id:
                    program = stat.get(3, stat.get(4))
        if scope is not None:
            out[(program, name)] = scope
    return out


def op_scopes(path: Path, plane_name: re.Pattern
              ) -> Dict[str, Dict[Tuple[Optional[int], str], str]]:
    """For each plane whose name matches ``plane_name``: the name-scope
    path of each operation, keyed by (program id, event name)."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for num, plane in fields(buf):
        if num != 1:
            continue
        name = next((_text(v) for k, v in fields(plane) if k == 2), "")
        if plane_name.match(name):
            out[name] = _plane_scopes(plane)
    return out
