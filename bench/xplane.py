"""The `tf_op` stat of each device operation, decoded from a raw XSpace.

`jax.profiler.ProfileData` shows an event's own stats only.  The scope
path of a device operation (`jit(f)/while/body/amtl.grad/...`) is the
`tf_op` stat of the plane's event *metadata*, which it does not show, so
this reads the protobuf wire format itself: varints and length-delimited
fields, nothing else.  Field numbers, from `xplane.proto`:

    XSpace          planes 1
    XPlane          name 2, event_metadata 4, stat_metadata 5
    map entry       key 1, value 2
    XEventMetadata  name 2, stats 5
    XStat           metadata_id 1, str_value 5, ref_value 7
    XStatMetadata   name 2

Planes' event lines (field 3), the bulk of a trace, are skipped by their
length.
"""
from __future__ import annotations

from typing import Iterator

TF_OP = "tf_op"
DEVICE_PREFIX = "/device:"

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: memoryview) -> Iterator[tuple[int, int | memoryview]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == _LEN:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == _I64:
            i += 8
        elif wire == _I32:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_entry(buf: memoryview) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _plane_tf_ops(plane: memoryview) -> tuple[str, dict[str, str]]:
    name, metas, stat_names = "", [], {}
    for number, v in fields(plane):
        if number == 2:
            name = _text(v)
        elif number == 4:
            metas.append(_map_entry(v)[1])
        elif number == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(s) for n, s in fields(meta) if n == 2), "")
    out = {}
    for meta in metas:
        op, tf_op = "", None
        for number, v in fields(meta):
            if number == 2:
                op = _text(v)
            elif number == 5:
                stat = dict(fields(v))
                if stat_names.get(stat.get(1)) != TF_OP:
                    continue
                if 5 in stat:
                    tf_op = _text(stat[5])
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        if tf_op is not None:
            out[op] = tf_op
    return name, out


def tf_ops(serialized: bytes) -> dict[str, dict[str, str]]:
    """Device plane name -> {operation's event name (its HLO text) ->
    its `tf_op` stat}, for the operations that carry one."""
    out = {}
    for number, plane in fields(memoryview(serialized)):
        if number != 1:
            continue
        for n, v in fields(plane):      # the name comes before the metadata
            if n == 2:
                if _text(v).startswith(DEVICE_PREFIX):
                    name, ops = _plane_tf_ops(plane)
                    out[name] = ops
                break
    return out
