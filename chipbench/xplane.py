"""What ``jax.profiler.ProfileData`` leaves out of an ``.xplane.pb`` trace:
the ``tf_op`` of each device operation, read from the protobuf wire format
with nothing but the standard library.

A device plane's event metadata holds, per operation, its name (the HLO
instruction text that ``ProfileData`` gives as an event's name) and stats,
among them ``tf_op``: the HLO ``op_name``, which the program's
``jax.named_scope`` stages extend (``jit(_resident_block_step)/verify/...``).
The fields read (tensorflow/tsl ``xplane.proto``)::

    XSpace.planes = 1
    XPlane.name = 2, .event_metadata = 4 (map<int64, XEventMetadata>),
           .stat_metadata = 5 (map<int64, XStatMetadata>)
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

from chipbench.tracing import DEVICE_PLANE

TF_OP = "tf_op"

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message in ``buf[i:end]``; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _I64:
            value, i = None, i + 8
        elif wire == _I32:
            value, i = None, i + 4
        elif wire == _LEN:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield field, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    """The value (field 2) of a protobuf map entry."""
    for field, value in _fields(buf, *span):
        if field == 2:
            return value
    return None


def _plane_tf_ops(buf: bytes, span) -> Dict[str, str]:
    """``{operation name: tf_op}`` of one plane; empty unless the plane is
    a TPU device's (its name, field 2, precedes the metadata maps)."""
    events, stat_names = [], {}
    for field, value in _fields(buf, *span):
        if field == 2:
            if not DEVICE_PLANE.match(_text(buf, value)):
                return {}
        elif field == 4:
            events.append(_map_value(buf, value))
        elif field == 5:
            meta = _map_value(buf, value)
            sid, sname = None, ""
            for f, v in _fields(buf, *meta):
                if f == 1:
                    sid = v
                elif f == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
    tf_ops: Dict[str, str] = {}
    for ev in events:
        if ev is None:
            continue
        op_name, stats = "", []
        for f, v in _fields(buf, *ev):
            if f == 2:
                op_name = _text(buf, v)
            elif f == 5:
                stats.append(dict(_fields(buf, *v)))
        for st in stats:
            if stat_names.get(st.get(1)) != TF_OP:
                continue
            if 5 in st:
                tf_ops.setdefault(op_name, _text(buf, st[5]))
            elif 7 in st:
                tf_ops.setdefault(op_name, stat_names.get(st[7], ""))
    return tf_ops


def device_tf_ops(path: str) -> Dict[str, str]:
    """``{operation name: tf_op}`` over the ``/device:TPU:N`` planes; an
    operation without a ``tf_op`` is absent.  Where two operations share a
    name (on one device or on several running one program), the first
    ``tf_op`` is kept."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, str] = {}
    for field, value in _fields(buf, 0, len(buf)):
        if field == 1:
            for op_name, tf_op in _plane_tf_ops(buf, value).items():
                out.setdefault(op_name, tf_op)
    return out
