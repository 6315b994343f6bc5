"""The metadata maps of a profiler ``.xplane.pb``, read from its
protobuf wire format with nothing but the standard library.

``jax.profiler.ProfileData`` gives each device operation's name, start
and duration, but not the stats its event metadata carries: ``tf_op``
(the op's name-stack path, ``jit(run)/insitu.0.fft/.../dot_general:``),
``hlo_category`` (``convolution fusion``, ``data formatting``, ...) and
``source``. ``read`` walks the file's ``XSpace`` and keeps, per plane,
only its ``event_metadata`` and ``stat_metadata`` maps; it skips each
plane's ``lines``, which hold the events and most of the bytes.

The messages, as ``tsl/profiler/protobuf/xplane.proto`` defines them
(field numbers):

* ``XSpace``: ``planes`` 1;
* ``XPlane``: ``name`` 2, ``lines`` 3, ``event_metadata`` 4 and
  ``stat_metadata`` 5 (maps: key 1, value 2);
* ``XEventMetadata``: ``id`` 1, ``name`` 2, ``display_name`` 4,
  ``stats`` 5;
* ``XStatMetadata``: ``id`` 1, ``name`` 2;
* ``XStat``: ``metadata_id`` 1, then one value: ``double_value`` 2,
  ``uint64_value`` 3, ``int64_value`` 4, ``str_value`` 5,
  ``bytes_value`` 6, ``ref_value`` 7 (the id of a stat metadata whose
  name is the value).
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Dict, Iterator, Tuple

VARINT, I64, LEN, I32 = 0, 1, 2, 5


@dataclasses.dataclass
class EventMeta:
    name: str
    display_name: str
    stats: Dict[str, object]        # stat name → value, refs resolved


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint or fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == VARINT:
            val, i = _varint(buf, i)
        elif wire == I64:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == LEN:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == I32:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i} is not "
                             f"one proto3 writes")
        yield num, wire, val


def _int64(val: int) -> int:
    return val - (1 << 64) if val >= 1 << 63 else val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _, val in fields(view):
        if num == 1:
            key = _int64(val)
        elif num == 2:
            value = val
    return key, value


def _stat(view) -> Tuple[int, object, bool]:
    """``(metadata id, value, value is a ref)`` of one ``XStat``."""
    mid, value, ref = 0, None, False
    for num, _, val in fields(view):
        if num == 1:
            mid = val
        elif num == 2:
            value = struct.unpack("<d", val.to_bytes(8, "little"))[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _int64(val)
        elif num == 5:
            value = _text(val)
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value, ref = val, True
    return mid, value, ref


def _plane(view):
    name, events, stat_names = "", {}, {}
    for num, _, val in fields(view):
        if num == 2:
            name = _text(val)
        elif num == 4:
            key, meta = _map_entry(val)
            events[key] = meta
        elif num == 5:
            key, meta = _map_entry(val)
            stat_names[key] = next(
                (_text(v) for n, _, v in fields(meta) if n == 2), "")
    out = {}
    for key, meta in events.items():
        ev = EventMeta("", "", {})
        for num, _, val in fields(meta):
            if num == 2:
                ev.name = _text(val)
            elif num == 4:
                ev.display_name = _text(val)
            elif num == 5:
                mid, value, ref = _stat(val)
                ev.stats[stat_names.get(mid, str(mid))] = (
                    stat_names.get(value, "") if ref else value)
        out[key] = ev
    return name, out


def read(path) -> Dict[str, Dict[int, EventMeta]]:
    """Plane name → its event metadata by id, each with its stats by
    name (a ``ref_value`` resolved to the name it refers to)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = {}
    for num, wire, val in fields(buf):
        if num == 1 and wire == LEN:
            name, events = _plane(val)
            planes[name] = events
    return planes
