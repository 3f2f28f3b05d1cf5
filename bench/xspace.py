"""A reader of the profiler's ``.xplane.pb`` (an ``XSpace`` protobuf) that
keeps what ``jax.profiler.ProfileData`` leaves out: the stats an event
inherits from its metadata. On a TPU the ``XLA Ops`` events carry their
op's JAX scope (``tf_op``) and HLO text (``long_name``) there, not on the
event itself.

It decodes the protobuf wire format directly, with no generated code,
and keeps only the fields below (field numbers of tsl's ``xplane.proto``):

  XSpace          planes 1
  XPlane          name 2, lines 3, event_metadata 4, stat_metadata 5
  XLine           name 2, timestamp_ns 3, events 4
  XEvent          metadata_id 1, offset_ps 2, duration_ps 3, stats 4
  XEventMetadata  id 1, name 2, display_name 4, stats 5
  XStatMetadata   id 1, name 2
  XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                  bytes 6, ref 7

Where the chip's events carry no scope, the program's HLO does: the
plane ``/host:metadata`` holds one event metadata per compiled module,
named as the ``XLA Modules`` events are (``jit_batch_search(12)``), with
its ``HloProto`` as the bytes of the stat ``Hlo Proto``. ``op_names``
reads from it each instruction's JAX scope (field numbers of XLA's
``hlo.proto``: HloProto.hlo_module 1, HloModuleProto.computations 3,
HloComputationProto.instructions 2, HloInstructionProto name 1 and
metadata 7, OpMetadata.op_name 2).
"""
from __future__ import annotations

import dataclasses
import struct


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of each field of one message; a
    length-delimited value is bytes, a fixed64 one raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield num, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, names: dict[int, str]) -> tuple[str, object]:
    mid, val, ref = 0, None, None
    for num, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = v.decode(errors="replace")
        elif num == 6:
            val = bytes(v)
        elif num == 7:
            ref = v
    if ref is not None:
        val = names.get(ref, "")
    return names.get(mid, str(mid)), val


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: dict          # the event's own stats over its metadata's

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    # event metadata by id: (name, stats), for events and for planes
    # such as /host:metadata that keep their content there
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Space:
    planes: list


def _map_value(buf: bytes) -> bytes:
    """The value of one entry of a protobuf map field."""
    for num, v in _fields(buf):
        if num == 2:
            return v
    return b""


def _plane(buf: bytes) -> Plane:
    name, lines, ev_meta, stat_names = "", [], [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = v.decode(errors="replace")
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(_map_value(v))
        elif num == 5:
            sid, sname = 0, ""
            for n2, v2 in _fields(_map_value(v)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = v2.decode(errors="replace")
            stat_names[sid] = sname
    meta: dict[int, tuple[str, dict]] = {}
    for m in ev_meta:
        mid, mname, disp, stats = 0, "", "", {}
        for num, v in _fields(m):
            if num == 1:
                mid = v
            elif num == 2:
                mname = v.decode(errors="replace")
            elif num == 4:
                disp = v.decode(errors="replace")
            elif num == 5:
                k, val = _stat(v, stat_names)
                stats[k] = val
        meta[mid] = (mname or disp, stats)
    return Plane(name, [_line(ln, meta, stat_names) for ln in lines], meta)


def _line(buf: bytes, meta: dict, stat_names: dict) -> Line:
    name, ts_ns, raw = "", 0, []
    for num, v in _fields(buf):
        if num == 2:
            name = v.decode(errors="replace")
        elif num == 3:
            ts_ns = _signed(v)
        elif num == 4:
            raw.append(v)
    events = []
    for ev in raw:
        mid = off = dur = 0
        own: dict = {}
        for num, v in _fields(ev):
            if num == 1:
                mid = v
            elif num == 2:
                off = _signed(v)
            elif num == 3:
                dur = _signed(v)
            elif num == 4:
                k, val = _stat(v, stat_names)
                own[k] = val
        mname, mstats = meta.get(mid, ("", {}))
        start = ts_ns + off / 1000.0
        events.append(Event(mname, start, start + dur / 1000.0,
                            {**mstats, **own}))
    return Line(name, events)


def read(path: str) -> Space:
    """The planes of one ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        buf = f.read()
    return Space([_plane(v) for num, v in _fields(buf) if num == 1])


def _only(buf: bytes, num: int) -> list[bytes]:
    return [v for n, v in _fields(buf) if n == num]


def op_names(hlo_proto: bytes) -> dict[str, str]:
    """Instruction name -> JAX scope (its ``op_name`` metadata) of every
    instruction of one serialized ``HloProto``."""
    out: dict[str, str] = {}
    for module in _only(hlo_proto, 1):
        for comp in _only(module, 3):
            for inst in _only(comp, 2):
                name, scope = "", ""
                for num, v in _fields(inst):
                    if num == 1:
                        name = v.decode(errors="replace")
                    elif num == 7:
                        for n2, v2 in _fields(v):
                            if n2 == 2:
                                scope = v2.decode(errors="replace")
                if scope:
                    out[name] = scope
    return out
