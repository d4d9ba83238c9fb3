"""Reads a profiler `.xplane.pb` with nothing but Python.

`jax.profiler.ProfileData` gives planes, lines and events, but not the
statistics kept on an event's *metadata*, and that is where the profiler
puts an operation's `tf_op` (its `jax.named_scope` path, such as
`jit(step)/local_train/...`) and its `hlo_category`. So this decodes the
protocol-buffer wire format of `XSpace` itself (tsl/profiler/protobuf/
xplane.proto): only the fields the reducer needs, and only the lines it
asks for."""

from __future__ import annotations

import dataclasses
import struct
from typing import Callable, Dict, Iterator, List, Tuple


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in `buf`."""
    while pos < end:
        tag, pos = _varint(buf, pos)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val = (pos, pos + n)
            pos += n
        elif wire == 1:
            val = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            val = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield field, wire, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclasses.dataclass
class EventMeta:
    name: str = ""
    display_name: str = ""
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[float, float, int]]   # (start_ns, duration_ns, metadata id)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_meta: Dict[int, EventMeta]


def _stat(buf, span, stat_names) -> Tuple[str, object]:
    key, val = 0, None
    for f, wire, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = _text(buf, v)
        elif f == 7:
            val = stat_names.get(v, "")       # a reference to a stat's name
    return stat_names.get(key, str(key)), val


def _plane(buf: bytes, span, want_line: Callable[[str, str], bool]) -> Plane:
    name, line_spans, meta_spans, stat_names = "", [], [], {}
    for f, _wire, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            line_spans.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            sid, sname = 0, ""
            for ef, _w, ev in _fields(buf, *v):
                if ef == 2:
                    for sf, _sw, sv in _fields(buf, *ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = _text(buf, sv)
            stat_names[sid] = sname
    lines, used = [], set()
    for ls in line_spans:
        lname, ts_ns, ev_spans = "", 0, []
        for f, _wire, v in _fields(buf, *ls):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                ts_ns = _signed(v)
            elif f == 4:
                ev_spans.append(v)
        if not want_line(name, lname):
            continue
        events = []
        for es in ev_spans:
            mid = off = dur = 0
            for f, wire, v in _fields(buf, *es):
                if wire != 0:
                    continue
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
            events.append((ts_ns + off / 1000.0, dur / 1000.0, mid))
            used.add(mid)
        lines.append(Line(lname, events))
    meta = {}
    for ms in meta_spans:
        mid, em = 0, None
        for f, _wire, v in _fields(buf, *ms):
            if f == 1:
                mid = v
            elif f == 2 and mid in used:
                em = EventMeta()
                for mf, _mw, mv in _fields(buf, *v):
                    if mf == 2:
                        em.name = _text(buf, mv)
                    elif mf == 4:
                        em.display_name = _text(buf, mv)
                    elif mf == 5:
                        k, val = _stat(buf, mv, stat_names)
                        em.stats[k] = val
        if em is not None:
            meta[mid] = em
    return Plane(name, lines, meta)


def read(path: str, want_line: Callable[[str, str], bool]) -> List[Plane]:
    """The planes of an `.xplane.pb`, with the lines for which
    `want_line(plane name, line name)` holds."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v, want_line)
            for f, wire, v in _fields(buf, 0, len(buf))
            if f == 1 and wire == 2]
