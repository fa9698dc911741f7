"""A profiler trace file (`.xplane.pb`) as plain Python values.

`jax.profiler.ProfileData` gives an event its name, start, duration and
own stats, but not the stats of its METADATA, and that is where the
profiler keeps an operation's path (`jit(decode)/Llama/layer_3/attn/
kv_write/dynamic_update_slice`). So this reads the protocol-buffer wire
format itself, the few fields of XSpace that the reducer needs (field
numbers from tsl/profiler/protobuf/xplane.proto), with nothing but the
standard library; a line whose name the caller does not want is skipped
without being decoded.

    XSpace.planes = 1
    XPlane: name 2, lines 3, event_metadata 4 (map), stat_metadata 5 (map)
    XLine: name 2, timestamp_ns 3, events 4, display_name 11
    XEvent: metadata_id 1, offset_ps 2, duration_ps 3, stats 4
    XStat: metadata_id 1, double 2, uint64 3, int64 4, str 5, bytes 6, ref 7
    XEventMetadata: id 1, name 2, metadata 3, display_name 4, stats 5
    XStatMetadata: id 1, name 2
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple


def _varint(buf, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos: int, end: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: a varint's
    value, a fixed field's bytes, or (start, end) of a length-delimited
    field inside `buf`."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value, pos = bytes(buf[pos:pos + 8]), pos + 8
        elif wire == 5:
            value, pos = bytes(buf[pos:pos + 4]), pos + 4
        else:
            raise ValueError(f'wire type {wire} at byte {pos}')
        yield number, wire, value


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode('utf-8', errors='replace')


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    name, value = '?', None
    for number, wire, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack('<d', v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


class Event:
    """One event: `meta` holds its metadata's stats (one dict shared by
    every event of that metadata), `stats` those with the event's own
    over them, decoded when first asked for."""
    __slots__ = ('name', 'start_s', 'duration_s', 'meta', '_own',
                 '_decode')

    def __init__(self, name: str, start_s: float, duration_s: float,
                 meta: Dict[str, Any], own: Optional[List[Tuple[int, int]]],
                 decode: Callable[[List[Tuple[int, int]]], Dict[str, Any]]
                 ) -> None:
        self.name, self.start_s, self.duration_s = name, start_s, duration_s
        # `own`: where the event's own stats lie in the file; `decode`,
        # one function a plane, reads them.
        self.meta, self._own, self._decode = meta, own, decode

    @property
    def stats(self) -> Dict[str, Any]:
        if not self._own:
            return self.meta
        return dict(self.meta, **self._decode(self._own))


@dataclasses.dataclass
class Line:
    name: str
    n_events: int
    events: List[Event]        # empty when the line was not wanted


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _map_entry(buf, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf, span, want_line: Callable[[str, str], bool]) -> Plane:
    name, line_spans, meta_spans, stat_spans = '', [], [], []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            line_spans.append(v)
        elif number == 4:
            meta_spans.append(v)
        elif number == 5:
            stat_spans.append(v)
    stat_names: Dict[int, str] = {}
    for entry in stat_spans:
        key, value = _map_entry(buf, entry)
        if value is not None:
            for number, _, v in _fields(buf, *value):
                if number == 2:
                    stat_names[key] = _text(buf, v)
    #: metadata id -> [name, its stats' spans]; the stats are decoded
    #: only for a metadata some wanted event names.
    metadata: Dict[int, List[Any]] = {}
    for entry in meta_spans:
        key, value = _map_entry(buf, entry)
        if value is None:
            continue
        meta_name, stats = '', []
        for number, _, v in _fields(buf, *value):
            if number == 2:
                meta_name = _text(buf, v)
            elif number == 5:
                stats.append(v)
        metadata[key] = [meta_name, stats]
    decoded: Dict[int, Dict[str, Any]] = {}

    def decode(spans) -> Dict[str, Any]:
        return dict(_stat(buf, s, stat_names) for s in spans)

    def meta_stats(meta_id: int) -> Dict[str, Any]:
        if meta_id not in decoded:
            decoded[meta_id] = decode(metadata.get(meta_id, ['', []])[1])
        return decoded[meta_id]

    lines = []
    for line_span in line_spans:
        line_name, display, t0_ns, event_spans = '', '', 0, []
        for number, _, v in _fields(buf, *line_span):
            if number == 2:
                line_name = _text(buf, v)
            elif number == 11:
                display = _text(buf, v)
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                event_spans.append(v)
        line_name = line_name or display
        events: List[Event] = []
        if want_line(name, line_name):
            for ev_span in event_spans:
                meta_id = offset_ps = duration_ps = 0
                own = None
                for number, _, v in _fields(buf, *ev_span):
                    if number == 1:
                        meta_id = v
                    elif number == 2:
                        offset_ps = v
                    elif number == 3:
                        duration_ps = v
                    elif number == 4:
                        own = own or []
                        own.append(v)
                events.append(Event(
                    metadata.get(meta_id, ['?'])[0],
                    t0_ns * 1e-9 + offset_ps * 1e-12,
                    duration_ps * 1e-12, meta_stats(meta_id), own,
                    decode))
        lines.append(Line(line_name, len(event_spans), events))
    return Plane(name, lines)


def read(path: str, want_line: Callable[[str, str], bool] = lambda p, l: True
         ) -> List[Plane]:
    """Every plane of the file; `want_line(plane name, line name)` says
    which lines' events to decode (the others keep their count)."""
    with open(path, 'rb') as f:
        buf = memoryview(f.read())
    return [_plane(buf, v, want_line)
            for number, wire, v in _fields(buf, 0, len(buf))
            if number == 1 and wire == 2]
