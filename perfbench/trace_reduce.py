"""From a profiler trace to device busy time, idle gaps and where the
device's time went: by operation, by jitted program and by operation
path (the `jax.named_scope` names).

The arithmetic works on plain intervals so that it can be tested on
hand-written ones; `read_xplane` is the only part that knows the
profiler's file (an `.xplane.pb`, decoded by `perfbench/xplane.py`
with the standard library alone, since the operation path is a stat of
an event's METADATA, which `jax.profiler.ProfileData` does not show).

Busy is the union of the intervals in which an operation ran on the
device; the traced span of a device is from its first operation's
start to its last operation's end (the device plane carries no mark of
where the host started and stopped the profiler); idle share is
1 - busy / span. With several devices, busy and span are averaged.

An idle gap is named by the program's own phase event (`engine.*`,
`train.*`: `observability/tracing.phase`, on `/host:CPU`) that covers
most of it, innermost phase first. The host's and the device's lines
are on the profiler's clocks, which the recorded v5e trace shows about
a millisecond apart (the device's operation starts 1.15 ms BEFORE the
host call that launched it), so a gap shorter than that can be named
by its neighbour's phase.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import xplane

Interval = Tuple[float, float]            # (start, end), seconds
#: (name, start, duration) and, from a trace file, the operation's path
#: as a fourth item.
Event = Tuple[Any, ...]

#: Planes that are devices, and the lines on them that hold operations.
#: (The other lines of a TPU plane - "Steps", "XLA Modules", "XLA
#: TraceMe", "Framework Ops" - repeat the same time at another grain
#: and would hide every gap inside a step.)
DEVICE_PLANE_PREFIX = '/device:TPU:'
OP_LINE = 'XLA Ops'
MODULE_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'
#: The program's phase events (observability/tracing.phase).
PHASE_PREFIXES = ('engine.', 'train.')
#: The stat of an operation's metadata that holds its path.
PATH_STAT = 'tf_op'
_PATH_RE = re.compile(r'^\w*jit\(')
NO_PATH = '(no path)'

HOST_UNATTRIBUTED = 'host: unattributed'


def short_op(name: str, limit: int = 100) -> str:
    """The trace names an operation by its whole HLO text; keep its
    start, without the layouts."""
    name = re.sub(r'\{[^{}]*\}', '', name)
    return name if len(name) <= limit else name[:limit - 3] + '...'


def short_module(name: str) -> str:
    """`jit_step(123456)` -> `jit_step`."""
    return re.sub(r'\(\d+\)$', '', name)


def module_of(name: str) -> str:
    """The module part of an event name made by `read_xplane`."""
    return name.split(': ', 1)[0] if ': ' in name else '?'


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals (touching ones merge)."""
    merged: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals: Iterable[Interval]) -> List[Interval]:
    """Idle intervals between the merged busy ones."""
    return gaps_of_merged(union(intervals))


def gaps_of_merged(merged: List[Interval]) -> List[Interval]:
    return [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)]


def self_seconds(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, its SELF seconds) for every event of one line: an
    event's duration minus what the events nested inside it cover (a
    `while` holds its body's operations; counting both would count the
    time twice)."""
    out: List[Tuple[Event, float]] = []
    stack: List[List[Any]] = []           # [event, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            ev, _, own = stack.pop()
            out.append((ev, max(own, 0.0)))

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        start, dur = ev[1], ev[2]
        close(start)
        end = start + dur
        if stack:
            # Nested: the parent loses the child's (clipped) duration.
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([ev, end, dur])
    close(float('inf'))
    return out


def self_times(events: Iterable[Event]) -> Dict[str, float]:
    """Summed self time per name on one line."""
    out: Dict[str, float] = {}
    for ev, own in self_seconds(list(events)):
        out[ev[0]] = out.get(ev[0], 0.0) + own
    return out


def collapse_path(path: str) -> str:
    """`.../layer_12/attn/...` -> `.../layer_N/attn/...`: the layers of
    one model are one row."""
    return re.sub(r'\blayer_\d+\b', 'layer_N', path)


def innermost_segments(events: Iterable[Event]
                       ) -> List[Tuple[float, float, str]]:
    """One thread's nested events as non-overlapping (start, end, name)
    pieces, each named by the innermost event open in it."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []   # (name, end)
    cursor = float('-inf')

    def emit(upto: float) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            segs.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for name, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        end = start + dur
        stack.append((name, min(end, stack[-1][1]) if stack else end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def name_gap(gap: Interval, segments: List[Tuple[float, float, str]],
             starts: List[float]) -> Optional[str]:
    """The phase whose pieces cover most of the gap; None where none
    covers any of it. `segments` sorted by start, `starts` their
    starts."""
    a, b = gap
    cover: Dict[str, float] = {}
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segments) and segments[i][0] < b:
        lo, hi, name = segments[i]
        overlap = min(hi, b) - max(lo, a)
        if overlap > 0:
            cover[name] = cover.get(name, 0.0) + overlap
        i += 1
    return max(cover, key=cover.get) if cover else None


def reduce_events(per_device: Dict[str, List[Event]],
                  top: int = 10, longest: int = 10,
                  modules: Optional[Dict[str, List[Event]]] = None,
                  phases: Optional[Dict[str, List[Event]]] = None
                  ) -> Optional[Dict[str, Any]]:
    """Busy/span/idle and the breakdown from each device's operation
    events. None when no operation ran on any device.

    `modules`: each device's jitted-program events (for the calls a
    program made); `phases`: the program's phase events by host thread.
    `by_program` is {program: [device self seconds, calls]} and
    `by_path` rows of [program, path, device self seconds, events],
    both over the whole span and averaged over the devices."""
    per_device = {d: ev for d, ev in per_device.items() if ev}
    if not per_device:
        return None
    n = len(per_device)
    segments = sorted(seg for events in (phases or {}).values()
                      for seg in innermost_segments(events))
    seg_starts = [seg[0] for seg in segments]
    busy, span = [], []
    ops: Dict[str, float] = {}
    by_program: Dict[str, List[float]] = {}
    by_path: Dict[Tuple[str, str], List[float]] = {}
    idle: List[Tuple[float, str]] = []
    first, last = float('inf'), float('-inf')
    for device, events in per_device.items():
        merged = union((e[1], e[1] + e[2]) for e in events)
        busy.append(sum(b - a for a, b in merged))
        span.append(merged[-1][1] - merged[0][0])
        first, last = min(first, merged[0][0]), max(last, merged[-1][1])
        for ev, own in self_seconds(events):
            name = ev[0]
            ops[name] = ops.get(name, 0.0) + own / n
            program = module_of(name)
            by_program.setdefault(program, [0.0, 0.0])[0] += own / n
            path = collapse_path(ev[3]) if len(ev) > 3 and ev[3] else NO_PATH
            row = by_path.setdefault((program, path), [0.0, 0.0])
            row[0] += own / n
            row[1] += 1.0 / n
        for name, start, dur, *_ in (modules or {}).get(device, ()):
            if start <= merged[-1][1] and start + dur >= merged[0][0]:
                by_program.setdefault(name, [0.0, 0.0])[1] += 1.0 / n
        ends = sorted((e[1] + e[2], e[0]) for e in events)
        starts = sorted((e[1], e[0]) for e in events)
        end_times = [e[0] for e in ends]
        start_times = [e[0] for e in starts]
        for a, b in gaps_of_merged(merged):
            # The programs that ran into and out of the gap.
            i = bisect.bisect_right(end_times, a + 1e-12) - 1
            j = bisect.bisect_left(start_times, b - 1e-12)
            before = module_of(ends[i][1]) if i >= 0 else '?'
            after = (module_of(starts[j][1]) if j < len(starts) else '?')
            idle.append((b - a, a, f'{before} -> {after}'))
    busy_s, window_s = sum(busy) / n, sum(span) / n
    idle.sort(reverse=True)
    named, unnamed_at = [], []
    for sec, a, programs in idle[:longest]:
        phase = name_gap((a, a + sec), segments, seg_starts)
        named.append([f'{phase or HOST_UNATTRIBUTED} ({programs})', sec])
        if phase is None:
            unnamed_at.append(a - first)
    return {
        'devices': n,
        'busy_s': busy_s,
        'window_s': window_s,
        'first_op_s': first,
        'last_op_s': last,
        'idle_pct': 100.0 * (1.0 - busy_s / window_s) if window_s else None,
        'device_ops': [[name, sec] for name, sec in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        'idle_gaps': named,
        'idle_gap_count': len(idle),
        #: Where in the span (seconds after its first operation) the
        #: listed gaps that no phase covers begin: a phase that began
        #: before the profiler did leaves no event.
        'unattributed_at_s': unnamed_at,
        'first_phase_s': (segments[0][0] - first) if segments else None,
        'by_program': {k: v for k, v in sorted(
            by_program.items(), key=lambda kv: -kv[1][0])},
        'by_path': [[program, path, sec, count]
                    for (program, path), (sec, count) in sorted(
                        by_path.items(), key=lambda kv: -kv[1][0])],
        'phases_seen': dict(sorted(collections.Counter(
            e[0] for evs in (phases or {}).values() for e in evs).items())),
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return paths[-1] if paths else None


def op_path(stats: Dict[str, Any]) -> str:
    """The operation's path from its metadata's stats: the profiler's
    op-name stat, else any text stat that reads like a JAX op name."""
    value = stats.get(PATH_STAT)
    if not (isinstance(value, str) and value):
        value = next((v for v in stats.values()
                      if isinstance(v, str) and _PATH_RE.match(v)), '')
    return value.rstrip(':')       # the stat reads `<path>:<type>`


def read_xplane(path: str, plane_prefix: str = DEVICE_PLANE_PREFIX,
                op_line: str = OP_LINE
                ) -> Tuple[Dict[str, List[Event]], List[str],
                           Dict[str, Dict[str, List[Event]]]]:
    """(operation events per device plane; a description of every plane
    and line in the file for an earlier line of the run's output;
    `modules`, the jitted-program events per device plane, and
    `phases`, the program's phase events per host thread).
    Planes and lines are chosen by the start of their names; an
    operation is (`<jitted program>: <operation>`, start, duration,
    path)."""
    planes = xplane.read(path, lambda plane, line: plane.startswith(
        (plane_prefix, HOST_PLANE)))
    per_device: Dict[str, List[Event]] = {}
    modules_of: Dict[str, List[Event]] = {}
    phases: Dict[str, List[Event]] = {}
    seen: List[str] = []
    for plane in planes:
        seen.append(f'{plane.name}: ' + ', '.join(
            f'{ln.name}({ln.n_events})' for ln in plane.lines))
        if plane.name.startswith(HOST_PLANE):
            for i, ln in enumerate(plane.lines):
                found = [(ev.name, ev.start_s, ev.duration_s)
                         for ev in ln.events
                         if ev.name.startswith(PHASE_PREFIXES)]
                if found:
                    phases[f'{ln.name}#{i}'] = found
        if not plane.name.startswith(plane_prefix):
            continue
        modules = sorted(
            (ev.start_s, ev.start_s + ev.duration_s, short_module(ev.name))
            for ln in plane.lines if ln.name == MODULE_LINE
            for ev in ln.events)
        modules_of[plane.name] = [(m[2], m[0], m[1] - m[0])
                                  for m in modules]
        module_starts = [m[0] for m in modules]
        events = per_device.setdefault(plane.name, [])
        for ln in plane.lines:
            if not ln.name.startswith(op_line):
                continue
            for ev in ln.events:
                if ev.duration_s <= 0:
                    continue
                # The jitted program the operation ran in, by time.
                i = bisect.bisect_right(module_starts, ev.start_s) - 1
                inside = i >= 0 and ev.start_s < modules[i][1]
                module = modules[i][2] if inside else '?'
                events.append((f'{module}: {short_op(ev.name)}',
                               ev.start_s, ev.duration_s,
                               op_path(ev.meta)))
    return per_device, seen, {'modules': modules_of, 'phases': phases}


def reduce_trace_dir(trace_dir: str, **kw
                     ) -> Tuple[Optional[Dict[str, Any]], List[str],
                                Optional[str]]:
    """(the summary, the planes' description, the trace file's path)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None, [f'no .xplane.pb under {trace_dir}'], None
    per_device, seen, extra = read_xplane(path, **kw)
    return reduce_events(per_device, **extra), seen, path
