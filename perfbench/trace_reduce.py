"""From a profiler trace to device busy time, idle gaps and top ops.

The arithmetic works on plain intervals so that it can be tested on
hand-written ones; `read_xplane` is the only part that knows the
profiler's file (an `.xplane.pb` read with `jax.profiler.ProfileData`,
which needs JAX but no device).

Busy is the union of the intervals in which an operation ran on the
device; the traced span of a device is from its first operation's
start to its last operation's end (the device plane carries no mark of
where the host started and stopped the profiler); idle share is
1 - busy / span. With several devices, busy and span are averaged.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]            # (start, end), seconds
Event = Tuple[str, float, float]          # (name, start, duration)

#: Planes that are devices, and the lines on them that hold operations.
#: (The other lines of a TPU plane - "Steps", "XLA Modules", "XLA
#: TraceMe", "Framework Ops" - repeat the same time at another grain
#: and would hide every gap inside a step.)
DEVICE_PLANE_PREFIX = '/device:TPU:'
OP_LINE = 'XLA Ops'
MODULE_LINE = 'XLA Modules'

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


def self_times(events: Iterable[Event]) -> Dict[str, float]:
    """Summed SELF time per name on one line: an event's duration
    minus what the events nested inside it cover (a `while` holds its
    body's operations; counting both would count the time twice)."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []           # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        end = start + dur
        if stack:
            # Nested: the parent loses the child's (clipped) duration.
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur])
    close(float('inf'))
    return out


def reduce_events(per_device: Dict[str, List[Event]],
                  top: int = 10, longest: int = 5) -> Optional[Dict[str, Any]]:
    """Busy/span/idle and the breakdown from each device's operation
    events. None when no operation ran on any device."""
    per_device = {d: ev for d, ev in per_device.items() if ev}
    if not per_device:
        return None
    busy, span = [], []
    ops: Dict[str, float] = {}
    idle: List[Tuple[float, str]] = []
    for events in per_device.values():
        merged = union((s, s + d) for _, s, d in events)
        busy.append(sum(b - a for a, b in merged))
        span.append(merged[-1][1] - merged[0][0])
        for name, sec in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + sec / len(per_device)
        ends = sorted((s + d, name) for name, s, d in events)
        starts = sorted((s, name) for name, s, d in events)
        end_times = [e[0] for e in ends]
        start_times = [e[0] for e in starts]
        for a, b in gaps_of_merged(merged):
            # The programs that ran into and out of the gap.
            i = bisect.bisect_right(end_times, a + 1e-12) - 1
            j = bisect.bisect_left(start_times, b - 1e-12)
            before = module_of(ends[i][1]) if i >= 0 else '?'
            after = (module_of(starts[j][1]) if j < len(starts) else '?')
            idle.append((b - a, f'{before} -> {after}'))
    n = len(per_device)
    busy_s, window_s = sum(busy) / n, sum(span) / n
    idle.sort(reverse=True)
    return {
        'devices': n,
        'busy_s': busy_s,
        'window_s': window_s,
        'idle_pct': 100.0 * (1.0 - busy_s / window_s) if window_s else None,
        'device_ops': [[name, sec] for name, sec in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        'idle_gaps': [[f'{HOST_UNATTRIBUTED} ({name})', sec]
                      for sec, name in idle[:longest]],
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    return paths[-1] if paths else None


def read_xplane(path: str, plane_prefix: str = DEVICE_PLANE_PREFIX,
                op_line: str = OP_LINE
                ) -> Tuple[Dict[str, List[Event]], List[str]]:
    """(operation events per device plane, a description of every plane
    and line in the file for an earlier line of the run's output).
    Planes and lines are chosen by the start of their names; an event
    is named `<jitted program>: <operation>`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_device: Dict[str, List[Event]] = {}
    seen: List[str] = []
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append(f'{plane.name}: ' + ', '.join(
            f'{ln.name}({sum(1 for _ in ln.events)})' for ln in lines))
        if not plane.name.startswith(plane_prefix):
            continue
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             short_module(ev.name))
            for ln in lines if ln.name == MODULE_LINE for ev in ln.events)
        module_starts = [m[0] for m in modules]
        events = per_device.setdefault(plane.name, [])
        for ln in lines:
            if not ln.name.startswith(op_line):
                continue
            for ev in ln.events:
                if ev.duration_ns <= 0:
                    continue
                # The jitted program the operation ran in, by time.
                i = bisect.bisect_right(module_starts, ev.start_ns) - 1
                inside = i >= 0 and ev.start_ns < modules[i][1]
                module = modules[i][2] if inside else '?'
                events.append((f'{module}: {short_op(ev.name)}',
                               ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return per_device, seen


def reduce_trace_dir(trace_dir: str, **kw) -> Tuple[Optional[Dict[str, Any]], List[str]]:
    path = find_xplane(trace_dir)
    if path is None:
        return None, [f'no .xplane.pb under {trace_dir}']
    per_device, seen = read_xplane(path, **kw)
    return reduce_events(per_device), seen
