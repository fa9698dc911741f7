"""Distributed request tracing for the serving plane.

Dependency-free span layer: every sampled request gets a 16-hex
trace_id that travels LB -> prefill replica -> decode peer over the
`x-skypilot-trace` header, and every interesting stage (route
decision, queue wait, admission, prefill chunks, decode rounds,
device_get stalls, KV handoff export/POST/import, spill/restore)
becomes a complete ('X') Chrome trace event — the exact format
`utils/timeline.py` / `--trace-file` already emits, so a merged
trace loads in chrome://tracing or Perfetto unchanged.

Design constraints, in order:

  1. ZERO overhead when off. `new_ctx()` is one comparison when
     `--trace-sample 0` (the default); `span(name, None)` returns a
     shared no-op singleton — no allocation, no clock reads.
  2. BOUNDED memory. Completed spans land in a per-process LRU of at
     most `MAX_TRACES` traces x `MAX_SPANS_PER_TRACE` spans; an
     unscraped process can run forever.
  3. DETERMINISTIC sampling. The sample decision and the ids both
     come from one seeded `random.Random`, so `--trace-seed` makes a
     run's sampled set (and its ids) reproducible — the property the
     tier-1 determinism test pins.

Wall-clock anchors, monotonic durations: `ts` is `time.time()` (the
only clock comparable across processes — the `stpu trace` merge
sorts on it) while `dur` comes from a `perf_counter` pair, so a span
is never shrunk or stretched by NTP slew.

Header format (`HEADER`): `<trace_id>:<parent_span_id>:<flags>`,
flags bit 0 = sampled. Unsampled requests send no header at all.

Each process tags its spans with a `process` name (`configure`), and
any single span can override it — that is what lets the in-process
stub fleet (LB + N replicas in one interpreter, one shared module)
still produce per-role `pid` rows.

Span discipline: every span must be closed — use `with span(...)`
or put `.end()` in a `finally`. `stpu check` rule SKY007 enforces
this for non-test code.

Loop phases (`phase` / `PhaseClock`) are the second primitive: a
loop thread's OWN work (the engine's scheduler loop, the trainer's
step loop), not a request's. Unsampled and always on: a phase adds
its self time to a per-name accumulator its caller owns (served as
counters through `/stats` and the `--metrics-file` records) and is a
`jax.profiler.TraceAnnotation`, so that under any profiler session it
lies in the host plane of the same trace as the device operations.

The process's garbage collections are the third (`GcPauses`,
installed by the inference server): each pause is an `engine.gc`
annotation on the line of the thread that collected, and is counted
per generation.
"""
from __future__ import annotations

import collections
import gc
import random
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu.utils import timeline

#: The propagation header (lowercase: http.server title-cases on the
#: wire but compares case-insensitively).
HEADER = 'x-skypilot-trace'

#: Bounds on the per-process completed-span store.
MAX_TRACES = 256
MAX_SPANS_PER_TRACE = 512

_lock = threading.Lock()
_sample = 0.0
_rng = random.Random(0)
_process = 'skypilot'
_traces: 'collections.OrderedDict[str, List[dict]]' = \
    collections.OrderedDict()


class Ctx:
    """Propagation context: which trace, and which span is the
    parent of whatever starts next. Immutable by convention."""

    __slots__ = ('trace_id', 'span_id')

    def __init__(self, trace_id: str, span_id: str = '') -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f'Ctx({self.trace_id}:{self.span_id})'


def configure(sample: Optional[float] = None,
              seed: Optional[int] = None,
              process: Optional[str] = None) -> None:
    """Set the sampling rate / rng seed / process tag. Any argument
    left None keeps its current value (so the LB can set `process`
    without touching the replica-configured rate in tests)."""
    global _sample, _rng, _process
    with _lock:
        if sample is not None:
            _sample = max(0.0, min(1.0, float(sample)))
        if seed is not None:
            _rng = random.Random(seed)
        if process is not None:
            _process = str(process)


def enabled() -> bool:
    return _sample > 0.0


def new_ctx() -> Optional[Ctx]:
    """Head-based sampling decision for a request arriving with no
    trace header. Returns None (do nothing, forward nothing) for
    unsampled requests — the common case is one float compare."""
    if _sample <= 0.0:
        return None
    with _lock:
        if _rng.random() >= _sample:
            return None
        return Ctx('%016x' % _rng.getrandbits(64))


def _new_span_id() -> str:
    with _lock:
        return '%08x' % _rng.getrandbits(32)


def parse_header(value: Optional[str]) -> Optional[Ctx]:
    """`<trace_id>:<parent_span_id>:<flags>` -> Ctx, or None for a
    missing/malformed/unsampled header (all equivalent: no tracing)."""
    if not value:
        return None
    parts = value.strip().split(':')
    if len(parts) != 3:
        return None
    trace_id, span_id, flags = parts
    if not trace_id or not flags.isdigit() or not (int(flags) & 1):
        return None
    return Ctx(trace_id, span_id)


def format_header(ctx: Ctx) -> str:
    return f'{ctx.trace_id}:{ctx.span_id}:1'


class Span:
    """A live span. Started on construction; records one Chrome
    trace event on `end()` (idempotent). `ctx` is the context to
    hand to children / the wire."""

    __slots__ = ('name', 'ctx', '_parent', '_proc', '_args',
                 '_wall', '_t0', '_done')

    def __init__(self, name: str, ctx: Ctx,
                 process: Optional[str] = None,
                 **args: Any) -> None:
        self.name = name
        self._parent = ctx.span_id
        self.ctx = Ctx(ctx.trace_id, _new_span_id())
        self._proc = process
        self._args = dict(args)
        self._wall = time.time()
        self._t0 = time.perf_counter()
        self._done = False

    def add(self, **kv: Any) -> None:
        """Attach extra args to the span before it ends."""
        self._args.update(kv)

    def end(self, **kv: Any) -> None:
        if self._done:
            return
        self._done = True
        dur = time.perf_counter() - self._t0
        if kv:
            self._args.update(kv)
        args = {'trace_id': self.ctx.trace_id,
                'span_id': self.ctx.span_id,
                'parent_id': self._parent}
        args.update(self._args)
        event = {
            'name': self.name,
            'cat': 'skypilot_tpu',
            'ph': 'X',
            'ts': self._wall * 1e6,
            'dur': dur * 1e6,
            'pid': self._proc if self._proc is not None else _process,
            'tid': threading.get_ident() % 100000,
            'args': args,
        }
        with _lock:
            spans = _traces.get(self.ctx.trace_id)
            if spans is None:
                while len(_traces) >= MAX_TRACES:
                    _traces.popitem(last=False)
                spans = _traces[self.ctx.trace_id] = []
            if len(spans) < MAX_SPANS_PER_TRACE:
                spans.append(event)

    def __enter__(self) -> 'Span':
        return self

    def __exit__(self, *exc: Any) -> None:
        if exc and exc[0] is not None:
            self._args.setdefault('error', str(exc[0].__name__))
        self.end()


class _NoopSpan:
    """Shared do-nothing span for unsampled requests. `ctx` is None
    so children short-circuit the same way."""

    __slots__ = ()
    ctx: Optional[Ctx] = None
    name = ''

    def add(self, **kv: Any) -> None:
        pass

    def end(self, **kv: Any) -> None:
        pass

    def __enter__(self) -> '_NoopSpan':
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


NOOP = _NoopSpan()


def span(name: str, ctx: Optional[Ctx],
         process: Optional[str] = None, **args: Any):
    """Open a span under `ctx`. With `ctx=None` (unsampled) this is
    free: the shared no-op singleton comes back. Close it — context
    manager or `finally` — or SKY007 will flag the call site."""
    if ctx is None:
        return NOOP
    return Span(name, ctx, process=process, **args)


def start_span(name: str, ctx: Optional[Ctx],
               process: Optional[str] = None, **args: Any):
    """Manual-lifetime variant of `span` for spans that cross
    function boundaries (queue wait, decode-round occupancy). The
    caller owns `.end()` — put it in a `finally` (SKY007)."""
    if ctx is None:
        return NOOP
    return Span(name, ctx, process=process, **args)


def record_span(name: str, ctx: Optional[Ctx], dur_s: float,
                start: Optional[float] = None,
                process: Optional[str] = None, **args: Any) -> None:
    """Record an interval the caller already measured (a perf_counter
    pair around existing code) as one completed span. This is how the
    engine scheduler traces without restructuring its hot loop: no
    open span object lives across scheduler iterations, so there is
    nothing for SKY007 to leak. `start` is the wall-clock begin
    (time.time()); default anchors the span so it ENDS now."""
    if ctx is None:
        return
    sp = Span(name, ctx, process=process, **args)
    sp._wall = start if start is not None else time.time() - dur_s
    sp._t0 = time.perf_counter() - dur_s
    sp.end()


class PhaseClock:
    """Per-name accumulator of one loop thread's phases:
    `totals[name] = [n, self seconds, inclusive seconds]`. Single
    writer (the loop thread); other threads read racily, like the
    engine's other counters. Phases nest, and a phase's seconds are
    SELF time (its children's durations are taken off), so the names
    under one root partition the root's inclusive time."""

    __slots__ = ('totals', '_open')

    def __init__(self) -> None:
        self.totals: Dict[str, List[float]] = {}
        self._open: List['_Phase'] = []

    def n(self, name: str) -> int:
        return int(self.totals.get(name, _ZERO)[0])

    def seconds(self, name: str) -> float:
        """Accumulated self time of `name`."""
        return self.totals.get(name, _ZERO)[1]

    def inclusive(self, name: str) -> float:
        """Accumulated whole durations of `name`, children included."""
        return self.totals.get(name, _ZERO)[2]

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """{name: {'n', 's'}}, self seconds (racy read: at worst one
        phase apart)."""
        return {name: {'n': int(rec[0]), 's': round(rec[1], 6)}
                for name, rec in sorted(self.totals.items())}


_ZERO = (0, 0.0, 0.0)
_annotation = None      # jax.profiler.TraceAnnotation, imported late


def _trace_annotation():
    global _annotation
    if _annotation is None:
        # Late: the LB and the CLI import this module and stay off
        # JAX; only a loop that opens a phase needs it.
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _Phase:
    """One open phase; `dur` holds its whole duration after exit."""

    __slots__ = ('name', 'dur', '_clock', '_ann', '_t0', '_child')

    def __init__(self, name: str, clock: PhaseClock) -> None:
        self.name = name
        self.dur = 0.0
        self._clock = clock
        self._child = 0.0

    def __enter__(self) -> '_Phase':
        self._ann = (_annotation or _trace_annotation())(self.name)
        self._ann.__enter__()
        self._clock._open.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dur = self.dur = time.perf_counter() - self._t0
        clock = self._clock
        clock._open.pop()
        if clock._open:
            clock._open[-1]._child += dur
        rec = clock.totals.get(self.name)
        if rec is None:
            rec = clock.totals[self.name] = [0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += dur - self._child
        rec[2] += dur
        self._ann.__exit__(*exc)
        if timeline.enabled():
            timeline.record(self.name, self._t0, dur)


def phase(name: str, clock: PhaseClock) -> _Phase:
    """Open a phase of a loop thread's own work, as a context manager
    (SKY007 flags a bare call). On exit its self time and 1 go to
    `clock`; for its duration it is a `TraceAnnotation`, which costs
    nothing to speak of while no profiler session runs; with
    `utils/timeline` enabled it is a Chrome event there too. No
    sampling, no store."""
    return _Phase(name, clock)


#: The annotation a garbage collection's pause takes.
GC_PHASE = 'engine.gc'


class GcPauses:
    """The process's garbage collections, as a `gc.callbacks` hook:
    each pause is a `GC_PHASE` annotation on the line of the thread
    that collected (which holds the GIL throughout, so every other
    Python thread stands still too) and is added to `clock` under its
    generation (`gen0`, `gen1`, `gen2`), with the longest pause in
    `max_s`. Collections never overlap, so the clock has one writer at
    a time whichever thread collects. Measures only: how often the
    collector runs is left as it is."""

    def __init__(self) -> None:
        self.clock = PhaseClock()
        self.max_s: Dict[str, float] = {}
        self._ann: Any = None
        self._t0 = 0.0

    def __call__(self, stage: str, info: Dict[str, Any]) -> None:
        if stage == 'start':
            self._ann = (_annotation or _trace_annotation())(GC_PHASE)
            self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        ann, self._ann = self._ann, None
        if ann is None:
            return      # hooked in between a collection's two calls
        dur = time.perf_counter() - self._t0
        ann.__exit__(None, None, None)
        name = f'gen{info["generation"]}'
        rec = self.clock.totals.get(name)
        if rec is None:
            rec = self.clock.totals[name] = [0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += dur
        rec[2] += dur
        if dur > self.max_s.get(name, 0.0):
            self.max_s[name] = dur

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """{'gen0'|'gen1'|'gen2': {'n', 's', 'max_s'}}, all three."""
        return {name: {'n': self.clock.n(name),
                       's': round(self.clock.seconds(name), 6),
                       'max_s': round(self.max_s.get(name, 0.0), 6)}
                for name in ('gen0', 'gen1', 'gen2')}


#: This process's one hook (`install_gc_pauses`).
gc_pauses = GcPauses()


def install_gc_pauses() -> None:
    """Hook `gc_pauses` into the collector, once however often it is
    called (every server of the process calls it)."""
    _trace_annotation()
    if gc_pauses not in gc.callbacks:
        gc.callbacks.append(gc_pauses)


def remove_gc_pauses() -> None:
    """Unhook it (a server's shutdown); its counts stay."""
    while gc_pauses in gc.callbacks:
        gc.callbacks.remove(gc_pauses)


def get_trace(trace_id: str) -> Optional[Dict[str, Any]]:
    """Completed spans of one trace as a Chrome-trace JSON body, or
    None if this process recorded nothing for it."""
    with _lock:
        spans = _traces.get(trace_id)
        if spans is None:
            return None
        return {'traceEvents': list(spans)}


def trace_ids() -> List[str]:
    """Known trace ids, oldest first (bounded by MAX_TRACES)."""
    with _lock:
        return list(_traces)


def reset() -> None:
    """Test hook: drop all stored traces and disable sampling."""
    global _sample, _rng, _process
    with _lock:
        _traces.clear()
        _sample = 0.0
        _rng = random.Random(0)
        _process = 'skypilot'


def merge_traces(bodies: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Stitch per-process `get_trace` bodies into one timeline:
    de-duplicate on span_id (an in-process fleet shares one store, so
    every node returns every span), then sort by wall-clock `ts`.
    Used by `stpu trace` and by anything replaying saved dumps."""
    seen = set()
    merged: List[dict] = []
    for body in bodies:
        for ev in (body or {}).get('traceEvents', []):
            key = (ev.get('args', {}).get('span_id'),
                   ev.get('name'), ev.get('ts'))
            if key in seen:
                continue
            seen.add(key)
            merged.append(ev)
    merged.sort(key=lambda e: e.get('ts', 0))
    return {'traceEvents': merged}
