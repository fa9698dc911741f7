"""What both drivers share: the run's context, the earlier lines, the
device claim, the per-layer readers and the result line.

The process that runs a driver holds the chip: it calls the program's
own `main()` on its main thread (the entry point users call) while a
controller thread measures, prints the result line and ends the process
with `os._exit` - the program's loops never return by themselves.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from perfbench import manifest as manifest_lib

REHEARSAL_MARK = '[rehearsal] '


@dataclasses.dataclass
class Ctx:
    args: Any                      # argparse namespace of run.py
    manifest: Dict[str, Any]
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    t_start: float                 # time.time() at process start
    work: str                      # scratch directory of this run
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def rehearse(self) -> bool:
        return bool(self.args.rehearse)

    @property
    def trace(self) -> bool:
        return bool(self.args.trace)

    def say(self, msg: str) -> None:
        """An earlier line: names the device, never a metric's name in
        a rehearsal (every rehearsal line is marked)."""
        d = self.device
        where = (f'[{d["platform"]} {d["kind"]!r} x{d["count"]}] '
                 if d else '')
        mark = REHEARSAL_MARK if self.rehearse else ''
        for line in str(msg).splitlines() or ['']:
            print(f'{mark}perfbench: {where}{line}', flush=True)

    def preset(self, data: Dict[str, Any]) -> Dict[str, Any]:
        """`data` with its `rehearse` overrides applied in a rehearsal
        (the tiny CPU presets sit beside the real sizes, as data)."""
        out = {k: v for k, v in data.items() if k != 'rehearse'}
        if self.rehearse:
            out.update(data.get('rehearse') or {})
        return out


def make_work_dir(args) -> str:
    if args.work_dir:
        shutil.rmtree(args.work_dir, ignore_errors=True)
        os.makedirs(args.work_dir)
        return os.path.abspath(args.work_dir)
    return tempfile.mkdtemp(prefix='perfbench-')     # under TMPDIR


def die(ctx: Optional[Ctx], msg: str, code: int = 1) -> None:
    """End the process without a result line. Used from any thread."""
    mark = REHEARSAL_MARK if ctx is not None and ctx.rehearse else ''
    print(f'{mark}perfbench: FAILED - {msg}', file=sys.stderr, flush=True)
    sys.stdout.flush()
    if ctx is not None and not ctx.args.work_dir:
        shutil.rmtree(ctx.work, ignore_errors=True)
    os._exit(code)


def claim_device(ctx: Ctx) -> Dict[str, Any]:
    """Import JAX in THIS process and hold the chip. Anything but the
    TPU with the cell's number of chips ends the run with no result;
    `--rehearse` is the CPU dress rehearsal and says so on every line."""
    if ctx.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
        os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)
    else:
        # One persistent compile cache at a fixed path inside the
        # checkout (or where the environment already says): the
        # program's compile_cache.configure() then sets none in code.
        from skypilot_tpu.utils import compile_cache
        os.environ.setdefault(compile_cache.ENV_VAR,
                              compile_cache.default_dir())
    import jax
    if ctx.rehearse:
        jax.config.update('jax_platforms', 'cpu')
    elif ctx.trace:
        # A program read from the persistent cache carries the op names
        # of whichever checkout compiled it first (the key leaves the
        # metadata out), and the reducer reads those names. A traced
        # run keys its programs on their metadata as well: its first
        # run in a checkout compiles them anew, under its own names;
        # untraced runs keep the cache they had.
        jax.config.update('jax_compilation_cache_include_metadata_in_key',
                          True)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        die(ctx, f'JAX found no device: {e}')
    ctx.device = {'platform': devices[0].platform,
                  'kind': devices[0].device_kind,
                  'count': len(devices)}
    want = 'cpu' if ctx.rehearse else 'tpu'
    if ctx.device['platform'] != want:
        ctx.device = {}
        die(ctx, f'found platform {devices[0].platform!r} '
                 f'({devices[0].device_kind} x{len(devices)}), not '
                 f'{want!r}: the benchmark measures the chip and has no '
                 f'CPU fallback (--rehearse is the dress rehearsal)')
    if not ctx.rehearse and len(devices) != ctx.cell['chips']:
        die(ctx, f'the cell asks for {ctx.cell["chips"]} chip(s), JAX '
                 f'sees {len(devices)}')
    ctx.say(f'device claimed {time.time() - ctx.t_start:.1f}s after '
            f'process start; compile cache '
            f'{os.environ.get("JAX_COMPILATION_CACHE_DIR")}')
    return ctx.device


def memory_peak_bytes(ctx: Ctx) -> int:
    """Peak bytes on the fullest chip, as the allocator measured them:
    `peak_bytes_in_use` (weights, optimizer state, pools, inputs and
    outputs) plus `peak_bytes_reserved` (the scratch the allocator set
    aside for running programs, which `bytes_in_use` leaves out: 13.15
    GB of the trainer's 14.78, my chip run, PR 24). A TPU whose
    allocator lacks either key ends the run; the CPU reports nothing
    and reads 0."""
    import jax
    stats = [(d.memory_stats() or {}) for d in jax.local_devices()]
    ctx.say(f'memory: allocator of the first chip {json.dumps(stats[0])}')
    if ctx.rehearse:
        return 0
    keys = ('peak_bytes_in_use', 'peak_bytes_reserved')
    if any(k not in s for s in stats for k in keys):
        die(ctx, f'the allocator reports no {" or no ".join(keys)}')
    return max(sum(int(s[k]) for k in keys) for s in stats)


def read_layer_metrics(ctx: Ctx, sources: Dict[str, Any]
                       ) -> Dict[str, Dict[str, Any]]:
    """Each of the cell's per-layer metrics through its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in manifest_lib.per_layer(ctx.manifest, ctx.cell['name']):
        spec = m['spec']
        try:
            value = manifest_lib.reader(spec['reader']).read(
                sources, **(spec.get('args') or {}))
        except Exception as e:  # pylint: disable=broad-except
            ctx.say(f'reader {spec["reader"]} for {m["name"]} failed: '
                    f'{type(e).__name__}: {e}')
            if not ctx.rehearse:
                die(ctx, f'reader {spec["reader"]} failed on the chip')
            value = None
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def holds(entry: Dict[str, Any]) -> bool:
    """Whether a compared number keeps to its limit(s): `at_most`,
    `at_least`. A missing or non-finite number does not."""
    value = entry.get('value')
    if value is None or isinstance(value, bool) or not math.isfinite(value):
        return False
    return (('at_most' not in entry or value <= entry['at_most'])
            and ('at_least' not in entry or value >= entry['at_least']))


def say_trace(ctx: Ctx, summary: Optional[Dict[str, Any]],
              rows: int = 24) -> None:
    """Earlier lines: where the device's time went by program and by
    operation path, and the phases the host plane held."""
    if not summary:
        return
    ctx.say('device self seconds (calls) by program: ' + ', '.join(
        f'{name} {sec:.4f} ({calls:.0f})' for name, (sec, calls)
        in list(summary['by_program'].items())[:rows]))
    for program, path, sec, count in summary['by_path'][:rows]:
        ctx.say(f'by path: {sec:.4f}s {count:.0f} ops {program}: {path}')
    ctx.say(f'idle gaps {summary["idle_gap_count"]}; phase events on the '
            f'host plane {json.dumps(summary["phases_seen"])}; the first '
            f'begins {summary["first_phase_s"]}s after the span\'s first '
            f'operation; listed gaps no phase covers begin at '
            f'{summary["unattributed_at_s"]}s')


def finish(ctx: Ctx, *, compared: Dict[str, Dict[str, Any]],
           attempted: int, failed: int,
           end_to_end: Dict[str, float], sources: Dict[str, Any],
           trace_summary: Optional[Dict[str, Any]]) -> None:
    """Print the result line (the LAST line of stdout) and end the
    process. `--trace 0`: the cell's end-to-end metrics; `--trace 1`:
    its per-layer metrics, with busy_s/window_s and the breakdown.
    `correct` is whether every compared number keeps to its limit;
    the numbers stand beside their limits on the last lines of stderr
    and under the result's last key, `compared`."""
    compared = {name: dict(entry, ok=holds(entry))
                for name, entry in compared.items()}
    correct = bool(compared) and all(e['ok'] for e in compared.values())
    device = dict(ctx.device, memory_peak_bytes=memory_peak_bytes(ctx))
    if ctx.trace:
        metrics = read_layer_metrics(ctx, sources)
        if trace_summary is None:
            die(ctx, 'traced run, but no operation was found on a '
                     'device plane of the trace')
        device['busy_s'] = trace_summary['busy_s']
        device['window_s'] = trace_summary['window_s']
    else:
        units = {m['name']: m['unit'] for m in manifest_lib.end_to_end(
            ctx.manifest, ctx.cell['name'])}
        missing = [n for n in units if end_to_end.get(n) is None]
        if missing:
            die(ctx, f'no value for end-to-end metric(s) {missing}')
        metrics = {n: {'value': float(end_to_end[n]), 'unit': units[n]}
                   for n in units}
    result: Dict[str, Any] = {
        'correct': correct, 'attempted': int(attempted),
        'failed': int(failed), 'metrics': metrics, 'device': device}
    if ctx.trace:
        result['breakdown'] = {
            'device_ops': trace_summary['device_ops'][:10],
            'idle_gaps': trace_summary['idle_gaps'][:10]}
    if ctx.args.rate is not None:
        result['sweep'] = True        # a sweep run is not a measurement
    if ctx.rehearse:
        # No number from a CPU run stands under a metric's name.
        result['metrics'] = {f'rehearsal.{k}': v
                             for k, v in metrics.items()}
    result['compared'] = compared     # the last key of the line
    mark = REHEARSAL_MARK if ctx.rehearse else ''
    line = mark + json.dumps(result)
    if not ctx.args.work_dir:
        shutil.rmtree(ctx.work, ignore_errors=True)
    sys.stdout.flush()
    for name, entry in compared.items():
        limits = ', '.join(f'{k.replace("_", " ")} {entry[k]:g}'
                           for k in ('at_least', 'at_most') if k in entry)
        print(f'{mark}perfbench: compared {name} = {entry["value"]} '
              f'({limits}): {"ok" if entry["ok"] else "NOT MET"}',
              file=sys.stderr)
    print(f'{mark}perfbench: correct = {str(correct).lower()}',
          file=sys.stderr)
    sys.stderr.flush()
    # Past any Tee: the result line is the LAST line, whole and unmarked
    # by anything but the rehearsal's own mark.
    out = getattr(sys.stdout, '_stream', sys.stdout)
    out.write(line + '\n')
    out.flush()
    os._exit(0)


def start_controller(ctx: Ctx, body: Callable[[], None],
                     deadline_s: float) -> threading.Thread:
    """Run `body` on a daemon thread; an exception in it, or no result
    within `deadline_s`, ends the process without a result line."""
    def guarded() -> None:
        try:
            body()
            die(ctx, 'the controller returned without a result')
        except SystemExit:
            raise
        except BaseException as e:  # pylint: disable=broad-except
            import traceback
            traceback.print_exc()
            die(ctx, f'controller: {type(e).__name__}: {e}')

    def watchdog() -> None:
        time.sleep(deadline_s)
        die(ctx, f'no result after {deadline_s:.0f}s')

    threading.Thread(target=watchdog, daemon=True).start()
    t = threading.Thread(target=guarded, daemon=True, name='perfbench')
    t.start()
    return t


class Tee:
    """stdout that also keeps the lines (the program's own `setup:` and
    `profile:` lines are read from it; the process is one, so there is
    no pipe to read them from)."""

    def __init__(self, stream, mark: str = '') -> None:
        self._stream = stream
        self._mark = mark                 # put before every line
        self._lock = threading.Lock()
        self._buf = ''
        self.lines: List[str] = []

    def write(self, s: str) -> int:
        with self._lock:
            self._buf += s
            *done, self._buf = self._buf.split('\n')
            self.lines.extend(done)
            for line in done:
                mark = '' if line.startswith(self._mark) else self._mark
                self._stream.write(f'{mark}{line}\n')
        return len(s)

    def flush(self) -> None:
        self._stream.flush()

    def __getattr__(self, name):
        return getattr(self._stream, name)

    def find(self, prefix: str) -> Optional[str]:
        with self._lock:
            for line in self.lines:
                if line.startswith(prefix):
                    return line
        return None
