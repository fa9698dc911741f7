"""Driver `serve_open_loop`: the serving engine under open-loop load.

This process claims the chip and IS the server: it installs the
configuration shim and a compile listener, then calls
`skypilot_tpu.recipes.serve_lm.main()` on its main thread with the
flags the configuration file gives (`serve()` installs its SIGTERM
handler, which only the main thread may; and `serve_lm` builds its
parser inside `main()`, so this is the one way to its defaults and
refusals). A controller thread waits for `/readyz`, runs the load
generator (a child that never imports JAX) for the warm-up and for the
window, reads `/stats` at the window's two ends, starts and stops the
profiler for `--trace 1`, scores some finished rows against the
configuration's plain float32 reference, prints the result line and ends
the process. The traced span is the window's last seconds; the closing
`/stats` is read at the close and the profiler stopped after it.
"""
from __future__ import annotations

import json
import math
import os
import random
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import accounting
from perfbench import harness
from perfbench import manifest as manifest_lib
from perfbench import schedule
from perfbench import shim
from perfbench import trace_reduce

#: How far below the position's best log-probability (under the plain
#: float32 reference) the engine's greedy token may score, in nats,
#: where the configuration file states no `score_margin_nats` of its
#: own, and why. A configuration states its own with
#: `score_margin_why` (routed experts, say: a near-tie in the router
#: flips an expert between bf16 and float32); one without its why is
#: refused.
DEFAULT_MARGIN_NATS = 0.5
DEFAULT_MARGIN_WHY = (
    'Llama blocks of 8-32 layers computed in bf16 (paged kernel, chunked '
    'prefill, a batch of slots) differ from the float32 reference by '
    'rounding noise that grows with depth and leaves a near-tie\'s argmax '
    'free to flip: the largest shortfall read on the chip is 0.057 nats '
    'over 8 layers, 0.17 over 32 and 0.225 over 16 '
    '(PERF.md, PR 21 and 24); with seeded random weights the best logit '
    'stands about 5 nats above a typical token, which is where a kernel '
    'that drops a page, a mask or the softmax scale lands')


def score_margin(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The scoring margin the configuration states, or the default."""
    if 'score_margin_nats' not in cfg:
        return {'nats': DEFAULT_MARGIN_NATS, 'why': DEFAULT_MARGIN_WHY,
                'from': 'the driver\'s default'}
    why = str(cfg.get('score_margin_why') or '').strip()
    nats = cfg['score_margin_nats']
    if not why:
        raise manifest_lib.ManifestError(
            'the configuration states score_margin_nats without '
            'score_margin_why: a tolerance comes with its reason')
    if isinstance(nats, bool) or not isinstance(nats, (int, float)) \
            or not 0 < nats < 10:
        raise manifest_lib.ManifestError(
            f'score_margin_nats {nats!r} is not a number of nats')
    return {'nats': float(nats), 'why': why,
            'from': 'the configuration file'}


COMPILE_EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'
#: Limits, in seconds: the server's start to /readyz, one warm-up wave
#: (a cold cache compiles some 30 programs in it), and the whole run,
#: inside the 1200 s the contract gives a run that compiles.
READY_LIMIT_S = 900.0
WARMUP_LIMIT_S = 900.0
DEADLINE_S = 1150.0
LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'loadgen.py')


class CompileLog:
    """Wall-clock times of JAX compile events in this process. The
    event fires when a new shape is lowered, whether or not the
    persistent cache then has the program."""

    def __init__(self) -> None:
        self.times: List[float] = []

    def __call__(self, event: str, duration: float, **kw) -> None:
        del duration, kw
        if event == COMPILE_EVENT:
            self.times.append(time.time())

    def register(self) -> 'CompileLog':
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in list(self.times) if lo <= t < hi)


def keep_runtime() -> List[Any]:
    """Wrap `inference.runtime.build_runtime` (which `serve_lm.main()`
    imports when it is called) so that the runtime it builds is kept:
    the reference scores the engine on the weights the server holds."""
    from skypilot_tpu.inference import runtime
    built: List[Any] = []
    original = runtime.build_runtime

    def build_runtime(args):
        built.append(original(args))
        return built[-1]

    runtime.build_runtime = build_runtime
    return built


def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def get_json(base: str, path: str, body: Optional[dict] = None,
             timeout: float = 600.0) -> Any:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        base + path, data=data,
        headers={'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def wait_ready(ctx: harness.Ctx, base: str, limit_s: float) -> float:
    """Seconds from process start to the first /readyz 200."""
    end = time.time() + limit_s
    while time.time() < end:
        try:
            if get_json(base, '/readyz', timeout=5).get('ready'):
                return time.time() - ctx.t_start
        except (OSError, ValueError, urllib.error.HTTPError):
            pass
        time.sleep(0.2)
    harness.die(ctx, f'the server was not ready within {limit_s:.0f}s')
    return 0.0


def run_loadgen(ctx: harness.Ctx, tag: str, base: str, open_at: float,
                requests: List[Dict[str, Any]], send_until: float,
                drain_s: float) -> subprocess.Popen:
    plan = os.path.join(ctx.work, f'{tag}.plan.json')
    with open(plan, 'w', encoding='utf-8') as f:
        json.dump({'base': base, 'open_at': open_at,
                   'send_until': send_until, 'drain_s': drain_s,
                   'requests': requests}, f)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(('JAX_', 'XLA_', 'TPU_', 'LIBTPU'))}
    return subprocess.Popen(
        [sys.executable, LOADGEN, '--plan', plan,
         '--out', os.path.join(ctx.work, f'{tag}.records.jsonl')],
        env=env, stdout=sys.stderr, stderr=sys.stderr)


def reap(ctx: harness.Ctx, tag: str, proc: subprocess.Popen,
         timeout: float) -> List[Dict[str, Any]]:
    """Wait for the load generator to end (kill it if it does not) and
    read its records."""
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        harness.die(ctx, f'the load generator ({tag}) did not end within '
                         f'{timeout:.0f}s')
    if rc != 0:
        harness.die(ctx, f'the load generator ({tag}) exited with {rc}')
    with open(os.path.join(ctx.work, f'{tag}.records.jsonl'), 'r',
              encoding='utf-8') as f:
        return [json.loads(line) for line in f if line.strip()]


def warm_up(ctx: harness.Ctx, base: str, mix: Dict[str, Any], vocab: int,
            prefill_chunk: int, compiles: CompileLog) -> None:
    """Two waves over every prefill shape the mix reaches; the second,
    with fresh prompts, shows whether the first left anything to
    compile."""
    for wave in (1, 2):
        reqs = schedule.warmup(mix, ctx.args.seed * 2 + wave, vocab,
                               prefill_chunk)
        t0, n0 = time.time(), len(compiles.times)
        proc = run_loadgen(ctx, f'warmup{wave}', base, t0, reqs, 0.0,
                           WARMUP_LIMIT_S)
        records = reap(ctx, f'warmup{wave}', proc, WARMUP_LIMIT_S + 30)
        bad = [r for r in records if accounting.failed(r)]
        if bad:
            harness.die(ctx, f'warm-up wave {wave}: {len(bad)} request(s) '
                             f'failed, first: {json.dumps(bad[0])[:600]}')
        ctx.say(f'warm-up wave {wave}: {len(reqs)} requests, prompt '
                f'lengths {[len(r["prompt"]) for r in reqs]}, '
                f'{time.time() - t0:.1f}s, '
                f'{len(compiles.times) - n0} compile events')


def pick_rows(ctx: harness.Ctx, mix: Dict[str, Any],
              requests: List[Dict[str, Any]],
              records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The finished rows that are scored: the longest that fits
    `score_max_tokens` and `score_rows` - 1 others drawn from the seed,
    each as its tokens (prompt, then what the engine served, cut to the
    cap) and the prompt's length."""
    cap = int(mix.get('score_max_tokens', 512))
    want = int(mix.get('score_rows', 3))
    prompts = {r['id']: r['prompt'] for r in requests}
    fits = [r for r in records if not accounting.failed(r)
            and r['prompt_tokens'] + 8 <= cap]
    if not fits:
        return []
    rng = random.Random(f'perfbench-score-{ctx.args.seed}')
    longest = max(fits, key=lambda r: (
        min(cap, r['prompt_tokens'] + len(r['tokens'])), -r['id']))
    others = [r for r in fits if r is not longest]
    chosen = [longest] + rng.sample(others, min(want - 1, len(others)))
    return [{'id': r['id'], 'prompt_tokens': r['prompt_tokens'],
             'tokens': (prompts[r['id']] + r['tokens'])[:cap],
             'cap': cap} for r in chosen]


def _padded(row: Dict[str, Any]) -> List[int]:
    """The row's tokens padded to the cap, so that the reference
    compiles one shape (attention is causal: the kept positions score
    exactly)."""
    return row['tokens'] + [1] * (row['cap'] - len(row['tokens']))


def score_rows(cfg: Dict[str, Any], params: Any,
               rows: List[Dict[str, Any]], margin: float
               ) -> Dict[str, Any]:
    """The rows against the configuration's plain reference
    (`perfbench/references/`, float32, no cache, no kernels) on the
    weights the server holds: at every generated position the engine's
    token scores within `margin` nats of the reference's best. Outside
    the window, on the device.

    A row cut to `score_max_tokens` scores its kept positions
    exactly."""
    reference = manifest_lib.reference(cfg['reference'])
    worst, n_pos = 0.0, 0
    for row in rows:
        tokens = row['tokens']
        lp = np.asarray(reference.log_probs(params, cfg, _padded(row)))
        for i in range(row['prompt_tokens'], len(tokens)):
            chosen_lp, best = lp[i - 1, tokens[i]], lp[i - 1].max()
            if not (math.isfinite(chosen_lp) and math.isfinite(best)):
                worst = float('inf')
            worst = max(worst, float(best - chosen_lp))
            n_pos += 1
    return {'ok': bool(rows) and worst <= margin, 'rows': len(rows),
            'positions': n_pos, 'worst_shortfall_nats': worst,
            'margin': margin}


def control_reading(cfg: Dict[str, Any], params: Any,
                    rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """`--control`: the reference put in the program's place, computed
    in the nearest precision below the served one (the reference's
    `control_shortfall`), on the rows and positions just scored. What
    it reads is what the margin must refuse (PERF.md section 2)."""
    reference = manifest_lib.reference(cfg['reference'])
    worst = 0.0
    for row in rows:
        worst = max(worst, reference.control_shortfall(
            params, cfg, _padded(row), row['prompt_tokens'],
            len(row['tokens'])))
    return {'rows': len(rows), 'control_shortfall_nats': worst}


def control(ctx: harness.Ctx, base: str, cfg: Dict[str, Any],
            mix: Dict[str, Any], compiles: CompileLog,
            runtimes: List[Any]) -> None:
    seconds = float(ctx.args.seconds)
    margin = score_margin(cfg)
    ctx.say(f'scoring margin {margin["nats"]:g} nats, from '
            f'{margin["from"]}: {margin["why"]}')
    ready_s = wait_ready(ctx, base, READY_LIMIT_S)
    info = get_json(base, '/')
    stats0 = get_json(base, '/stats')
    vocab, prefill_chunk = info['vocab_size'], stats0['prefill_chunk']
    ctx.say(f'ready {ready_s:.1f}s after process start: '
            f'{json.dumps(info)}; kv_cache {stats0.get("kv_cache")!r}, '
            f'storage {json.dumps(stats0.get("storage"))}, page_pool '
            f'{json.dumps(stats0.get("page_pool"))}')
    warm_up(ctx, base, mix, vocab, prefill_chunk, compiles)

    rate = float(ctx.args.rate if ctx.args.rate is not None
                 else mix['rate_per_s'])
    requests = schedule.build(mix, ctx.args.seed, seconds, rate, vocab)
    drain_s = float(mix['drain_s'])
    open_at = time.time() + 0.5
    proc = run_loadgen(ctx, 'window', base, open_at, requests, seconds,
                       drain_s)
    time.sleep(max(0.0, open_at - time.time()))
    read_open = time.time()
    stats_open = get_json(base, '/stats')
    setup_s = open_at - ctx.t_start
    trace_dir = os.path.join(ctx.work, 'profile')
    trace_t0 = trace_t1 = None
    if ctx.trace:
        import jax
        # The traced span is the window's LAST seconds, so that the
        # closing /stats is read at the close and the profiler stopped
        # after it: the stop takes 25-30 s at this server's operation
        # rate (my chip run, PR 26), and a /stats read behind it spread
        # every per-count metric over the drain.
        span = min(float(mix.get('trace_span_s', 3.0)), seconds * 0.5)
        time.sleep(max(0.0, open_at + seconds - span - time.time()))
        # Device events and the program's own phase events; the Python
        # tracer (on by default) hooks every call of the scheduler loop
        # and slows the host it is meant to observe.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        t_call = time.time()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        trace_t0 = time.time()
        ctx.say(f'profiler started {trace_t0 - open_at:.3f}s into the '
                f'window (the call took {trace_t0 - t_call:.3f}s)')
    time.sleep(max(0.0, open_at + seconds - time.time()))
    read_close = time.time()
    stats_close = get_json(base, '/stats')
    close_at = time.time()
    ctx.say(f'closing /stats read {1000 * (close_at - open_at - seconds):.0f}'
            f'ms after the close')
    if ctx.trace:
        trace_t1 = time.time()
        jax.profiler.stop_trace()
        ctx.say(f'profiler stopped after the closing /stats; the stop took '
                f'{time.time() - trace_t1:.1f}s')
    n_compiles = compiles.between(open_at, open_at + seconds)
    records = reap(ctx, 'window', proc, drain_s + 60)
    stats_end = get_json(base, '/stats')

    summary = accounting.summarize(records, seconds)
    mid, close = (accounting.backlog(records, seconds / 2),
                  accounting.backlog(records, seconds))
    ctx.say(f'window {seconds:g}s at {rate:g} requests/s: '
            f'{json.dumps(summary)}')
    ctx.say('first token after due, ms, every request in due order: '
            + ' '.join(f'{1000 * accounting.ttft_s(r, seconds):.0f}'
                       for r in records))
    ctx.say(f'backlog (requests due and unfinished) at the middle {mid}, '
            f'at the close {close}; compile events in the window '
            f'{n_compiles}')
    engine = {k: (stats_close.get(k), stats_close.get(k, 0)
                  - stats_open.get(k, 0))
              for k in ('decode_calls', 'tokens_committed',
                        'prefill_chunks_run', 'decode_stall_s',
                        'preemptions')}
    ctx.say(f'/stats at the close (value, growth in the window): '
            f'{json.dumps(engine)}; queued {stats_close.get("queued")}, '
            f'active_slots {stats_close.get("active_slots")}, prefix_cache '
            f'{json.dumps(stats_close.get("prefix_cache"))}')

    finished_wrong = [r for r in records if r.get('end') == 'done'
                      and len(r['tokens']) != r['max_new_tokens']]
    rows = pick_rows(ctx, mix, requests, records)
    scoring = score_rows(cfg, runtimes[0].params, rows, margin['nats'])
    if ctx.args.control:
        ctx.say(f'control (not part of a run of the benchmark): '
                f'{json.dumps(control_reading(cfg, runtimes[0].params, rows))}'
                f' against the program\'s {scoring["worst_shortfall_nats"]}')
    paged = str(stats_end.get('kv_cache', '')).startswith('paged')
    # Each number compared, beside its limit (`at_most`).
    compared = {
        'shortfall_nats': {'value': scoring['worst_shortfall_nats'],
                           'at_most': margin['nats']},
        'rows_scored': {'value': scoring['rows'], 'at_least': 1},
        'wrong_lengths': {'value': len(finished_wrong), 'at_most': 0},
        'soft_errors': {'value': stats_end.get('soft_errors'),
                        'at_most': 0},
        'engine_restarts': {'value': stats_end.get('engine_restarts'),
                            'at_most': 0},
        'paged_cache': {'value': int(paged), 'at_least': 1},
    }
    ctx.say(f'scoring {json.dumps(scoring)}')

    trace_summary = trace_path = None
    if ctx.trace:
        kw = mix.get('trace_planes') or {}
        t_reduce = time.time()
        trace_summary, seen, trace_path = trace_reduce.reduce_trace_dir(
            trace_dir, **kw)
        for line in seen:
            ctx.say(f'trace plane {line[:300]}')
        ctx.say(f'trace {trace_path} reduced in '
                f'{time.time() - t_reduce:.1f}s')
        harness.say_trace(ctx, trace_summary)
    end_to_end = {'ttft_p95_ms': summary['ttft_p95_ms'],
                  'itl_p95_ms': summary['itl_p95_ms'],
                  'serve_tokens_per_s': summary['serve_tokens_per_s'],
                  'setup_s': setup_s}
    sources = {
        'stats_open': stats_open, 'stats_close': stats_close,
        'records': records, 'end_to_end': end_to_end,
        'trace': trace_summary, 'trace_path': trace_path,
        # The traced span on the records' clock (seconds from the
        # window's opening): from start_trace's return to the call of
        # stop_trace, so the device's lines cover all of it.
        'trace_t0': None if trace_t0 is None else trace_t0 - open_at,
        'trace_t1': None if trace_t1 is None else trace_t1 - open_at,
        'config': cfg, 'mix': mix, 'device': ctx.device, 'say': ctx.say,
        # The counters grow between the two /stats reads, both taken
        # at the window's ends (the profiler is stopped after the
        # second).
        'harness': {'window_s': seconds, 'ready_s': ready_s,
                    'stats_span_s': read_close - read_open,
                    'compiles_in_window': n_compiles,
                    'lateness_p95_ms': summary['lateness_p95_ms']}}
    harness.finish(ctx, compared=compared,
                   attempted=summary['attempted'],
                   failed=summary['failed'], end_to_end=end_to_end,
                   sources=sources, trace_summary=trace_summary)


def run(ctx: harness.Ctx) -> None:
    harness.claim_device(ctx)
    cfg, mix = ctx.preset(ctx.config), ctx.preset(ctx.mix)
    shim.install()
    compiles = CompileLog().register()
    runtimes = keep_runtime()
    port = free_port()
    argv = ['serve_lm', '--model', cfg['serve_model'], *cfg['serve_lm'],
            '--port', str(port), '--drain-grace', '5']
    ctx.say('python -m skypilot_tpu.recipes.serve_lm ' + ' '.join(argv[1:]))
    ctx.say('the weights are the program\'s own seeded random ones (a '
            'fixed key inside); --seed draws the prompts\' token ids and '
            'the rows that are scored; sizes and due times are the mix\'s')
    if ctx.rehearse:
        sys.stdout = harness.Tee(sys.stdout, harness.REHEARSAL_MARK)
    harness.start_controller(
        ctx, lambda: control(ctx, f'http://127.0.0.1:{port}', cfg, mix,
                             compiles, runtimes),
        deadline_s=DEADLINE_S)
    sys.argv = argv
    from skypilot_tpu.recipes import serve_lm
    serve_lm.main()
    harness.die(ctx, 'serve_lm returned before the window closed')
