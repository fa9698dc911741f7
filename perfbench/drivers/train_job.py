"""Driver `train_job`: the trainer's own loop, `train_lm.main()`.

The mix file gives `train_lm`'s flags. This process claims the chip and
runs `skypilot_tpu.recipes.train_lm.main()` on its main thread with far
more steps than fit; a controller thread follows `--metrics-file`, opens
the window at the second record (the first holds the compile), closes
it at the first record `--seconds` later, reads the device's memory peak
and ends the process. `--trace 1` adds `--profile DIR --profile-steps
A:B` (the program's own profiler hook) and reduces the trace here.

(The trainer runs in this process and not in a child so that the
process that holds the chip can report the device and its memory peak:
`train_lm` prints both only after its last step, which never comes.)
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Any, Dict, List

from perfbench import harness
from perfbench import trace_reduce

STEPS = 10 ** 7          # far more than fit in any window
#: No result after this many seconds ends the run: inside the 1200 s
#: the contract gives a run that compiles.
DEADLINE_S = 1100.0


def read_records(path: str) -> List[Dict[str, Any]]:
    try:
        with open(path, 'r', encoding='utf-8') as f:
            lines = f.read().split('\n')
    except OSError:
        return []
    out = []
    for line in lines[:-1]:             # the last piece is unfinished
        if line.strip():
            out.append(json.loads(line))
    return out


def window_of(records: List[Dict[str, Any]], seconds: float):
    """(index of the opening record, index of the closing record or
    None). Opens at the second record; closes at the first record at
    least `seconds` after it."""
    if len(records) < 2:
        return None, None
    t_open = records[1]['time']
    for i in range(2, len(records)):
        if records[i]['time'] - t_open >= seconds:
            return 1, i
    return 1, None


def judge(records: List[Dict[str, Any]], a: int, b: int,
          vocab_size: int, log_every: int) -> Dict[str, Any]:
    """The numbers compared for a training window, each beside its
    limit: every loss finite; the closing record's loss below the
    run's first record's and not below ln(vocab) - 0.05 (the tokens are
    uniform and drawn afresh each step, so the loss can only approach
    ln(vocab) from above); no step missing between the window's
    records. (No reference yet: PERF.md section 7.)"""
    losses = [r['loss'] for r in records[:b + 1]]
    floor = math.log(vocab_size) - 0.05
    steps = [r['step'] for r in records[a:b + 1]]
    missing = sum(1 for i in range(len(steps) - 1)
                  if steps[i + 1] - steps[i] != log_every)
    return {
        'losses_not_finite': {
            'value': sum(1 for x in losses if not math.isfinite(x)),
            'at_most': 0},
        # Strictly below the first loss: at most the float before it.
        'last_loss': {'value': losses[-1], 'at_least': floor,
                      'at_most': math.nextafter(losses[0], -math.inf)},
        'steps_missing': {'value': missing, 'at_most': 0},
    }


def control(ctx: harness.Ctx, tee: harness.Tee, metrics_path: str,
            profile_dir: str, cfg: Dict[str, Any], mix: Dict[str, Any]
            ) -> None:
    seconds = float(ctx.args.seconds)
    flags = mix['train_lm']
    batch, seq = int(flags['--global-batch']), int(flags['--seq'])
    log_every = int(flags['--log-every'])
    announced = False
    while True:
        records = read_records(metrics_path)
        a, b = window_of(records, seconds)
        if a is not None and not announced:
            announced = True
            ctx.say(f'window opens at step {records[a]["step"]}, '
                    f'{records[a]["time"] - ctx.t_start:.1f}s after '
                    f'process start')
        traced = (not ctx.trace) or tee.find('profile: ') is not None
        if b is not None and traced:
            break
        time.sleep(0.05)
    ra, rb = records[a], records[b]
    chips = ctx.device['count']
    window_s = rb['time'] - ra['time']
    n_steps = rb['step'] - ra['step']
    tokens_per_s = n_steps * batch * seq / window_s / chips
    compared = judge(records, a, b, cfg['vocab_size'], log_every)
    end_to_end = {'train_tokens_per_s': tokens_per_s,
                  'setup_s': ra['time'] - ctx.t_start}
    ctx.say(f'window: steps {ra["step"]}..{rb["step"]} in '
            f'{window_s:.3f}s, batch {batch} x seq {seq} on {chips} '
            f'chip(s); first loss {records[0]["loss"]}, compared '
            f'{json.dumps(compared)}')
    ctx.say(f'program lines: {tee.find("setup: ")} | '
            f'{tee.find("step metrics -> ")}')
    summary = trace_path = None
    if ctx.trace:
        kw = mix.get('trace_planes') or {}
        summary, seen, trace_path = trace_reduce.reduce_trace_dir(
            profile_dir, **kw)
        for line in seen:
            ctx.say(f'trace plane {line[:300]}')
        harness.say_trace(ctx, summary)
    # The trainer's own hook traces the steps the mix names; when, on
    # the records' clock, is the program's to say and is not known here.
    sources = {'records': records[a + 1:b + 1], 'stdout': list(tee.lines),
               'end_to_end': end_to_end, 'trace': summary,
               'trace_path': trace_path, 'trace_t0': None, 'trace_t1': None,
               'config': cfg, 'mix': mix, 'device': ctx.device,
               'say': ctx.say, 'harness': {'window_s': window_s}}
    harness.finish(ctx, compared=compared, attempted=n_steps,
                   failed=compared['steps_missing']['value'],
                   end_to_end=end_to_end,
                   sources=sources, trace_summary=summary)


def run(ctx: harness.Ctx) -> None:
    harness.claim_device(ctx)
    cfg, mix = ctx.preset(ctx.config), ctx.preset(ctx.mix)
    metrics_path = os.path.join(ctx.work, 'metrics.jsonl')
    profile_dir = os.path.join(ctx.work, 'profile')
    argv = ['train_lm', '--model', cfg['registry_name'],
            '--steps', str(STEPS), '--metrics-file', metrics_path]
    for flag, value in mix['train_lm'].items():
        argv += [flag, str(value)]
    if ctx.trace:
        argv += ['--profile', profile_dir,
                 '--profile-steps', mix['profile_steps']]
    ctx.say('train_lm has no --seed (its data and its weights come from '
            'fixed seeds inside): --seed changes nothing in this cell')
    ctx.say('python -m skypilot_tpu.recipes.train_lm ' + ' '.join(argv[1:]))
    tee = harness.Tee(
        sys.stdout, harness.REHEARSAL_MARK if ctx.rehearse else '')
    sys.stdout = tee
    harness.start_controller(
        ctx, lambda: control(ctx, tee, metrics_path, profile_dir, cfg, mix),
        deadline_s=DEADLINE_S)
    sys.argv = argv
    from skypilot_tpu.recipes import train_lm
    train_lm.main()
    harness.die(ctx, 'train_lm ran out of steps before the window closed')
