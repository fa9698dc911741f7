"""`perfbench/run.py` as a process: without a chip it fails with no
result line; `--rehearse` runs each driver end to end on the CPU at the
tiny presets, every line marked, no number under a metric's name."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join(ROOT, 'perfbench', 'run.py')]
MANIFEST = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))


def _run(args, timeout=300):
    # One CPU device, as one chip; one compute thread, so that a
    # rehearsal does not crowd the tests that run beside it.
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_cpu_multi_thread_eigen=false',
               OMP_NUM_THREADS='1')
    return subprocess.run(RUN + args, cwd=ROOT, env=env, timeout=timeout,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    out = _run(['--workload', 'gpt2-124m.pretrain', '--seed', '1',
                '--seconds', '1', '--trace', '0'])
    assert out.returncode != 0
    assert 'not \'tpu\'' in out.stderr
    assert not any(line.startswith('{') for line in out.stdout.splitlines())


def test_unknown_workload_fails_and_prints_no_result():
    out = _run(['--workload', 'nothing.here', '--seed', '1',
                '--seconds', '1', '--trace', '0'])
    assert out.returncode != 0 and '{' not in out.stdout


CELLS = {'train_job': 'gpt2-124m.pretrain',
         'serve_open_loop': 'mistral-7b-l16.chat-r2'}


# One rehearsal of each driver runs in tier-1 and covers both kinds of
# result line, and the traced serving run as well (the order of the
# closing /stats and the profiler's stop is asserted in it); the
# untraced trainer run is `slow` (and `e2e`: live processes).
_SLOW = [pytest.mark.slow, pytest.mark.e2e]
#: Readers that need the chip's peaks or a TPU plane's programs: a CPU
#: rehearsal leaves their metrics out.
_CHIP_ONLY = {'train.mfu_pct', 'serve.mfu_pct',
              'kernel.paged_decode_roofline', 'engine.prefill_share_pct'}


@pytest.mark.parametrize('driver, trace', [
    ('train_job', 1), ('serve_open_loop', 0),
    pytest.param('train_job', 0, marks=_SLOW),
    ('serve_open_loop', 1)])
def test_rehearsal_of_each_driver(driver, trace):
    cell = CELLS[driver]
    out = _run(['--workload', cell, '--seed', str(2 ** 31 + 12345),
                '--seconds', '2', '--trace', str(trace), '--rehearse'])
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-3000:]
    lines = out.stdout.splitlines()
    assert all(line.startswith('[rehearsal] ') for line in lines), [
        l for l in lines if not l.startswith('[rehearsal] ')][:5]
    result = json.loads(lines[-1][len('[rehearsal] '):])
    assert set(result) >= {'correct', 'attempted', 'failed', 'metrics',
                           'device'}
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 0
    assert result['device']['platform'] == 'cpu'
    group = 'per_layer' if trace else 'end_to_end'
    declared = {m['name'] for m in MANIFEST[group]
                if cell in m.get('workloads', [cell])}
    got = set(result['metrics'])
    assert all(name.startswith('rehearsal.') for name in got)
    got = {name[len('rehearsal.'):] for name in got}
    # A reader that finds nothing leaves its metric out (the CPU is in
    # no table of peaks); nothing undeclared is ever reported.
    assert got <= declared and got >= declared - _CHIP_ONLY
    # The numbers compared stand beside their limits: under the result's
    # LAST key, and on the last lines of stderr.
    assert list(result)[-1] == 'compared' and result['compared']
    assert all(e['ok'] and ('at_most' in e or 'at_least' in e)
               for e in result['compared'].values())
    tail = out.stderr.strip().splitlines()[-len(result['compared']) - 1:]
    assert tail[-1] == '[rehearsal] perfbench: correct = true'
    assert all(f'compared {name} = ' in line
               for name, line in zip(result['compared'], tail))
    if trace and driver == 'serve_open_loop':
        # The closing /stats is read at the close, the profiler stopped
        # after it (a stop of 25-30 s on the chip otherwise spreads
        # every per-count metric over the drain).
        said = [i for i, line in enumerate(lines)
                if 'closing /stats read' in line
                or 'profiler stopped after the closing /stats' in line
                or 'profiler started' in line]
        assert len(said) == 3 and said == sorted(said)
        assert 'profiler started' in lines[said[0]]
        assert 'closing /stats read' in lines[said[1]]
        ms = float(lines[said[1]].split('read ')[1].split('ms')[0])
        assert ms < 1000
        assert any('phase events on the host plane' in line
                   and 'engine.decode_dispatch' in line for line in lines)
    if trace:
        assert result['device']['busy_s'] > 0
        assert result['device']['window_s'] >= result['device']['busy_s']
        assert len(result['breakdown']['device_ops']) <= 10
    else:
        assert 'breakdown' not in result


FAULT = """
import runpy, sys
sys.path.insert(0, {root!r})
from skypilot_tpu.models import batching
original = batching.ContinuousBatchingEngine._commit_token
calls = [0]
def altered(self, slot, next_tok):
    calls[0] += 1
    if calls[0] % 5 == 0:       # every fifth token, where it is produced
        next_tok = (int(next_tok) + 1) % 512
    return original(self, slot, next_tok)
batching.ContinuousBatchingEngine._commit_token = altered
sys.argv = ['run.py'] + sys.argv[1:]
runpy.run_path({run!r}, run_name='__main__')
"""


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The rest of a run with the timed path broken underneath: the
    engine installs another token than the one it sampled, every fifth
    time; the run ends, and `correct` is false by the one number that
    is there to catch it."""
    code = FAULT.format(root=ROOT, run=RUN[1])
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_cpu_multi_thread_eigen=false',
               OMP_NUM_THREADS='1')
    out = subprocess.run(
        [sys.executable, '-c', code, '--workload',
         'mistral-7b-l16.chat-saturated-r2', '--seed', '5', '--seconds',
         '2', '--trace', '0', '--rehearse'], cwd=ROOT, env=env,
        timeout=300, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(
        out.stdout.splitlines()[-1][len('[rehearsal] '):])
    assert result['correct'] is False
    bad = {k for k, e in result['compared'].items() if not e['ok']}
    assert bad == {'shortfall_nats'}
    assert 'compared shortfall_nats = ' in out.stderr
    assert out.stderr.strip().endswith('correct = false')
