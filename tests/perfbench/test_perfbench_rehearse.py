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
         'serve_open_loop': 'mistral-7b-l16.chat'}


# One rehearsal of each driver runs in tier-1 and covers both kinds of
# result line; the other two are `slow` (and `e2e`: live processes), to
# keep tier-1 short.
_SLOW = [pytest.mark.slow, pytest.mark.e2e]


@pytest.mark.parametrize('driver, trace', [
    ('train_job', 1), ('serve_open_loop', 0),
    pytest.param('train_job', 0, marks=_SLOW),
    pytest.param('serve_open_loop', 1, marks=_SLOW)])
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
    assert got <= declared and got >= declared - {'train.mfu_pct'}
    if trace:
        assert result['device']['busy_s'] > 0
        assert result['device']['window_s'] >= result['device']['busy_s']
        assert len(result['breakdown']['device_ops']) <= 10
    else:
        assert 'breakdown' not in result
