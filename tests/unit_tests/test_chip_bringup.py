"""What PR 21 (chip bring-up) added, as far as a CPU can check it.

The chip itself is `chip_smoke.py`'s and `ops/kernel_check.py`'s
business; here: the entry points FAIL without a chip instead of
carrying on on the CPU, the rehearsal drives both stages, the compile
cache is placeable from outside and otherwise fixed, the peak table
knows what a v5e calls itself and refuses what it does not know, the
depth-cut registry entry is a depth cut, one real replica per TPU
host, and the OpenAI surface takes token ids when the model has no
tokenizer.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(cmd, env=None, cwd=_REPO, timeout=600):
    full = dict(os.environ, JAX_PLATFORMS='cpu')
    full.pop('JAX_COMPILATION_CACHE_DIR', None)
    full.update(env or {})
    return subprocess.run(cmd, cwd=cwd, env=full, capture_output=True,
                          text=True, timeout=timeout)


# -- no chip is a failure ----------------------------------------------------
def test_chip_smoke_without_a_chip_fails_and_says_what_it_found():
    out = _run([sys.executable, 'chip_smoke.py'])
    assert out.returncode != 0
    assert "found platform 'cpu'" in out.stdout
    # No result line: nothing that could be read as a pass.
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(_REPO, 'chip_smoke.py'), tmp_path)
    out = _run([sys.executable, 'chip_smoke.py'], cwd=str(tmp_path),
               env={'PYTHONPATH': ''})
    assert out.returncode != 0
    assert 'no skypilot_tpu package' in out.stdout
    assert '"ok"' not in out.stdout


def test_bench_without_a_chip_fails_and_says_what_it_found():
    out = _run([sys.executable, 'bench.py'])
    assert out.returncode != 0
    assert "found platform 'cpu'" in out.stderr
    assert out.stdout.strip() == ''     # no JSON line under any name
    with open(os.path.join(_REPO, 'bench.py'), encoding='utf-8') as f:
        assert 'execv' not in f.read()


def test_chip_smoke_rehearsal_runs_both_stages(tmp_path):
    """The dress rehearsal drives the trainer (direct, then resumed
    through `stpu launch --infra local`) and the server (concurrent
    /generate, streamed /v1/completions, a prefix hit, plain-forward
    scoring, /stats) at the tiny presets — and marks every line."""
    out = _run([sys.executable, 'chip_smoke.py', '--rehearse',
                '--work-dir', str(tmp_path / 'work')], timeout=900)
    assert out.returncode == 0, out.stdout[-6000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert all(l.startswith('[rehearsal] ') for l in lines), lines
    for stage in ('device', 'train', 'resume', 'serve'):
        assert f'[rehearsal] {stage}: PASSED' in out.stdout
    assert 'resumed from checkpoint step 12' in out.stdout
    assert 'streamed /v1/completions: 6 tokens' in out.stdout
    # The last line is marked too: it is not the contract's JSON.
    with pytest.raises(ValueError):
        json.loads(lines[-1])
    assert json.loads(lines[-1][len('[rehearsal] '):])['ok'] is True


# -- the compile cache -------------------------------------------------------
_CACHE_PROBE = '''
import json, jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append([k, v]), real(k, v))[1]
from skypilot_tpu.utils import compile_cache
if {fake_tpu!r}:
    jax.default_backend = lambda: 'tpu'
print(json.dumps({{'ret': compile_cache.configure(), 'calls': calls,
                  'default': compile_cache.default_dir()}}))
'''


def _cache_probe(env, fake_tpu=False):
    out = _run([sys.executable, '-c',
                _CACHE_PROBE.format(fake_tpu=fake_tpu)], env=env)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_set_means_code_sets_no_directory(tmp_path):
    placed = str(tmp_path / 'placed')
    got = _cache_probe({'JAX_COMPILATION_CACHE_DIR': placed},
                       fake_tpu=True)
    assert got['ret'] == placed
    assert not [c for c in got['calls']
                if c[0] == 'jax_compilation_cache_dir']


def test_compile_cache_default_is_fixed_and_in_the_checkout():
    a = _cache_probe({}, fake_tpu=True)
    b = _cache_probe({}, fake_tpu=True)
    want = os.path.join(_REPO, '.jax_cache')
    assert a['ret'] == b['ret'] == a['default'] == want
    assert ['jax_compilation_cache_dir', want] in a['calls']
    # On the CPU backend (these tests) no directory is set at all.
    cpu = _cache_probe({})
    assert cpu['ret'] is None
    assert not [c for c in cpu['calls']
                if c[0] == 'jax_compilation_cache_dir']


def test_local_jobs_inherit_the_launchers_cache_dir(monkeypatch):
    from skypilot_tpu.client import cli
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/placed/outside')
    local = cli._build_task('echo hi', None, None, 'local', 'tpu-v5e-1',
                            None, None, None, None, ())
    assert local.envs['JAX_COMPILATION_CACHE_DIR'] == '/placed/outside'
    # A machine elsewhere has no use for this machine's path.
    other = cli._build_task('echo hi', None, None, None, None, None,
                            None, None, None, ())
    assert 'JAX_COMPILATION_CACHE_DIR' not in other.envs


# -- peaks -------------------------------------------------------------------
class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_flops_keyed_by_what_the_chip_prints(monkeypatch):
    import jax

    from skypilot_tpu.observability import step_metrics
    monkeypatch.setattr(jax, 'devices',
                        lambda *a: [_Dev('tpu', 'TPU v5 lite')])
    assert step_metrics.peak_flops_per_device() == 197e12
    # An assumed peak is not a measurement: unknown TPU kind raises,
    # and no environment variable can supply one.
    monkeypatch.setenv('SKYPILOT_DEVICE_PEAK_FLOPS', '1e15')
    monkeypatch.setattr(jax, 'devices',
                        lambda *a: [_Dev('tpu', 'TPU v9 hyper')])
    with pytest.raises(KeyError, match='TPU v9 hyper'):
        step_metrics.peak_flops_per_device()
    monkeypatch.setattr(jax, 'devices', lambda *a: [_Dev('cpu', 'cpu')])
    assert step_metrics.peak_flops_per_device() is None


# -- the depth-cut registry entry --------------------------------------------
def test_llama3_8b_l8_differs_from_llama3_8b_only_in_depth():
    from skypilot_tpu.models.llama import LlamaConfig
    from skypilot_tpu.recipes.train_lm import _build_model
    cut = _build_model('llama3-8b-l8', 2048, False)[0].config
    full = _build_model('llama3-8b', 2048, False)[0].config
    assert full == LlamaConfig.llama3_8b(max_seq_len=2048)
    diff = {k for k, v in dataclasses.asdict(cut).items()
            if v != dataclasses.asdict(full)[k]}
    assert diff == {'num_layers'}
    assert (cut.num_layers, full.num_layers) == (8, 32)


# -- one process per chip ----------------------------------------------------
def test_second_real_replica_on_a_tpu_host_is_refused(monkeypatch):
    from skypilot_tpu.serve.replica_plane import replica_manager
    from skypilot_tpu.utils import tpu_utils
    sleeper = [sys.executable, '-c', 'import time; time.sleep(60)']
    env = {k: v for k, v in os.environ.items() if k != 'JAX_PLATFORMS'}
    procs = []
    assert tpu_utils.local_tpu_chips() >= 0     # counts, without JAX
    try:
        monkeypatch.setattr(tpu_utils, 'local_tpu_chips', lambda: 1)
        spawn = replica_manager.serve_lm_factory(sleeper, env=env)
        procs.append(spawn(0, 1))
        with pytest.raises(RuntimeError, match='belongs to one process'):
            spawn(1, 2)
        # Once the holder is gone the chip is free again.
        procs[0].kill()
        procs[0].wait(timeout=10)
        procs.append(spawn(2, 3))
        # CPU replicas share a host freely, as every fleet test does.
        cpu = replica_manager.serve_lm_factory(sleeper + ['--cpu'],
                                               env=env)
        procs += [cpu(0, 4), cpu(1, 5)]
        # And a host without chips is not a TPU host.
        monkeypatch.setattr(tpu_utils, 'local_tpu_chips', lambda: 0)
        plain = replica_manager.serve_lm_factory(sleeper, env=env)
        procs += [plain(0, 6), plain(1, 7)]
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=10)


# -- token ids on the OpenAI surface -----------------------------------------
def test_completion_prompts_as_token_ids():
    from skypilot_tpu.inference import openai_compat as oai

    class _Rt:
        vocab_size = 100

    assert oai.normalize_prompts('a b') == ['a b']
    assert oai.normalize_prompts([1, 2, 3]) == [[1, 2, 3]]
    assert oai.normalize_prompts([[1, 2], [3]]) == [[1, 2], [3]]
    assert oai.normalize_prompts(['a', 'b']) == ['a', 'b']
    tok = oai.TokenIdText()
    assert oai.encode_prompt(_Rt, tok, [5, 6]) == [5, 6]
    assert oai.encode_prompt(_Rt, tok, '5 6') == [5, 6]
    assert tok.decode([5, 6]) == ' 5 6'
    with pytest.raises(ValueError, match='outside the vocabulary'):
        oai.encode_prompt(_Rt, tok, [5, 100])
    with pytest.raises(ValueError, match='no tokenizer'):
        oai.encode_prompt(_Rt, tok, 'hello world')


# -- the engine says which cache it runs -------------------------------------
def test_engine_reports_the_dense_cache_and_why():
    """examples/serve_inframework.yaml's old shape: a default pool
    (128 pages x 16 tokens) against --max-total-len 2048 used to pick
    the dense cache without a word."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    cfg = dataclasses.replace(LlamaConfig.tiny(), max_seq_len=2048)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=2048)
    try:
        assert not eng.paged
        assert eng.kv_cache_choice.startswith('dense: the page pool '
                                              '(128 pages x 16')
        assert '--kv-pool-bytes' in eng.kv_cache_choice
        assert eng.attention_impl() == 'dense'
        assert eng.soft_errors_total == 0
    finally:
        eng.stop()
    paged = ContinuousBatchingEngine(model, params, num_slots=2,
                                     max_total_len=256)
    try:
        assert paged.kv_cache_choice.startswith('paged: ')
    finally:
        paged.stop()


def test_device_memory_lists_every_local_device():
    import jax

    from skypilot_tpu.parallel import mesh as mesh_lib
    mem = mesh_lib.device_memory()
    assert [m['id'] for m in mem] == [d.id for d in jax.local_devices()]
    assert set(mem[0]) == {'id', 'bytes_in_use', 'peak_bytes_in_use',
                           'bytes_limit'}
