"""What the phases and named scopes leave in a profiler trace.

A tiny engine (in this process) and three trainer steps (`train_lm
--profile`, a child) are profiled on the CPU backend. The phases are
`TraceAnnotation`s: they must lie on the host plane of the same
`.xplane.pb` as the operations, on the thread that ran them. The
scopes are metadata of the compiled programs: the CPU backend names
its trace events by HLO instruction (`dot.15`) and stores each
program's HLO, op names included, in the trace's `/host:metadata`
plane, which `ProfileData` does not open, so those are looked for in
the file's bytes (on the TPU they are each device event's `tf_op`).
"""
import collections
import glob
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _xplane(root):
    paths = glob.glob(os.path.join(root, '**', '*.xplane.pb'),
                      recursive=True)
    assert len(paths) == 1, paths
    return paths[0]


def _names_scope(path, scope):
    """Some op name in the file passes through `scope`: `.../xent/...`
    or, under autodiff, `.../jvp(xent)/...`."""
    with open(path, 'rb') as f:
        blob = f.read()
    return re.search(rb'[/(]' + scope.encode() + rb'[/)]', blob)


def _host_lines(path, prefix):
    """{line index: {event name: count}} of the host plane's lines
    that hold an event whose name starts with `prefix`."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith('/host:CPU'):
            continue
        for i, line in enumerate(plane.lines):
            names = collections.Counter(
                ev.name for ev in line.events
                if ev.name.startswith(prefix))
            if names:
                out[i] = names
    return out


@pytest.fixture(scope='module')
def engine_trace(tmp_path_factory):
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import flax.linen as nn
    import jax.numpy as jnp

    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    model = Llama(LlamaConfig.tiny(kv_page_size=8, kv_total_pages=40))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64, prefill_chunk=8)
    root = str(tmp_path_factory.mktemp('engine_profile'))
    try:
        # Compile first, so that the traced request meets programs the
        # backend has already named.
        engine.submit(list(range(1, 20)), max_new_tokens=3
                      ).result(timeout=240)
        # As the benchmark's driver and POST /debug/profile set it.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(root, profiler_options=options)
        try:
            engine.submit(list(range(1, 20)), max_new_tokens=6
                          ).result(timeout=240)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.stop()
    return _xplane(root)


@pytest.fixture(scope='module')
def trainer_trace(tmp_path_factory):
    root = str(tmp_path_factory.mktemp('trainer_profile'))
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    out = subprocess.run(
        [sys.executable, '-m', 'skypilot_tpu.recipes.train_lm',
         '--cpu', '--model', 'tiny', '--steps', '5', '--seq', '16',
         '--global-batch', '4', '--log-every', '2',
         '--profile', root, '--profile-steps', '1:4'],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'profile: steps 1..4 traced' in out.stdout
    return _xplane(root)


def test_engine_phases_lie_on_the_scheduler_threads_line(engine_trace):
    lines = _host_lines(engine_trace, 'engine.')
    assert len(lines) == 1, lines        # one thread ran them all
    names = next(iter(lines.values()))
    for name in ('engine.loop', 'engine.admit',
                 'engine.prefill_dispatch', 'engine.first_token_sync',
                 'engine.decode_dispatch', 'engine.fetch_wait',
                 'engine.commit'):
        assert names[name] > 0, (name, names)
    # The operations are in the same file, on other lines of the
    # same plane.
    assert not _host_lines(engine_trace, 'train.')


def test_trainer_phases_lie_on_the_main_threads_line(trainer_trace):
    lines = _host_lines(trainer_trace, 'train.')
    assert len(lines) == 1, lines
    names = next(iter(lines.values()))
    # Steps 1, 2 and 3; the session starts and stops INSIDE an
    # iteration, so only step 2's enclosing phase is whole.
    assert names['train.data'] == 3 and names['train.dispatch'] == 3
    assert names['train.loop'] == 1
    assert names['train.sync'] >= 1      # the drain before the stop


@pytest.mark.parametrize('scope', ['kv_write', 'paged_attention',
                                   'chunk_attention', 'lm_head',
                                   'sample'])
def test_engine_scopes_reach_the_trace(engine_trace, scope):
    assert _names_scope(engine_trace, scope)


@pytest.mark.parametrize('scope', ['xent', 'attention', 'optimizer'])
def test_trainer_scopes_reach_the_trace(trainer_trace, scope):
    assert _names_scope(trainer_trace, scope)


def test_debug_profile_runs_one_session_and_refuses_a_second(tmp_path):
    """POST /debug/profile: the operator's way to the phases in
    Perfetto. The session's file holds the scheduler's idle phases
    (an engine with no request still iterates); a second POST while
    it runs is answered 409, a malformed one 400."""
    import json
    import threading
    import urllib.error
    import urllib.request

    import jax
    jax.config.update('jax_platforms', 'cpu')
    import flax.linen as nn
    import jax.numpy as jnp

    from skypilot_tpu.inference.http_server import make_server
    from skypilot_tpu.inference.runtime import InferenceRuntime
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    model = Llama(LlamaConfig.tiny(kv_page_size=8, kv_total_pages=40))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64)
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=64, spec_total=64, speculative=0, engine=engine)
    server = make_server(rt, 0)
    url = f'http://127.0.0.1:{server.server_address[1]}/debug/profile'
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def post(body):
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={'Content-Type': 'application/json'})
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        assert post({'seconds': 0, 'dir': str(tmp_path)})[0] == 400
        assert post({'seconds': 1})[0] == 400
        first = {}
        t = threading.Thread(target=lambda: first.update(
            zip(('code', 'body'),
                post({'seconds': 1.0, 'dir': str(tmp_path)}))))
        t.start()
        import time
        time.sleep(0.4)
        assert post({'seconds': 0.1, 'dir': str(tmp_path)})[0] == 409
        t.join(timeout=120)
        assert first['code'] == 200
        assert first['body'] == {'dir': str(tmp_path), 'seconds': 1.0}
    finally:
        server.shutdown()
        engine.stop()
    lines = _host_lines(_xplane(str(tmp_path)), 'engine.')
    assert len(lines) == 1
    assert next(iter(lines.values()))['engine.idle_wait'] > 3
