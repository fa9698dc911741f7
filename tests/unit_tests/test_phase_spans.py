"""Loop phases (observability/tracing.phase): the primitive, the
engine's scheduler loop as /stats serves it, its dispatch phases one
level down, the GC pause hook, the per-layer metrics that read them,
the per-request histograms, the trainer's record fields and the SKY007
rule.

Everything runs on the CPU with the tiny llama engine (a routed tiny
model for the counter fetch).
"""
import json
import threading
import time
import urllib.request

import pytest

from skypilot_tpu import analysis
from skypilot_tpu.observability import tracing

ENGINE_PHASES = ('engine.control', 'engine.admit',
                 'engine.prefill_dispatch', 'engine.first_token_sync',
                 'engine.decode_dispatch', 'engine.fetch_wait',
                 'engine.commit', 'engine.idle_wait')


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------
def test_phase_accumulates_self_time_and_nests():
    clock = tracing.PhaseClock()
    with tracing.phase('outer', clock) as outer:
        time.sleep(0.02)
        for _ in range(2):
            with tracing.phase('inner', clock) as inner:
                time.sleep(0.01)
    assert clock.n('outer') == 1 and clock.n('inner') == 2
    assert inner.dur >= 0.01 and outer.dur >= 0.04
    # Self time: the children's durations are taken off the parent,
    # so the names partition the root's whole duration.
    assert clock.inclusive('outer') == pytest.approx(outer.dur)
    assert clock.seconds('outer') + clock.seconds('inner') == \
        pytest.approx(outer.dur)
    assert clock.seconds('outer') < outer.dur - 0.015
    assert clock.seconds('never') == 0.0 and clock.n('never') == 0


def test_phase_closes_on_an_exception():
    clock = tracing.PhaseClock()
    with pytest.raises(KeyError):
        with tracing.phase('outer', clock):
            with tracing.phase('inner', clock):
                raise KeyError('x')
    assert clock.n('outer') == 1 and clock.n('inner') == 1
    assert not clock._open


def test_phase_is_a_chrome_event_while_the_timeline_is_on(
        tmp_path, monkeypatch):
    from skypilot_tpu.utils import timeline
    clock = tracing.PhaseClock()
    with tracing.phase('quiet', clock):
        pass                              # timeline off: no event
    monkeypatch.setattr(timeline, '_enabled_path',
                        str(tmp_path / 't.json'))
    monkeypatch.setattr(timeline, '_events', [])
    with tracing.phase('loud', clock):
        pass
    timeline.save()
    events = json.load(open(tmp_path / 't.json'))['traceEvents']
    assert [e['name'] for e in events] == ['loud']
    assert events[0]['ph'] == 'X' and events[0]['dur'] >= 0


# ---------------------------------------------------------------------------
# the engine's scheduler loop and the per-request histograms
# ---------------------------------------------------------------------------
def _tiny():
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import flax.linen as nn
    import jax.numpy as jnp

    from skypilot_tpu.models.llama import Llama, LlamaConfig
    model = Llama(LlamaConfig.tiny(kv_page_size=8, kv_total_pages=40))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    return model, params


@pytest.fixture(scope='module')
def served_stats():
    """/stats of a tiny server after four UNSAMPLED streamed requests
    whose prompts take several prefill chunks each, so that chunks
    interleave with decode rounds; read once the engine has stopped,
    so that no phase is open."""
    from skypilot_tpu.inference.http_server import make_server
    from skypilot_tpu.inference.runtime import InferenceRuntime
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    model, params = _tiny()
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64, prefill_chunk=8)
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=64, spec_total=64, speculative=0, engine=engine)
    server = make_server(rt, 0)
    url = f'http://127.0.0.1:{server.server_address[1]}'
    threading.Thread(target=server.serve_forever, daemon=True).start()
    assert not tracing.enabled()

    def post(i):
        req = urllib.request.Request(
            f'{url}/generate',
            data=json.dumps({'tokens': [list(range(1, 20 + i))],
                             'max_new_tokens': 12,
                             'stream': True}).encode(),
            headers={'Content-Type': 'application/json'})
        urllib.request.urlopen(req, timeout=240).read()

    threads = [threading.Thread(target=post, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    time.sleep(0.15)                      # a few idle iterations
    engine.stop()
    assert not engine._thread.is_alive()
    stats = json.loads(urllib.request.urlopen(f'{url}/stats',
                                              timeout=30).read())
    server.shutdown()
    return stats, engine


def test_engine_phases_sum_to_the_loops_wall_time(served_stats):
    stats, engine = served_stats
    total = sum(rec['s'] for rec in stats['phases'].values())
    assert stats['loop_s'] > 0
    assert total == pytest.approx(stats['loop_s'], rel=0.02)
    assert total <= stats['loop_s'] + 1e-5
    assert 'engine.loop' not in stats['phases']
    assert stats['time'] == pytest.approx(time.time(), abs=60)
    assert engine.prefill_chunks_run >= 8    # chunks interleaved


@pytest.mark.parametrize('name', ENGINE_PHASES)
def test_every_exercised_engine_phase_ran(served_stats, name):
    stats, _ = served_stats
    rec = stats['phases'][name]
    assert rec['n'] > 0 and rec['s'] > 0


def test_decode_stall_is_the_fetch_wait_phase(served_stats):
    stats, engine = served_stats
    assert stats['decode_stall_s'] == \
        stats['phases']['engine.fetch_wait']['s']
    assert engine.decode_stall_s == \
        engine.phases.seconds('engine.fetch_wait')
    assert stats['phases']['engine.prefill_dispatch']['n'] == \
        stats['prefill_chunks_run']


@pytest.mark.parametrize('name', ['queue_wait', 'admit_to_first_token',
                                  'http_ttft_overhead'])
def test_an_unsampled_request_lands_in_every_histogram(served_stats,
                                                       name):
    from skypilot_tpu.observability import catalog
    stats, _ = served_stats
    hist = stats['latency'][name]
    assert hist['n'] >= 4 and hist['sum_s'] > 0
    assert sum(hist['buckets'].values()) == hist['n']
    assert hist['ratio'] == pytest.approx(2 ** 0.125)
    edges = {f'{1e3 * b:.4f}' for b in catalog.FINE_BUCKETS}
    assert set(hist['buckets']) <= edges | {'inf'}


def test_phase_seconds_reach_the_prometheus_exposition(served_stats):
    from skypilot_tpu.observability import REGISTRY
    _, engine = served_stats
    engine.update_metric_gauges()
    text = REGISTRY.render()
    assert ('# TYPE skypilot_serving_scheduler_phase_seconds_total '
            'counter') in text
    assert (f'skypilot_serving_scheduler_phase_seconds_total{{engine='
            f'"{engine.engine_id}",phase="engine.commit"}}') in text
    assert (f'skypilot_serving_queue_wait_seconds_count{{engine='
            f'"{engine.engine_id}"}} 4') in text


@pytest.mark.parametrize('kwargs', [
    {'pipeline_decode': False}, {'decode_chunk': 2},
    {'speculative_k': 2}], ids=['plain', 'chunked', 'speculative'])
def test_other_decode_paths_carry_dispatch_wait_and_commit(kwargs):
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    model, params = _tiny()
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64, **kwargs)
    try:
        out = engine.submit([5, 6, 7, 5, 6, 7], max_new_tokens=6
                            ).result(timeout=240)
        assert len(out) == 12
    finally:
        engine.stop()
    for name in ('engine.decode_dispatch', 'engine.fetch_wait',
                 'engine.commit'):
        assert engine.phases.n(name) > 0, name
    total = sum(r['s'] for r in engine.phase_stats().values())
    assert total == pytest.approx(engine.loop_s, rel=0.02)


DECODE_SUB_PHASES = ('engine.grow_pages', 'engine.round_inputs',
                     'engine.decode_enqueue')


@pytest.mark.parametrize('kwargs', [{}, {'pipeline_decode': False}],
                         ids=['pipelined', 'plain'])
def test_dispatch_phases_run_once_a_round_and_once_a_chunk(kwargs):
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    model, params = _tiny()
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64, prefill_chunk=8,
                                      **kwargs)
    try:
        futs = [engine.submit(list(range(1, 20 + i)), max_new_tokens=6)
                for i in range(3)]
        for fut in futs:
            assert len(fut.result(timeout=240)) == 25 + futs.index(fut)
    finally:
        engine.stop()
    clock = engine.dispatch_phases
    assert engine.decode_calls > 0 and engine.prefill_chunks_run > 0
    for name in DECODE_SUB_PHASES:
        assert clock.n(name) == engine.decode_calls, name
    assert clock.n('engine.prefill_enqueue') == engine.prefill_chunks_run
    # One level down, on a clock of its own: the sub-phases fit in the
    # self seconds of the phase they lie in, and those still partition
    # the loop.
    assert sum(clock.seconds(name) for name in DECODE_SUB_PHASES) <= \
        engine.phases.seconds('engine.decode_dispatch')
    assert clock.seconds('engine.prefill_enqueue') <= \
        engine.phases.seconds('engine.prefill_dispatch')
    total = sum(r['s'] for r in engine.phase_stats().values())
    assert total == pytest.approx(engine.loop_s, rel=0.02)


def test_stats_serves_the_dispatch_phases_and_the_gc_pauses(served_stats):
    stats, engine = served_stats
    served = stats['dispatch_phases']
    for name in DECODE_SUB_PHASES + ('engine.prefill_enqueue',):
        assert served[name]['n'] > 0 and served[name]['s'] > 0, name
    assert served['engine.decode_enqueue']['n'] == stats['decode_calls']
    assert served['engine.prefill_enqueue']['n'] == \
        stats['prefill_chunks_run']
    assert served == engine.dispatch_phases.stats()
    # A model without device counters fetches none.
    assert 'engine.stats_counters' not in served
    assert set(stats['gc']) == {'gen0', 'gen1', 'gen2'}
    for rec in stats['gc'].values():
        assert set(rec) == {'n', 's', 'max_s'}
        assert rec['max_s'] <= rec['s'] + 1e-6


def test_a_routed_models_counter_fetch_is_one_stats_counters_phase():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import nemotron_h as nh
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32)
    params = nn.meta.unbox(jax.jit(nh.NemotronH(cfg).init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    engine = ContinuousBatchingEngine(nh.NemotronH(cfg), params,
                                      num_slots=2, max_total_len=64)
    try:
        assert engine.model_counters()
        assert engine.dispatch_phases.n('engine.stats_counters') == 1
    finally:
        engine.stop()


@pytest.mark.parametrize('name', ['engine.grow_pages_ms',
                                  'engine.round_inputs_ms',
                                  'engine.decode_enqueue_ms',
                                  'engine.prefill_enqueue_ms',
                                  'runtime.gc_pause_pct'])
def test_the_new_layer_metrics_read_the_served_stats(served_stats, name):
    """Each file loads and reads a number from a served /stats against
    the server's start (every accumulator at zero), and None from a
    server without the keys it reads, as a parent's would be."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from perfbench import manifest as manifest_lib
    spec = manifest_lib.per_layer(manifest_lib.load(),
                                  'mistral-7b-l16.chat-r2')
    spec = next(m['spec'] for m in spec if m['name'] == name)
    assert spec['name'] == name
    read = manifest_lib.reader(spec['reader']).read
    stats, _ = served_stats
    start = {'loop_s': 0.0, 'dispatch_phases': {}, 'gc': {}}
    value = read({'stats_open': start, 'stats_close': stats},
                 **spec['args'])
    assert value is not None and value >= 0
    if name.endswith('_ms'):
        assert 0 < value < 10_000
    older = {k: v for k, v in stats.items()
             if k not in ('dispatch_phases', 'gc')}
    assert read({'stats_open': {'loop_s': 0.0}, 'stats_close': older},
                **spec['args']) is None


def test_the_gc_hook_is_hooked_once_and_goes_with_the_server():
    import gc

    from skypilot_tpu.inference.http_server import make_server
    from skypilot_tpu.inference.runtime import InferenceRuntime
    model, params = _tiny()
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=64, spec_total=64, speculative=0)
    servers = [make_server(rt, 0) for _ in range(2)]
    for server in servers:
        threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        assert gc.callbacks.count(tracing.gc_pauses) == 1
        before = tracing.gc_pauses.stats()['gen2']
        gc.collect()
        after = tracing.gc_pauses.stats()['gen2']
        assert after['n'] >= before['n'] + 1
        assert after['s'] > before['s'] and after['max_s'] > 0
    finally:
        for server in servers:
            server.shutdown()
            server.server_close()
    assert tracing.gc_pauses not in gc.callbacks


def test_recovery_is_a_phase_of_the_loop():
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    from skypilot_tpu.robustness import faults
    model, params = _tiny()
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64)
    try:
        faults.install_plan({'rules': [
            {'point': 'engine.decode_step', 'action': 'raise',
             'exc': 'RuntimeError', 'message': 'once', 'times': 1}]})
        out = engine.submit([1, 2, 3, 4], max_new_tokens=4
                            ).result(timeout=240)
        assert len(out) == 8
    finally:
        faults.clear()
        engine.stop()
    assert engine.phases.n('engine.recover') == 1
    total = sum(r['s'] for r in engine.phase_stats().values())
    assert total == pytest.approx(engine.loop_s, rel=0.02)


# ---------------------------------------------------------------------------
# the trainer's record fields
# ---------------------------------------------------------------------------
def test_step_record_phase_fields_sum_to_the_step_time(tmp_path):
    from skypilot_tpu.observability.step_metrics import (StepMetrics,
                                                         read_jsonl)
    path = tmp_path / 'steps.jsonl'
    with StepMetrics(str(path), peak_flops=1e12) as emitter:
        emitter.log(5, step_time_s=0.2173, tokens=16384, loss=10.8,
                    phase_s={'data_s': 0.0004, 'dispatch_s': 0.0011,
                             'sync_s': 0.2151, 'ckpt_s': 0.0})
        emitter.log(10, step_time_s=0.25, tokens=16384, loss=10.7)
    first, second = read_jsonl(str(path))
    fields = ('data_s', 'dispatch_s', 'sync_s', 'ckpt_s', 'other_s')
    assert sum(first[f] for f in fields) == \
        pytest.approx(first['step_time_s'], abs=1e-6)
    assert first['other_s'] == pytest.approx(0.0007, abs=1e-6)
    assert not any(f in second for f in fields)


# ---------------------------------------------------------------------------
# SKY007 knows the phase
# ---------------------------------------------------------------------------
def _sky007(src):
    return [(f.rule, f.line)
            for f in analysis.run_source(src, 'snippet.py', ['SKY007'])]


def test_sky007_accepts_a_phase_under_with():
    assert _sky007('''\
from skypilot_tpu.observability import tracing

def loop(clock):
    with tracing.phase('engine.admit', clock) as ph:
        pass
    return ph.dur
''') == []


def test_sky007_flags_a_bare_phase_call():
    assert _sky007('''\
from skypilot_tpu.observability import tracing

def loop(clock):
    tracing.phase('engine.admit', clock)
''') == [('SKY007', 4)]


# ---------------------------------------------------------------------------
# A token stream's terminal event (serve_lm --stream-final)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('stream_final', ['rows', 'lengths'])
def test_a_streams_terminal_event_is_what_stream_final_says(stream_final):
    """`rows` (the default) repeats the full rows, as the non-streaming
    endpoint returns them; `lengths` gives their lengths and no
    `tokens` key: every generated token has been streamed either way."""
    from skypilot_tpu.inference.http_server import make_server
    from skypilot_tpu.inference.runtime import InferenceRuntime
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    model, params = _tiny()
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      max_total_len=64, prefill_chunk=8)
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=64, spec_total=64, speculative=0, engine=engine,
        stream_final=stream_final)
    server = make_server(rt, 0)
    url = f'http://127.0.0.1:{server.server_address[1]}'
    threading.Thread(target=server.serve_forever, daemon=True).start()
    prompt = list(range(1, 12))
    try:
        req = urllib.request.Request(
            f'{url}/generate',
            data=json.dumps({'tokens': [prompt], 'max_new_tokens': 5,
                             'temperature': 0, 'stream': True}).encode(),
            headers={'Content-Type': 'application/json'})
        lines = urllib.request.urlopen(req, timeout=240).read().decode()
    finally:
        engine.stop()
        server.shutdown()
    events = [json.loads(line[len('data: '):])
              for line in lines.splitlines()
              if line.startswith('data: ') and line != 'data: [DONE]']
    streamed = [e['token'] for e in events if 'token' in e]
    terminal = events[-1]
    assert terminal['done'] is True and len(streamed) == 5
    if stream_final == 'rows':
        assert terminal == {'done': True, 'tokens': [prompt + streamed]}
    else:
        assert terminal == {'done': True, 'lengths': [len(prompt) + 5]}


def test_stream_final_takes_its_two_values_only():
    from skypilot_tpu.inference.runtime import InferenceRuntime
    with pytest.raises(ValueError, match='stream_final'):
        InferenceRuntime(model=None, params=None, vocab_size=1,
                         model_name='x', max_total_len=8, spec_total=8,
                         speculative=0, stream_final='none')
