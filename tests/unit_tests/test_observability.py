"""Unified metrics layer: registry primitives (concurrency, histogram
bucketing, exposition escaping), end-to-end scrapes of both /metrics
endpoints (API server + inference server), and the trainer's JSONL
step-metrics round-trip.

The test-side Prometheus parser below is intentionally independent of
the production renderer (it validates the FORMAT, not just
self-consistency)."""
import json
import re
import threading
import urllib.request

import pytest

from skypilot_tpu.observability import metrics as m
from skypilot_tpu.observability import catalog


# ---------------------------------------------------------------------------
# test-side exposition parser
# ---------------------------------------------------------------------------
_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_][a-zA-Z0-9_]*)'
    r'(?:\{(?P<labels>.*)\})?'
    r' (?P<value>[^ ]+)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prom(text):
    """Parse text exposition → ({(name, labels_frozenset): value},
    {family: type}). Raises on malformed lines (the acceptance
    criterion: the endpoints emit PARSEABLE exposition)."""
    samples = {}
    types = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith('# TYPE '):
            _, _, family, typ = line.split(' ', 3)
            assert typ in ('counter', 'gauge', 'histogram', 'untyped')
            types[family] = typ
            continue
        if line.startswith('#'):
            continue
        match = _SAMPLE_RE.match(line)
        assert match, f'malformed sample line: {line!r}'
        labels = {}
        if match.group('labels'):
            consumed = _LABEL_RE.findall(match.group('labels'))
            labels = {k: v.replace('\\"', '"').replace('\\n', '\n')
                      .replace('\\\\', '\\') for k, v in consumed}
        raw = match.group('value')
        specials = {'NaN': float('nan'), '+Inf': float('inf'),
                    '-Inf': float('-inf')}
        value = specials[raw] if raw in specials else float(raw)
        samples[(match.group('name'),
                 frozenset(labels.items()))] = value
    return samples, types


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
def test_counter_concurrent_increments():
    reg = m.Registry()
    counter = reg.get_or_create(m.Counter, 'skypilot_test_total',
                                'concurrency test', ('worker',))
    n_threads, per_thread = 8, 5000

    def worker(i):
        child = counter.labels(worker=str(i % 2))
        for _ in range(per_thread):
            child.inc()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = (counter.labels(worker='0').value +
             counter.labels(worker='1').value)
    assert total == n_threads * per_thread
    with pytest.raises(ValueError):
        counter.labels(worker='0').inc(-1)  # counters only go up


def test_gauge_and_histogram_bucketing():
    reg = m.Registry()
    gauge = reg.get_or_create(m.Gauge, 'skypilot_test_gauge', 'g')
    gauge.set(5)
    gauge.inc(2)
    gauge.dec()
    assert gauge.value == 6

    hist = reg.get_or_create(m.Histogram, 'skypilot_test_seconds',
                             'h', (), buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 5.0, 50.0):
        hist.observe(v)
    samples, types = parse_prom(reg.render())
    assert types['skypilot_test_seconds'] == 'histogram'

    def bucket(le):
        return samples[('skypilot_test_seconds_bucket',
                        frozenset({('le', le)}))]

    assert bucket('0.1') == 2      # cumulative
    assert bucket('1') == 3
    assert bucket('10') == 4
    assert bucket('+Inf') == 5
    assert samples[('skypilot_test_seconds_count', frozenset())] == 5
    assert samples[('skypilot_test_seconds_sum',
                    frozenset())] == pytest.approx(55.6)


def test_exposition_escaping_roundtrip():
    reg = m.Registry()
    gauge = reg.get_or_create(m.Gauge, 'skypilot_test_escape',
                              'help with \\ backslash\nand newline',
                              ('path',))
    hostile = 'a"b\\c\nd'
    gauge.labels(path=hostile).set(1)
    text = reg.render()
    assert '\n\n' not in text.strip()  # escaped newline stays in-line
    samples, _ = parse_prom(text)
    assert samples[('skypilot_test_escape',
                    frozenset({('path', hostile)}))] == 1


def test_registry_conflicting_redeclaration_raises():
    reg = m.Registry()
    reg.get_or_create(m.Counter, 'skypilot_test_total', 'x', ('a',))
    # Same shape → same instance (idempotent).
    again = reg.get_or_create(m.Counter, 'skypilot_test_total', 'x',
                              ('a',))
    assert again is reg.get(name='skypilot_test_total')
    with pytest.raises(ValueError):
        reg.get_or_create(m.Gauge, 'skypilot_test_total', 'x', ('a',))
    with pytest.raises(ValueError):
        reg.get_or_create(m.Counter, 'skypilot_test_total', 'x',
                          ('a', 'b'))
    with pytest.raises(ValueError):
        reg.get_or_create(m.Counter, 'Bad-Name', 'x')


def test_catalog_instruments_constructible():
    """Every cataloged metric materializes in the default registry
    with its declared kind."""
    for name, spec in catalog.SPECS.items():
        metric = catalog._create(name)
        expected = {'counter': m.Counter, 'gauge': m.Gauge,
                    'histogram': m.Histogram,
                    'gauge_as_counter': m.Gauge}[spec[0]]
        assert type(metric) is expected, name


# ---------------------------------------------------------------------------
# end-to-end scrapes
# ---------------------------------------------------------------------------
def test_api_server_metrics_scrape(isolated_state):
    """GET /api/metrics returns parseable exposition including the
    orchestration gauges AND the per-route middleware series."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from skypilot_tpu.server import server as api_server

    async def scrape():
        app = api_server.create_app()
        async with TestClient(TestServer(app)) as client:
            assert (await client.get('/api/health')).status == 200
            resp = await client.get('/api/metrics')
            assert resp.status == 200
            return await resp.text()

    text = asyncio.new_event_loop().run_until_complete(scrape())
    samples, types = parse_prom(text)
    assert types['skypilot_services'] == 'gauge'
    assert types['skypilot_requests_total'] == 'counter'
    assert ('skypilot_services', frozenset()) in samples
    assert ('skypilot_server_rss_bytes', frozenset()) in samples
    assert samples[('skypilot_server_rss_bytes', frozenset())] > 0
    # Per-route middleware: the /api/health hit above is counted.
    key = ('skypilot_api_requests_total',
           frozenset({('route', '/api/health'), ('method', 'GET'),
                      ('code', '200')}))
    assert samples[key] >= 1
    assert types['skypilot_api_request_seconds'] == 'histogram'
    assert ('skypilot_api_requests_in_flight', frozenset()) in samples


@pytest.fixture(scope='module')
def tiny_inference_server():
    """A live inference HTTP server over a tiny llama + continuous
    engine (paged, prefix caching) on an ephemeral port."""
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
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f'http://127.0.0.1:{port}', engine
    server.shutdown()
    engine.stop()


def test_inference_metrics_scrape(tiny_inference_server):
    """POST /generate then scrape /metrics: engine internals (queue,
    slots, page pool, prefix cache) and the request-path histograms
    (TTFT recorded for the NON-streaming request) all expose."""
    url, engine = tiny_inference_server
    req = urllib.request.Request(
        f'{url}/generate',
        data=json.dumps({'tokens': [[1, 2, 3, 4, 5, 6, 7, 8, 9]],
                         'max_new_tokens': 5}).encode(),
        headers={'Content-Type': 'application/json'})
    out = json.loads(urllib.request.urlopen(req, timeout=240).read())
    assert len(out['tokens'][0]) == 14

    text = urllib.request.urlopen(f'{url}/metrics',
                                  timeout=30).read().decode()
    samples, types = parse_prom(text)
    eng = frozenset({('engine', engine.engine_id)})
    assert samples[('skypilot_serving_admissions_total', eng)] >= 1
    assert samples[('skypilot_serving_tokens_committed_total',
                    eng)] >= 5
    assert samples[('skypilot_serving_num_slots', eng)] == 2
    assert samples[('skypilot_serving_queue_depth', eng)] == 0
    assert samples[('skypilot_serving_pages_free', eng)] >= 1
    assert ('skypilot_serving_prefix_cache_hits_total',
            eng) in samples
    assert types['skypilot_serving_decode_step_seconds'] == 'histogram'
    assert samples[('skypilot_serving_decode_step_seconds_count',
                    eng)] >= 1
    # Request path: non-streaming TTFT + token counters.
    assert samples[('skypilot_serving_ttft_seconds_count',
                    frozenset())] >= 1
    assert samples[('skypilot_serving_prompt_tokens_total',
                    frozenset())] >= 9
    assert samples[('skypilot_serving_completion_tokens_total',
                    frozenset())] >= 5


def test_inference_stats_surfaces_engine_counters(
        tiny_inference_server):
    """Satellite: /stats carries prefix-cache hits/misses/evictions,
    page-pool occupancy, preemptions, and documents its window."""
    url, _ = tiny_inference_server
    stats = json.loads(urllib.request.urlopen(f'{url}/stats',
                                              timeout=30).read())
    assert stats['engine'] == 'continuous'
    assert {'hits', 'misses', 'hit_rate', 'evictions',
            'resident_unreferenced'} <= set(stats['prefix_cache'])
    assert {'total', 'free', 'used', 'utilization'} <= \
        set(stats['page_pool'])
    assert stats['preemptions'] == 0
    serving = stats['serving']
    assert serving['window'] == 1024
    assert 'itl_ms_p50' in serving
    # The non-streaming request from the scrape test recorded TTFT.
    assert serving['requests'] >= 1


# ---------------------------------------------------------------------------
# trainer step metrics
# ---------------------------------------------------------------------------
def test_train_lm_metrics_file_end_to_end(tmp_path):
    """Acceptance: `train_lm --metrics-file` writes one JSONL record
    per logged step with step_time_s, tokens_per_sec, loss (and
    grad_norm), and --trace-file captures per-phase spans."""
    import os
    import subprocess
    import sys

    from skypilot_tpu.observability.step_metrics import read_jsonl

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = tmp_path / 'steps.jsonl'
    trace = tmp_path / 'trace.json'
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, '-m', 'skypilot_tpu.recipes.train_lm',
         '--cpu', '--model', 'tiny', '--steps', '2', '--seq', '16',
         '--global-batch', '4', '--log-every', '1',
         '--metrics-file', str(out), '--trace-file', str(trace)],
        cwd=repo, env=env, capture_output=True, text=True,
        timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    records = read_jsonl(str(out))
    assert [r['step'] for r in records] == [1, 2]
    for rec in records:
        assert rec['step_time_s'] > 0
        assert rec['tokens_per_sec'] > 0
        assert rec['loss'] > 0
        assert rec['grad_norm'] is not None and rec['grad_norm'] > 0
    with open(trace, 'r', encoding='utf-8') as f:
        spans = {e['name'] for e in json.load(f)['traceEvents']}
    assert {'train/init', 'train.loop', 'train.data', 'train.dispatch',
            'train.sync', 'train.log'} <= spans


def test_step_metrics_jsonl_roundtrip(tmp_path):
    from skypilot_tpu.observability.step_metrics import (StepMetrics,
                                                         read_jsonl)
    path = tmp_path / 'metrics' / 'steps.jsonl'
    with StepMetrics(str(path), n_params=1_000_000, n_devices=2,
                     peak_flops=1e12) as emitter:
        emitter.log(10, step_time_s=0.5, tokens=4096, loss=3.25,
                    grad_norm=1.5)
        emitter.log(20, step_time_s=0.25, tokens=4096, loss=3.0)
    records = read_jsonl(str(path))
    assert [r['step'] for r in records] == [10, 20]
    first = records[0]
    assert first['step_time_s'] == 0.5
    assert first['tokens_per_sec'] == pytest.approx(8192.0)
    assert first['loss'] == 3.25
    assert first['grad_norm'] == 1.5
    # mfu = 6 * 1e6 * 8192 / (1e12 * 2)
    assert first['mfu'] == pytest.approx(0.0246, abs=1e-4)
    assert records[1]['grad_norm'] is None
    # Append mode: a resumed run extends the same file.
    with StepMetrics(str(path), n_params=None) as emitter:
        rec = emitter.log(30, step_time_s=0.1, tokens=10, loss=2.0)
        assert rec['mfu'] is None  # no param count -> no estimate
    assert len(read_jsonl(str(path))) == 3
