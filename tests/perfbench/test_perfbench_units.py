"""The benchmark's own arithmetic, on the CPU: trace reduction,
percentiles, the open-loop schedule, request accounting, the manifest
and its data files, the configuration shim. No chip, no topology call."""
import glob
import json
import math
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from perfbench import accounting
from perfbench import flops
from perfbench import harness
from perfbench import manifest as manifest_lib
from perfbench import peaks
from perfbench import schedule
from perfbench import shim
from perfbench import stats
from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = manifest_lib.ROOT
MANIFEST = manifest_lib.load()


# -- trace reduction ----------------------------------------------------------
@pytest.mark.parametrize('intervals, merged, busy', [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)], 2),                 # a gap
    ([(0, 2), (1, 3)], [(0, 3)], 3),                         # overlap
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)], 10),              # nesting
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)], 3),         # touching
    ([(1, 1), (3, 2)], [], 0),                               # empty
])
def test_union_and_busy(intervals, merged, busy):
    assert trace_reduce.union(intervals) == merged
    assert trace_reduce.busy_seconds(intervals) == busy


def test_gaps_between_merged_intervals():
    assert trace_reduce.gaps([(0, 1), (0.5, 2), (3, 4), (6, 7)]) == [
        (2, 3), (4, 6)]


def test_self_time_takes_nested_children_off_the_parent():
    events = [('while', 0.0, 10.0), ('dot', 1.0, 2.0), ('dot', 4.0, 3.0),
              ('fusion', 4.5, 1.0), ('copy', 12.0, 1.0)]
    own = trace_reduce.self_times(events)
    assert own == pytest.approx(
        {'while': 5.0, 'dot': 4.0, 'fusion': 1.0, 'copy': 1.0})
    assert sum(own.values()) == pytest.approx(
        trace_reduce.busy_seconds((s, s + d) for _, s, d in events))


def test_reduce_events_idle_share_and_breakdown():
    per_device = {
        'd0': [('m1: a', 0.0, 1.0), ('m2: b', 1.5, 0.5),
               ('m1: a', 3.0, 1.0)],
        'd1': [('m1: a', 0.0, 4.0)],
    }
    out = trace_reduce.reduce_events(per_device)
    assert out['devices'] == 2
    assert out['busy_s'] == pytest.approx((2.5 + 4.0) / 2)
    assert out['window_s'] == pytest.approx(4.0)
    assert out['idle_pct'] == pytest.approx(100 * (1 - 3.25 / 4.0))
    assert out['device_ops'][0] == ['m1: a', pytest.approx(3.0)]
    assert out['idle_gaps'][0] == [
        'host: unattributed (m2 -> m1)', pytest.approx(1.0)]
    assert out['idle_gaps'][1] == [
        'host: unattributed (m1 -> m2)', pytest.approx(0.5)]
    assert trace_reduce.reduce_events({'d0': []}) is None


def test_recorded_tpu_trace():
    """A trace of three calls of one small jitted function, 2 ms apart,
    recorded on a TPU v5e (PR 24) and kept beside this test."""
    path = os.path.join(HERE, 'data', 'tiny_v5e.xplane.pb')
    per_device, seen, extra = trace_reduce.read_xplane(path)
    assert any(s.startswith('/device:TPU:0') for s in seen)
    out = trace_reduce.reduce_events(per_device)
    assert out is not None and out['devices'] == 1
    assert 0 < out['busy_s'] < out['window_s']
    assert 0 < out['idle_pct'] < 100
    # Three calls leave two gaps of at least the 2 ms the host slept.
    assert len(out['idle_gaps']) >= 2
    assert out['idle_gaps'][1][1] >= 0.002
    assert 1 <= len(out['device_ops']) <= 10
    assert out['device_ops'][0][0].startswith('jit__lambda: %fusion = ')
    assert out['idle_gaps'][0][0] == (
        'host: unattributed (jit__lambda -> jit__lambda)')
    assert all(len(name) <= 120 for name, _ in out['device_ops'])
    assert sum(sec for _, sec in out['device_ops']) <= out['busy_s'] * 1.001


# -- percentiles --------------------------------------------------------------
@pytest.mark.parametrize('values, q, want', [
    ([1, 2, 3, 4, 5], 0.5, 3),
    ([1, 2, 3, 4], 0.5, 2.5),
    (list(range(101)), 0.95, 95),
    ([10, 20], 0.95, 19.5),
    ([7], 0.95, 7),
    ([], 0.95, None),
])
def test_percentile_interpolates(values, q, want):
    got = stats.percentile(values, q)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize('n, q, ok', [
    (199, 0.95, False), (200, 0.95, True), (1000, 0.99, True),
    (999, 0.99, False), (20, 0.5, True), (19, 0.5, False)])
def test_ten_samples_beyond_rule(n, q, ok):
    assert stats.tail_is_supported(n, q) is ok


# -- schedule -----------------------------------------------------------------
CHAT = manifest_lib.mix('chat-r2')


def test_schedule_is_a_pure_function_of_seed_rate_and_window():
    a = schedule.build(CHAT, 3000000001, 30.0, 5.0, 32768)
    b = schedule.build(CHAT, 3000000001, 30.0, 5.0, 32768)
    c = schedule.build(CHAT, 3000000002, 30.0, 5.0, 32768)
    assert a == b and a != c
    assert len(a) == len(c) == 150
    assert all(0.0 <= r['due'] < 30.0 for r in a)
    assert [r['due'] for r in a] == sorted(r['due'] for r in a)


def test_every_seed_gets_the_same_sizes_at_the_same_due_times():
    a = schedule.build(CHAT, 1, 30.0, 5.0, 32768)
    b = schedule.build(CHAT, 2 ** 31 + 17, 30.0, 5.0, 32768)
    shape = lambda reqs: [(r['due'], len(r['prompt']), r['max_new_tokens'])
                          for r in reqs]
    assert shape(a) == shape(b)
    assert [r['prompt'] for r in a] != [r['prompt'] for r in b]
    # Arrivals fill the window: the gaps sum to it.
    due = [r['due'] for r in a]
    assert due[0] == 0.0 and 29.0 < due[-1] < 30.0


def test_lengths_follow_the_mix():
    reqs = schedule.build(CHAT, 5, 40.0, 5.0, 32768)
    prompts = sorted(len(r['prompt']) for r in reqs)
    outs = sorted(r['max_new_tokens'] for r in reqs)
    assert prompts[0] >= 32 and prompts[-1] <= 1536
    assert outs[0] >= 16 and outs[-1] <= 384
    assert abs(prompts[len(prompts) // 2] - 256) <= 8
    assert abs(outs[len(outs) // 2] - 96) <= 4
    assert all(1 <= t < 32768 for r in reqs for t in r['prompt'])


@pytest.mark.parametrize('change', [
    {'prompt_tokens': {'dist': 'zipf'}}, {'arrivals': {'process': 'gamma'}}])
def test_a_mix_option_the_generator_does_not_have_is_an_error(change):
    with pytest.raises(ValueError):
        schedule.build(dict(CHAT, **change), 1, 10.0, 2.0, 1000)


def test_warmup_touches_every_prefill_shape():
    sizes = [len(r['prompt'])
             for r in schedule.warmup(CHAT, 1, 32768, 256)]
    assert sizes == [32, 64, 128, 256, 264, 272, 288, 320, 384, 512]
    tiny = dict(CHAT, prompt_tokens=dict(CHAT['prompt_tokens'],
                                         min=8, max=160))
    assert [len(r['prompt']) for r in schedule.warmup(
        tiny, 1, 512, 256)] == [8, 16, 32, 64, 128, 160]


# -- accounting ---------------------------------------------------------------
def _rec(due, sent, arrivals, max_new, end='done', status=200):
    return {'id': 0, 'due': due, 'sent': sent, 'status': status,
            'arrivals': arrivals, 'tokens': [1] * len(arrivals),
            'max_new_tokens': max_new, 'end': end, 'prompt_tokens': 4}


def test_latency_is_taken_from_the_due_time():
    rec = _rec(1.0, 1.4, [2.0, 2.1, 2.3], 3)
    assert accounting.ttft_s(rec, 10.0) == pytest.approx(1.0)
    assert accounting.token_gaps_s(rec) == pytest.approx([0.1, 0.2])


def test_attempted_and_failed_with_a_refused_and_a_cut_request():
    records = [
        _rec(0.0, 0.0, [0.5, 0.6], 2),
        _rec(1.0, 1.0, [], 2, end='refused', status=429),
        _rec(2.0, 2.0, [2.5], 2, end='cut'),
        _rec(3.0, 3.0, [3.5], 2, end='done'),          # short: 1 of 2
        _rec(9.5, 9.5, [10.5, 10.6], 2),               # finishes in drain
    ]
    s = accounting.summarize(records, 10.0)
    assert (s['attempted'], s['failed']) == (5, 3)
    assert s['ends'] == {'done': 3, 'refused': 1, 'cut': 1}
    # Failed requests count as the window's length.
    assert s['ttft_p95_ms'] == pytest.approx(10000.0)
    # Only tokens that arrived inside the window count for the rate.
    assert s['tokens_in_window'] == 4
    assert s['serve_tokens_per_s'] == pytest.approx(0.4)
    assert accounting.backlog(records, 5.0) == 3
    assert accounting.backlog(records, 10.0) == 4


class _FakeServer(BaseHTTPRequestHandler):
    """Streams like /generate; the prompt's first token picks the case."""

    def log_message(self, *a):
        pass

    def do_POST(self):  # noqa: N802
        body = json.loads(self.rfile.read(
            int(self.headers['Content-Length'])))
        case, n = body['tokens'][0][0], body['max_new_tokens']
        if case == 2:
            self.send_response(429)
            self.end_headers()
            self.wfile.write(b'{"error": "full"}')
            return
        self.send_response(200)
        self.send_header('Content-Type', 'text/event-stream')
        self.send_header('Connection', 'close')
        self.end_headers()
        for i in range(n - 1 if case == 4 else n):
            self.wfile.write(b'data: ' + json.dumps(
                {'index': 0, 'token': 7}).encode() + b'\n\n')
            self.wfile.flush()
            if case == 3:
                time.sleep(30)              # stalls: cut by the drain
            time.sleep(0.01)
        self.wfile.write(b'data: {"done": true, "tokens": [[]]}\n\n'
                         b'data: [DONE]\n\n')


def test_load_generator_records_every_ending(tmp_path):
    server = ThreadingHTTPServer(('127.0.0.1', 0), _FakeServer)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    plan = {'base': f'http://127.0.0.1:{server.server_port}',
            'open_at': time.time() + 0.3, 'send_until': 1.0,
            'drain_s': 1.0,
            'requests': [{'id': i, 'due': 0.2 * i, 'prompt': [case, 9],
                          'max_new_tokens': 3}
                         for i, case in enumerate([1, 2, 3, 4, 1])]}
    (tmp_path / 'plan.json').write_text(json.dumps(plan))
    out = tmp_path / 'records.jsonl'
    subprocess.run([sys.executable,
                    os.path.join(ROOT, 'perfbench', 'loadgen.py'),
                    '--plan', str(tmp_path / 'plan.json'),
                    '--out', str(out)], check=True, timeout=60)
    server.shutdown()
    records = [json.loads(l) for l in out.read_text().splitlines()]
    assert [r['end'] for r in records] == [
        'done', 'refused', 'cut', 'done', 'done']
    assert [accounting.failed(r) for r in records] == [
        False, True, True, True, False]
    assert records[1]['status'] == 429
    assert len(records[2]['arrivals']) == 1
    for r in records:
        assert r['sent'] >= r['due'] and r['sent'] - r['due'] < 0.25
    s = accounting.summarize(records, 1.0)
    assert (s['attempted'], s['failed']) == (5, 3)


# -- the manifest and its data files ------------------------------------------
def _names(m):
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        for entry in m[group]:
            yield entry['name']
    for w in m['workloads']:
        yield w['config']
        yield w['traffic']
    for c in m['configs']:
        yield from c['reduced']


def test_manifest_names_units_and_lengths_are_what_the_driver_accepts(m=MANIFEST):
    assert set(m) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    for name in _names(m):
        assert manifest_lib.NAME_RE.match(name), name
    for metric in m['end_to_end'] + m['per_layer']:
        assert manifest_lib.UNIT_RE.match(metric['unit']), metric
        assert metric['better'] in ('lower', 'higher')
        assert metric['source'] in ('device_trace', 'program_span',
                                    'program_counter', 'host_clock')
    for metric in m['end_to_end']:
        assert metric['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= metric['bound'] <= 0.1
        assert set(metric) <= {'name', 'unit', 'better', 'bound',
                               'source', 'workloads'}
    for metric in m['per_layer']:
        assert set(metric) <= {'name', 'unit', 'better', 'source',
                               'layer', 'moves', 'workloads'}
        assert 1 <= len(metric['layer']) <= 200
    for entry in m['configs'] + m['workloads']:
        assert 1 <= len(entry['why']) <= 200 and '\n' not in entry['why']
    assert 1 <= m['run_seconds'] <= 51
    assert all(w['chips'] == 1 for w in m['workloads'])
    assert len(json.dumps(m)) < 64 * 1024
    names = [e['name'] for g in ('end_to_end', 'per_layer')
             for e in m[g]]
    assert len(names) == len(set(names))
    assert 'setup_s' in names


def test_every_moves_names_a_metric_each_of_its_cells_reports(m=MANIFEST):
    cells = [w['name'] for w in m['workloads']]
    for layer_metric in m['per_layer']:
        target = next(e for e in m['end_to_end']
                      if e['name'] == layer_metric['moves'])
        for cell in layer_metric.get('workloads', cells):
            assert cell in cells
            assert cell in target.get('workloads', cells), (
                layer_metric['name'], cell)
    for cell in cells:
        e2e = [e['name'] for e in manifest_lib.end_to_end(m, cell)]
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert manifest_lib.per_layer(m, cell)
    used = {w['config'] for w in m['workloads']}
    assert used == {c['name'] for c in m['configs']}


def test_every_file_the_manifest_names_loads(m=MANIFEST):
    for c in m['configs']:
        assert c['file'].startswith('perfbench/configs/')
        cfg = manifest_lib.config(m, c['name'])
        assert cfg['source'] == c['source']
        assert cfg['reduced'] == c['reduced']
        assert flops.params(cfg) > 0
    for w in m['workloads']:
        mix = manifest_lib.mix(w['traffic'])
        assert hasattr(manifest_lib.driver(mix['driver']), 'run')
    for metric in m['per_layer']:
        for cell in metric.get('workloads',
                               [w['name'] for w in m['workloads']]):
            spec = next(p['spec'] for p in manifest_lib.per_layer(m, cell)
                        if p['name'] == metric['name'])
            assert spec['name'] == metric['name']
            assert hasattr(manifest_lib.reader(spec['reader']), 'read')


def test_files_under_paths_are_named_from_a_names_characters():
    for base in MANIFEST['paths']:
        for path in glob.glob(os.path.join(ROOT, base, '**'),
                              recursive=True):
            if '__pycache__' in path:
                continue
            rel = os.path.relpath(path, ROOT)
            assert all(ch.isalnum() or ch in '_.-/' for ch in rel), rel


def test_unknown_cell_is_an_error():
    with pytest.raises(manifest_lib.ManifestError):
        manifest_lib.cell(MANIFEST, 'no-such-cell')


# -- sizes, peaks, readers ----------------------------------------------------
def test_sizes_counted_from_the_configuration_files():
    gpt2 = manifest_lib.config(MANIFEST, 'gpt2-124m')
    assert flops.params(gpt2) == 124_475_904
    assert flops.train_flops_per_token(gpt2, 1024) == pytest.approx(
        6 * 124_475_904 + 12 * 12 * 1024 * 768)


def test_peaks_table_has_no_default():
    assert peaks.peak('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    for kind in ('cpu', 'TPU v9', '_source'):
        with pytest.raises(KeyError):
            peaks.peak(kind)


class _Chip:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize('chips, want', [
    ([{'peak_bytes_in_use': 10, 'peak_bytes_reserved': 5},
      {'peak_bytes_in_use': 12, 'peak_bytes_reserved': 1}], 15),
    ([{'peak_bytes_in_use': 10}], None),          # a key is missing
    ([None], None)])                              # no statistics at all
def test_memory_peak_is_the_allocators_measured_sum(monkeypatch, chips,
                                                    want):
    import jax

    class Died(Exception):
        pass

    def die(ctx, msg, code=1):
        raise Died(msg)

    monkeypatch.setattr(jax, 'local_devices',
                        lambda: [_Chip(c) for c in chips])
    monkeypatch.setattr(harness, 'die', die)
    ctx = harness.Ctx(args=type('A', (), {'rehearse': False})(),
                      manifest={}, cell={}, config={}, mix={},
                      t_start=0.0, work='')
    if want is None:
        with pytest.raises(Died):
            harness.memory_peak_bytes(ctx)
    else:
        assert harness.memory_peak_bytes(ctx) == want


def test_readers_on_hand_made_sources():
    read = lambda name, src, **kw: manifest_lib.reader(name).read(src, **kw)
    stats_src = {'stats_open': {'decode_calls': 100,
                                'tokens_committed': 1000},
                 'stats_close': {'decode_calls': 600,
                                 'tokens_committed': 13000,
                                 'num_slots': 32},
                 'harness': {'window_s': 10.0, 'stats_span_s': 10.0,
                             'ready_s': 12.5}}
    assert read('window_per_count', stats_src, counter='decode_calls',
                scale=1000.0) == pytest.approx(20.0)
    assert read('counter_share', stats_src, numerator='tokens_committed',
                denominator='decode_calls', per='num_slots',
                scale=100.0) == pytest.approx(75.0)
    assert read('harness_value', stats_src, key='ready_s') == 12.5
    assert read('harness_value', {}, key='ready_s') is None
    assert read('window_per_count', {}, counter='x') is None
    assert read('records_median', {'records': [
        {'step_time_s': 0.2}, {'step_time_s': 0.4}, {'step_time_s': 0.3}]},
        field='step_time_s', scale=1000.0) == pytest.approx(300.0)
    assert read('stdout_regex', {'stdout': [
        'x', 'setup: init 27.1s, first step (compile + run) 6.0s, c']},
        pattern=r'first step \(compile \+ run\) ([0-9.]+)s') == 6.0
    assert read('trace_idle', {'trace': None}) is None
    assert read('trace_idle', {'trace': {'idle_pct': 12.5}}) == 12.5
    gpt2 = manifest_lib.config(MANIFEST, 'gpt2-124m')
    mfu = read('train_mfu', {
        'end_to_end': {'train_tokens_per_s': 60000.0}, 'config': gpt2,
        'mix': manifest_lib.mix('pretrain'),
        'device': {'kind': 'TPU v5 lite'}})
    assert mfu == pytest.approx(100 * 60000 * 860_101_632 / 197e12)


def test_train_window_and_verdict():
    spec = manifest_lib.driver('train_job')
    recs = [{'step': 5 * (i + 1), 'time': 100.0 + 2.0 * i,
             'loss': 10.97 - 0.01 * i} for i in range(12)]
    assert spec.window_of(recs[:1], 10.0) == (None, None)
    assert spec.window_of(recs[:4], 10.0) == (1, None)
    assert spec.window_of(recs, 10.0) == (1, 6)
    judge = lambda: {name: harness.holds(entry) for name, entry
                     in spec.judge(recs, 1, 6, 50304, 5).items()}
    assert judge() == {'losses_not_finite': True, 'last_loss': True,
                       'steps_missing': True}
    # Every number compared stands beside its limit.
    assert spec.judge(recs, 1, 6, 50304, 5)['last_loss'] == {
        'value': recs[6]['loss'], 'at_least': math.log(50304) - 0.05,
        'at_most': math.nextafter(recs[0]['loss'], -math.inf)}
    recs[4]['step'] += 5
    assert not judge()['steps_missing']
    recs[4]['step'] -= 5
    recs[6]['loss'] = math.log(50304) - 0.2
    assert not judge()['last_loss']                # under the floor
    recs[6]['loss'] = recs[0]['loss']
    assert not judge()['last_loss']                # did not fall
    recs[6]['loss'] = float('nan')
    assert not judge()['last_loss'] and not judge()['losses_not_finite']


@pytest.mark.parametrize('entry, ok', [
    ({'value': 0.2, 'at_most': 0.5}, True),
    ({'value': 0.5, 'at_most': 0.5}, True),
    ({'value': 0.6, 'at_most': 0.5}, False),
    ({'value': 3, 'at_least': 1}, True),
    ({'value': 0, 'at_least': 1}, False),
    ({'value': 2, 'at_least': 1, 'at_most': 1.5}, False),
    ({'value': None, 'at_most': 0}, False),
    ({'value': float('nan'), 'at_most': 0}, False),
    ({'value': float('inf'), 'at_least': 0}, False)])
def test_a_compared_number_holds_its_limits(entry, ok):
    assert harness.holds(entry) is ok


# -- the configuration shim ---------------------------------------------------
def test_shim_builds_mistral_7b_l16_and_leaves_registry_names_alone():
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.recipes import train_lm
    before = train_lm._build_model
    shim.install()
    try:
        shim.install()                       # twice is once
        model, vocab, _ = train_lm._build_model('mistral-7b-l16', 2048,
                                                False)
        cfg = model.config
        assert (vocab, cfg.num_layers, cfg.embed_dim, cfg.mlp_dim,
                cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                cfg.rope_theta, cfg.norm_eps) == (
            32768, 16, 4096, 14336, 32, 8, 128, 1e6, 1e-5)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.ones((1, 8), jnp.int32))['params'])
        n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
        assert n == 3_758_231_552
        assert n == flops.params(
            manifest_lib.config(MANIFEST, 'mistral-7b-l16'))
        tiny, tiny_vocab, _ = train_lm._build_model('llama-tiny', 64, False)
        assert tiny_vocab == 512 and tiny.config.num_layers == 2
        gpt, gpt_vocab, _ = train_lm._build_model('gpt2-124m', 1024, False)
        assert gpt_vocab == 50304 and type(gpt).__name__ == 'GPT'
        with pytest.raises(Exception):
            train_lm._build_model('no-such-model', 64, False)
    finally:
        shim.uninstall()
    assert train_lm._build_model is before


# -- the plain reference ------------------------------------------------------
def test_plain_llama_reference_agrees_with_the_program_in_float32():
    """The reference shares no code with models/llama.py; at float32 the
    two agree to rounding (1e-4 on log-probabilities of size ~6: a
    dropped mask, RoPE convention, norm or GQA grouping is off by
    0.1 and more)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    program = LlamaConfig.tiny(dtype=jnp.float32)
    model = Llama(program)
    tokens = np.random.RandomState(0).randint(1, 512, (1, 48))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(1), jnp.asarray(tokens, jnp.int32))['params'])
    want = jax.nn.log_softmax(model.apply(
        {'params': params}, jnp.asarray(tokens, jnp.int32))[0], axis=-1)
    cfg = {'num_hidden_layers': 2, 'num_attention_heads': 4,
           'num_key_value_heads': 2, 'rope_theta': program.rope_theta,
           'rms_norm_eps': program.norm_eps}
    got = manifest_lib.reference('llama').log_probs(
        params, cfg, tokens[0].tolist())
    assert got.shape == (48, 512)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    # Causal: cutting the row leaves the kept positions' scores alone.
    cut = manifest_lib.reference('llama').log_probs(
        params, cfg, tokens[0, :20].tolist())
    assert float(jnp.max(jnp.abs(cut - got[:20]))) < 1e-4
