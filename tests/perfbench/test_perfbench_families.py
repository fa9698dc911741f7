"""What PR 27 opened: a family, a kernel's roofline and a scoring
margin come as files; the reducer keeps programs, paths and named
gaps; the readers that turn them into metrics. On the CPU, on
hand-made events and sources and on the recorded v5e trace."""
import json
import os
import shutil
import types

import pytest

from perfbench import flops
from perfbench import manifest as manifest_lib
from perfbench import shim
from perfbench import trace_reduce
from perfbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = manifest_lib.ROOT
MANIFEST = manifest_lib.load()
SERVING = ['mistral-7b-l16.chat-r2', 'mistral-7b-l16.chat-saturated-r2']
V5E = {'kind': 'TPU v5 lite'}


def _read(name, sources, **kw):
    return manifest_lib.reader(name).read(sources, **kw)


def _spec(name):
    with open(os.path.join(manifest_lib.HERE, 'layer_metrics',
                           f'{name}.json'), encoding='utf-8') as f:
        return json.load(f)


# -- a family is files --------------------------------------------------------
TOY_SIZES = '''
def params(cfg):
    return cfg['cells'] * cfg['width'] ** 2 + cfg['vocab_size'] * cfg['width']


def train_flops_per_token(cfg, seq):
    return 6.0 * params(cfg)


def serve_flops_per_token(cfg):
    return 2.0 * cfg['cells'] * cfg['width'] ** 2
'''

TOY_REFERENCE = '''
import numpy as np


def log_probs(params, cfg, tokens):
    """Token t is followed by t + 1 (mod vocab) with probability 0.9."""
    v = cfg['vocab_size']
    lp = np.full((len(tokens), v), np.log(0.1 / (v - 1)), np.float32)
    for i, t in enumerate(tokens):
        lp[i, (t + 1) % v] = np.log(0.9)
    return lp
'''


@pytest.fixture
def toy_tree(tmp_path, monkeypatch):
    """A copy of perfbench/ with a family `toy` added by NEW files
    alone, and the manifest pointed at it."""
    tree = tmp_path / 'perfbench'
    shutil.copytree(manifest_lib.HERE, tree, ignore=shutil.ignore_patterns(
        '__pycache__'))
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(tree) for p in fs}
    (tree / 'sizes' / 'toy.py').write_text(TOY_SIZES)
    (tree / 'references' / 'toy.py').write_text(TOY_REFERENCE)
    config = {'family': 'toy', 'registry_name': 'tiny', 'serve_model': 'tiny',
              'reference': 'toy', 'cells': 3, 'width': 16, 'vocab_size': 64,
              'source': 'https://example.org/toy', 'reduced': [],
              'score_margin_nats': 0.25,
              'score_margin_why': 'a toy: one near-tie in its router'}
    (tree / 'configs' / 'toy-3.json').write_text(json.dumps(config))
    monkeypatch.setattr(manifest_lib, 'HERE', str(tree))
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(tree) for p in fs}
    assert {p for p in before if before[p] != after[p]} == set()
    manifest = dict(MANIFEST, configs=MANIFEST['configs'] + [{
        'name': 'toy-3', 'file': 'perfbench/configs/toy-3.json',
        'source': config['source'], 'reduced': [], 'why': 'a toy'}])
    return manifest, str(tmp_path)


def test_a_new_family_loads_counts_and_scores_by_new_files_alone(toy_tree):
    manifest, root = toy_tree
    cfg = manifest_lib.config(manifest, 'toy-3', root=root)
    assert flops.params(cfg) == 3 * 256 + 64 * 16
    assert flops.train_flops_per_token(cfg, 128) == 6.0 * (768 + 1024)
    assert flops.serve_flops_per_token(cfg) == 2.0 * 768
    # The committed families count as before through the same door.
    assert flops.params(manifest_lib.config(MANIFEST, 'gpt2-124m')) == \
        124_475_904
    # A family with a registry name never reaches the shim's builder.
    assert shim.file_config('toy-3') is None
    driver = manifest_lib.driver('serve_open_loop')
    margin = driver.score_margin(cfg)
    assert margin['nats'] == 0.25 and 'router' in margin['why']
    ctx = types.SimpleNamespace(args=types.SimpleNamespace(seed=7))
    mix = {'score_rows': 2, 'score_max_tokens': 32}
    requests = [{'id': i, 'prompt': [1, 2, 3, 4]} for i in range(3)]
    good = [{'id': i, 'prompt_tokens': 4, 'max_new_tokens': 3,
             'tokens': [5, 6, 7], 'end': 'done', 'status': 200}
            for i in range(3)]
    rows = driver.pick_rows(ctx, mix, requests, good)
    assert len(rows) == 2 and rows[0]['tokens'] == [1, 2, 3, 4, 5, 6, 7]
    scored = driver.score_rows(cfg, None, rows, margin['nats'])
    assert scored['ok'] and scored['positions'] == 6
    assert scored['worst_shortfall_nats'] == 0.0
    # One altered token scores ln(0.9 / (0.1 / 63)) = 6.3 nats low.
    bad = [dict(r, tokens=[5, 9, 10]) for r in good]
    scored = driver.score_rows(cfg, None,
                               driver.pick_rows(ctx, mix, requests, bad),
                               margin['nats'])
    assert not scored['ok']
    assert scored['worst_shortfall_nats'] == pytest.approx(6.34, abs=0.01)


@pytest.mark.parametrize('family', ['nothing', '../flops', 'a b', ''])
def test_a_family_without_its_file_or_with_a_bad_name_is_an_error(family):
    with pytest.raises(manifest_lib.ManifestError):
        flops.params({'family': family})


def test_no_table_or_branch_is_keyed_on_a_familys_name():
    """flops.py, harness.py, the drivers and the readers name no
    family (shim.py keeps its Llama builder)."""
    paths = [os.path.join(manifest_lib.HERE, f)
             for f in ('flops.py', 'harness.py')]
    for sub in ('drivers', 'readers'):
        paths += [os.path.join(manifest_lib.HERE, sub, f)
                  for f in os.listdir(os.path.join(manifest_lib.HERE, sub))
                  if f.endswith('.py')]
    families = [f[:-3] for f in os.listdir(
        os.path.join(manifest_lib.HERE, 'sizes')) if f.endswith('.py')]
    assert set(families) >= {'gpt2', 'llama'}
    for path in paths:
        with open(path, encoding='utf-8') as f:
            text = f.read()
        for family in families:
            assert f"'{family}'" not in text and f'"{family}"' not in text, (
                path, family)


@pytest.mark.parametrize('config, want', [
    ('gpt2-124m', 2 * (12 * 12 * 768 ** 2 + 50304 * 768)),
    ('mistral-7b-l16', 2 * (3_758_231_552 - 32768 * 4096
                            - (2 * 16 + 1) * 4096))])
def test_serve_flops_leave_out_the_embedding_lookup_and_the_norms(config,
                                                                  want):
    cfg = manifest_lib.config(MANIFEST, config)
    assert flops.serve_flops_per_token(cfg) == want
    assert flops.serve_flops_per_token(cfg) < 2 * flops.params(cfg)


def test_the_sizes_did_not_move():
    assert flops.params(manifest_lib.config(MANIFEST, 'mistral-7b-l16')) \
        == 3_758_231_552
    assert flops.train_flops_per_token(
        manifest_lib.config(MANIFEST, 'gpt2-124m'), 1024) == 860_101_632


# -- the scoring margin -------------------------------------------------------
def test_the_margin_comes_from_the_configuration_with_its_why():
    driver = manifest_lib.driver('serve_open_loop')
    default = driver.score_margin({})
    assert default['nats'] == 0.5 and 'bf16' in default['why']
    # Set from the chip's readings (PERF.md section 2), with its why.
    mistral = driver.score_margin(
        manifest_lib.config(MANIFEST, 'mistral-7b-l16'))
    assert mistral['nats'] == 0.65 and 'int8' in mistral['why']
    assert mistral['from'] == 'the configuration file'
    stated = driver.score_margin({'score_margin_nats': 0.8,
                                  'score_margin_why': 'routed experts'})
    assert stated == {'nats': 0.8, 'why': 'routed experts',
                      'from': 'the configuration file'}


@pytest.mark.parametrize('cfg', [
    {'score_margin_nats': 0.8},
    {'score_margin_nats': 0.8, 'score_margin_why': '  '},
    {'score_margin_nats': 'wide', 'score_margin_why': 'because'},
    {'score_margin_nats': True, 'score_margin_why': 'because'},
    {'score_margin_nats': 0, 'score_margin_why': 'because'}])
def test_a_margin_without_its_why_or_that_is_no_number_is_refused(cfg):
    driver = manifest_lib.driver('serve_open_loop')
    with pytest.raises(manifest_lib.ManifestError):
        driver.score_margin(cfg)


# -- the reducer --------------------------------------------------------------
def _events():
    """Two programs on one device; `decode` twice, `prefill` once."""
    path = 'jit(decode)/Llama/layer_{}/attn/{}/op'
    return {'d0': [
        ('jit_decode: %a', 0.0, 1.0, path.format(0, 'paged_attention')),
        ('jit_decode: %b', 1.0, 0.5, path.format(0, 'kv_write')),
        ('jit_decode: %a', 1.5, 1.0, path.format(11, 'paged_attention')),
        ('jit_decode: %w', 2.5, 2.0, 'jit(decode)/while'),
        ('jit_decode: %c', 3.0, 1.0, path.format(3, 'paged_attention')),
        ('jit_prefill_paged: %d', 6.5, 1.5, ''),
        ('jit_decode: %a', 10.0, 1.0, path.format(1, 'paged_attention')),
    ]}


MODULES = {'d0': [('jit_decode', 0.0, 4.5), ('jit_prefill_paged', 6.5, 1.5),
                  ('jit_decode', 10.0, 1.0), ('jit_decode', 20.0, 1.0)]}
PHASES = {'python3#0': [
    ('engine.loop', 4.0, 7.0), ('engine.decode_dispatch', 4.4, 1.0),
    ('engine.fetch_wait', 4.6, 0.5), ('engine.prefill_dispatch', 5.5, 0.9),
    ('engine.first_token_sync', 8.1, 1.8)]}


def test_by_program_and_by_path_cover_the_whole_span():
    out = trace_reduce.reduce_events(_events(), modules=MODULES,
                                     phases=PHASES)
    assert out['by_program'] == {
        'jit_decode': [pytest.approx(5.5), 2.0],
        'jit_prefill_paged': [pytest.approx(1.5), 1.0]}
    assert sum(sec for sec, _ in out['by_program'].values()) == \
        pytest.approx(out['busy_s'])
    rows = {(r[0], r[1]): (r[2], r[3]) for r in out['by_path']}
    # layer_<n> collapsed: four operations of four layers are one row;
    # the while's self time is what its body does not cover.
    assert rows[('jit_decode', 'jit(decode)/Llama/layer_N/attn/'
                 'paged_attention/op')] == (pytest.approx(4.0), 4.0)
    assert rows[('jit_decode', 'jit(decode)/while')] == (
        pytest.approx(1.0), 1.0)
    assert rows[('jit_prefill_paged', trace_reduce.NO_PATH)] == (
        pytest.approx(1.5), 1.0)
    assert sum(r[2] for r in out['by_path']) == pytest.approx(out['busy_s'])
    assert out['by_path'][0][2] == max(r[2] for r in out['by_path'])
    assert (out['first_op_s'], out['last_op_s']) == (0.0, 11.0)


def test_an_idle_gap_is_named_by_the_innermost_phase_that_covers_most():
    out = trace_reduce.reduce_events(_events(), modules=MODULES,
                                     phases=PHASES)
    # 4.5-6.5: decode_dispatch's own pieces cover 0.1 + 0.3 of it,
    # fetch_wait (nested in it) 0.5, the loop's own 0.1 + 0.1,
    # prefill_dispatch 0.9: the chunk's dispatch wins.
    # 8.0-10.0: first_token_sync covers 1.8 of it, the loop 0.2.
    assert [sec for _, sec in out['idle_gaps']] == [2.0, 2.0]
    names = [name for name, sec in out['idle_gaps']]
    assert sorted(names) == [
        'engine.first_token_sync (jit_prefill_paged -> jit_decode)',
        'engine.prefill_dispatch (jit_decode -> jit_prefill_paged)']
    assert not any(n.startswith(trace_reduce.HOST_UNATTRIBUTED)
                   for n in names)
    assert out['phases_seen']['engine.loop'] == 1


def test_a_gap_no_phase_covers_reads_unattributed():
    phases = {'t#0': [('engine.loop', 100.0, 1.0)]}
    out = trace_reduce.reduce_events(_events(), phases=phases)
    assert all(name.startswith(trace_reduce.HOST_UNATTRIBUTED + ' (')
               for name, _ in out['idle_gaps'])
    out = trace_reduce.reduce_events(_events())
    assert sorted(name for name, _ in out['idle_gaps']) == [
        'host: unattributed (jit_decode -> jit_prefill_paged)',
        'host: unattributed (jit_prefill_paged -> jit_decode)']
    assert out['by_program']['jit_decode'][1] == 0.0   # no module line


@pytest.mark.parametrize('events, want', [
    ([('loop', 0, 10), ('a', 1, 2), ('b', 4, 3), ('c', 4.5, 1),
      ('x', 12, 1)],
     [(0, 1, 'loop'), (1, 3, 'a'), (3, 4, 'loop'), (4, 4.5, 'b'),
      (4.5, 5.5, 'c'), (5.5, 7, 'b'), (7, 10, 'loop'), (12, 13, 'x')]),
    ([('a', 0, 1), ('b', 1, 1)], [(0, 1, 'a'), (1, 2, 'b')]),
    ([], [])])
def test_innermost_segments_partition_a_threads_nested_events(events, want):
    assert trace_reduce.innermost_segments(events) == want


def test_name_gap_takes_the_phase_with_the_most_overlap():
    segs = [(0.0, 1.0, 'a'), (1.0, 1.4, 'b'), (1.4, 3.0, 'a')]
    starts = [s[0] for s in segs]
    assert trace_reduce.name_gap((0.9, 1.5), segs, starts) == 'b'
    assert trace_reduce.name_gap((0.5, 2.0), segs, starts) == 'a'
    assert trace_reduce.name_gap((3.0, 4.0), segs, starts) is None


@pytest.mark.parametrize('path, want', [
    ('jit(decode)/Llama/layer_12/attn/kv_write/x',
     'jit(decode)/Llama/layer_N/attn/kv_write/x'),
    ('jit(f)/layer_3/layer_44/y', 'jit(f)/layer_N/layer_N/y'),
    ('jit(f)/player_3/y', 'jit(f)/player_3/y')])
def test_layers_collapse_into_one_row(path, want):
    assert trace_reduce.collapse_path(path) == want


def test_recorded_tpu_trace_by_program_path_and_phases():
    """The v5e recording (three calls of one jitted matmul, 2 ms apart;
    no phase events: it predates them)."""
    path = os.path.join(HERE, 'data', 'tiny_v5e.xplane.pb')
    per_device, seen, extra = trace_reduce.read_xplane(path)
    assert extra['phases'] == {}
    assert [m[0] for m in extra['modules']['/device:TPU:0']] == \
        ['jit__lambda'] * 3
    out = trace_reduce.reduce_events(per_device, **extra)
    assert list(out['by_program']) == ['jit__lambda']
    sec, calls = out['by_program']['jit__lambda']
    assert calls == 3.0 and sec == pytest.approx(out['busy_s'])
    top = out['by_path'][0]
    assert top[:2] == ['jit__lambda', 'jit(<lambda>)/dot_general']
    assert top[3] == 3.0 and top[2] > 0.99 * out['busy_s']
    assert all(name.startswith('host: unattributed (jit__lambda -> ')
               for name, _ in out['idle_gaps'])
    # The same file through the reader of the profiler's own library.
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == '/device:TPU:0')
    theirs = sorted((ev.start_ns, ev.duration_ns) for ln in plane.lines
                    if ln.name == 'XLA Ops' for ev in ln.events)
    mine = xplane.read(path)
    ops = next(ln for p in mine if p.name == '/device:TPU:0'
               for ln in p.lines if ln.name == 'XLA Ops')
    assert ops.n_events == len(theirs) == 9
    for (s1, d1), ev in zip(theirs, sorted(ops.events,
                                           key=lambda e: e.start_s)):
        assert ev.start_s * 1e9 == pytest.approx(s1, abs=1.0)
        assert ev.duration_s * 1e9 == pytest.approx(d1, abs=1.0)
    assert len(per_device[plane.name]) == 9


def test_xplane_skips_the_lines_it_is_not_asked_for():
    path = os.path.join(HERE, 'data', 'tiny_v5e.xplane.pb')
    planes = xplane.read(path, lambda plane, line: line == 'XLA Modules')
    device = next(p for p in planes if p.name == '/device:TPU:0')
    by_name = {ln.name: ln for ln in device.lines}
    assert len(by_name['XLA Modules'].events) == 3
    assert by_name['XLA Ops'].events == [] and \
        by_name['XLA Ops'].n_events == 9
    ev = by_name['XLA Modules'].events[0]
    assert ev.name.startswith('jit__lambda(') and ev.stats['run_id']


# -- the new readers ----------------------------------------------------------
def _trace(seconds=0.010, events=160.0):
    return {'busy_s': 1.0,
            'by_program': {'jit_decode': [0.7, 50.0],
                           'jit_prefill_paged': [0.15, 4.0],
                           'jit_prefill_suffix': [0.05, 2.0],
                           'jit__where': [0.1, 50.0]},
            'by_path': [
                ['jit_decode', 'jit(decode)/Llama/layer_N/attn/'
                 'paged_attention/pallas_call', seconds, events],
                ['jit_decode', 'jit(decode)/Llama/layer_N/attn/kv_write/'
                 'dynamic_update_slice', 0.2, 5000.0],
                ['jit_prefill_suffix', 'jit(prefill_suffix)/Llama/layer_N/'
                 'attn/paged_attention/x', 0.5, 10.0]]}


def _records():
    # One request of 100 prompt tokens streaming a token every 0.1 s
    # from t = 47.0; one that finished before the span.
    return [{'prompt_tokens': 100,
             'arrivals': [47.0 + 0.1 * k for k in range(31)]},
            {'prompt_tokens': 900, 'arrivals': [1.0, 1.1, 1.2]}]


def _sources(**kw):
    cfg = manifest_lib.config(MANIFEST, 'mistral-7b-l16')
    base = {'trace': _trace(), 'records': _records(), 'trace_t0': 47.0,
            'trace_t1': 50.0, 'config': cfg, 'device': V5E}
    base.update(kw)
    return base


def test_paged_decode_cost_against_a_hand_count():
    cost = manifest_lib.roofline('paged_decode').cost(_sources())
    # Tokens 3..30 arrive in [47.25, 50.0] (token 30 at 50.0 to
    # rounding is the edge; count what the file counts): context 100+k.
    ks = [k for k in range(1, 31) if 47.25 <= 47.0 + 0.1 * k <= 50.0]
    assert ks[0] == 3
    context = sum(100 + k for k in ks)
    assert cost['tokens'] == len(ks) and cost['context_tokens'] == context
    assert cost['bytes'] == context * 2 * 8 * 128 * 2 * 16
    assert cost['flops'] == 4 * context * 32 * 128 * 16
    assert cost['bytes'] == context * 65536
    nothing = manifest_lib.roofline('paged_decode')
    assert nothing.cost(_sources(trace_t0=None)) is None
    assert nothing.cost(_sources(records=[])) is None
    assert nothing.cost(_sources(trace_t0=60.0, trace_t1=63.0)) is None


def test_scope_roofline_is_the_least_time_over_the_scopes_seconds():
    said = []
    spec = _spec('kernel.paged_decode_roofline')
    assert spec['reader'] == 'scope_roofline'
    got = _read('scope_roofline', _sources(say=said.append), **spec['args'])
    cost = manifest_lib.roofline('paged_decode').cost(_sources())
    # Bytes bound: 65,536 bytes a context token against 16,384 x 4
    # operations; the prefill program's scope of the same name is not
    # the decode kernel's.
    assert got == pytest.approx(100 * (cost['bytes'] / 819e9) / 0.010)
    assert 0 < got < 100
    assert len(said) == 1 and 'bytes bound' in said[0] \
        and '0.010000 device self seconds over 160 operations' in said[0]
    assert _read('scope_roofline', _sources(trace=None),
                 **spec['args']) is None
    assert _read('scope_roofline', _sources(records=[]),
                 **spec['args']) is None
    no_scope = _trace()
    no_scope['by_path'] = no_scope['by_path'][1:]
    assert _read('scope_roofline', _sources(trace=no_scope),
                 **spec['args']) is None


def test_a_share_over_100_is_refused_and_not_printed():
    """The scope ran for less than the chip needs for the counted
    work: the count is too high or the scope leaves work out."""
    spec = _spec('kernel.paged_decode_roofline')
    fast = _sources(trace=_trace(seconds=0.0001))
    with pytest.raises(ValueError, match='of the roofline'):
        _read('scope_roofline', fast, **spec['args'])
    with pytest.raises(KeyError):                 # no default peak
        _read('scope_roofline', _sources(device={'kind': 'cpu'}),
              **spec['args'])


def test_program_share_of_busy_time():
    spec = _spec('engine.prefill_share_pct')
    assert _read('program_share', _sources(), **spec['args']) == \
        pytest.approx(20.0)
    assert _read('program_share', _sources(), pattern='^jit_decode$') == \
        pytest.approx(70.0)
    assert _read('program_share', _sources(), pattern='^jit_nothing') is None
    assert _read('program_share', _sources(trace=None),
                 **spec['args']) is None


def test_serve_mfu_counts_prompts_and_committed_tokens():
    cfg = manifest_lib.config(MANIFEST, 'mistral-7b-l16')
    records = [{'prompt_tokens': 1000, 'arrivals': [0.5, 0.6]},
               {'prompt_tokens': 500, 'arrivals': [49.9]},
               {'prompt_tokens': 700, 'arrivals': [50.2]},     # after
               {'prompt_tokens': 300, 'arrivals': []}]         # never
    sources = {'stats_open': {'tokens_committed': 1000},
               'stats_close': {'tokens_committed': 36_000},
               'records': records, 'config': cfg, 'device': V5E,
               'harness': {'window_s': 50.0, 'stats_span_s': 50.0}}
    per_token = flops.serve_flops_per_token(cfg)
    want = 100 * (1500 + 35_000) * per_token / 50.0 / 197e12
    assert _read('serve_mfu', sources) == pytest.approx(want)
    assert 2 < want < 3
    assert _read('serve_mfu', dict(sources, stats_close=None)) is None
    crazy = dict(sources, stats_close={'tokens_committed': 10 ** 9})
    with pytest.raises(ValueError):
        _read('serve_mfu', crazy)


@pytest.mark.parametrize('cell', SERVING)
def test_the_serving_cells_report_the_new_metrics(cell):
    names = {m['name']: m for m in manifest_lib.per_layer(MANIFEST, cell)}
    assert names['serve.mfu_pct']['moves'] == 'serve_tokens_per_s'
    assert names['kernel.paged_decode_roofline']['unit'] == '%'
    # Beside the kernel's roofline, the whole step's share of the peak
    # moves the same end-to-end metric.
    assert names['kernel.paged_decode_roofline']['moves'] == \
        names['serve.mfu_pct']['moves']
    assert ('engine.prefill_share_pct' in names) == (cell == SERVING[0])
    e2e = {m['name'] for m in manifest_lib.end_to_end(MANIFEST, cell)}
    assert ('ttft_p95_ms' in e2e) == (cell == SERVING[0])
    assert {'itl_p95_ms', 'serve_tokens_per_s', 'setup_s'} <= e2e


@pytest.mark.parametrize('traffic, rate, n, drain', [
    ('chat-r2', 6.4, 320, 30), ('chat-saturated-r2', 10.0, 500, 45)])
def test_the_repitched_mixes_are_the_issues(traffic, rate, n, drain):
    from perfbench import schedule
    mix = manifest_lib.mix(traffic)
    assert (mix['rate_per_s'], mix['drain_s']) == (rate, drain)
    assert mix['prompt_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.8, 'min': 32, 'max': 1536}
    assert mix['output_tokens'] == {'dist': 'lognormal', 'median': 96,
                                    'sigma': 0.6, 'min': 16, 'max': 384}
    reqs = schedule.build(mix, 3, 50.0, mix['rate_per_s'], 32768)
    assert len(reqs) == n and reqs[-1]['due'] < 50.0
    # One fixed sequence: another seed draws other token ids only.
    other = schedule.build(mix, 4, 50.0, mix['rate_per_s'], 32768)
    assert [(r['due'], len(r['prompt']), r['max_new_tokens'])
            for r in reqs] == [(r['due'], len(r['prompt']),
                                r['max_new_tokens']) for r in other]
    assert reqs[0]['prompt'] != other[0]['prompt']
    assert not os.path.exists(os.path.join(
        manifest_lib.HERE, 'mixes', traffic[:-3] + '.json'))


# -- the control --------------------------------------------------------------
def test_the_int8_control_reads_above_what_bf16_does():
    """The reference put in the program's place, one precision below
    the served one (int8 products for bf16), against the program's own
    bf16 forward pass, at a size a test can hold: over four seeds the
    least the control reads is three times the most the program does,
    so a limit between them refuses the one and admits the other."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    reference = manifest_lib.reference('llama')
    program = LlamaConfig(vocab_size=2048, num_layers=4, num_heads=4,
                          num_kv_heads=2, embed_dim=256, mlp_dim=768,
                          max_seq_len=256, dtype=jnp.bfloat16)
    cfg = {'num_hidden_layers': 4, 'num_attention_heads': 4,
           'num_key_value_heads': 2, 'rope_theta': program.rope_theta,
           'rms_norm_eps': program.norm_eps}
    model = Llama(program)
    served, control = [], []
    for seed in range(4):
        tokens = np.random.RandomState(seed).randint(1, 2048, (1, 256))
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(seed),
            jnp.asarray(tokens, jnp.int32))['params'])
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        row = tokens[0].tolist()
        lp = reference.log_probs(params, cfg, row)
        pick = jnp.argmax(model.apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32))[0], -1)
        chosen = jnp.take_along_axis(lp, pick[:, None], -1)[:, 0]
        served.append(float(jnp.max(lp.max(-1) - chosen)))
        control.append(reference.control_shortfall(params, cfg, row, 1,
                                                   len(row)))
    limit = 2 * max(served)
    assert max(served) <= limit < min(control), (served, control)
    assert min(control) >= 3 * max(served), (served, control)
    with pytest.raises(ValueError):
        reference.log_probs(params, cfg, row[:8], weights='int4')
