"""The two readers of what the program aggregates from its own spans,
on hand-made `sources`."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import manifest as manifest_lib  # noqa: E402

RATIO = 2 ** 0.125


def _stats(loop_s, waits, commit=(0, 0.0), chunks=0):
    n, s = commit
    return {'loop_s': loop_s, 'prefill_chunks_run': chunks,
            'phases': {'engine.fetch_wait': {'n': 1, 's': waits},
                       'engine.commit': {'n': n, 's': s}}}


def _spec(name):
    path = os.path.join(ROOT, 'perfbench', 'layer_metrics',
                        f'{name}.json')
    with open(path, encoding='utf-8') as f:
        return json.load(f)


def _read(name, sources):
    spec = _spec(name)
    return manifest_lib.reader(spec['reader']).read(sources,
                                                    **spec['args'])


def test_growth_ratio_of_a_signed_sum_over_another_path():
    sources = {'stats_open': _stats(10.0, 4.0, (100, 1.0)),
               'stats_close': _stats(60.0, 36.5, (600, 3.5))}
    # (50 - 32.5 of waits) of 50 s; first_token_sync and idle_wait
    # never ran and read 0.
    assert _read('engine.host_busy_pct', sources) == pytest.approx(35.0)
    assert _read('engine.commit_ms', sources) == pytest.approx(5.0)


@pytest.mark.parametrize('name', ['engine.host_busy_pct',
                                  'engine.commit_ms',
                                  'engine.prefill_dispatch_ms'])
def test_growth_ratio_gives_none_without_growth_or_phases(name):
    same = _stats(10.0, 4.0, (100, 1.0), chunks=7)
    assert _read(name, {'stats_open': same, 'stats_close': same}) is None
    # A server from before the phases: counters, no `phases`, no
    # `loop_s`. The reader says nothing and does not raise.
    old = {'decode_calls': 5, 'prefill_chunks_run': 3}
    newer = {'decode_calls': 9, 'prefill_chunks_run': 8}
    assert _read(name, {'stats_open': old, 'stats_close': newer}) is None
    assert _read(name, {}) is None


def _hist(buckets):
    return {'n': sum(buckets.values()), 'sum_s': 1.0, 'ratio': RATIO,
            'buckets': buckets}


def _latency(buckets):
    return {'latency': {'queue_wait': _hist(buckets)}}


def test_percentile_of_the_growth_is_linear_inside_its_bucket():
    edge = f'{64.0:.4f}'
    sources = {'stats_open': _latency({edge: 10}),
               'stats_close': _latency({edge: 30})}
    low = 64.0 / RATIO
    got = _read('engine.queue_wait_p95_ms', sources)
    assert got == pytest.approx(low + 0.95 * (64.0 - low))


def test_percentile_on_a_bucket_edge_is_the_edge():
    # 19 of 20 observations at or under 32 ms, one in (58.7, 64]: the
    # 95th percentile's rank is 19, the upper edge of the first.
    sources = {'stats_open': _latency({}),
               'stats_close': _latency({f'{32.0:.4f}': 19,
                                        f'{64.0:.4f}': 1})}
    assert _read('engine.queue_wait_p95_ms', sources) == \
        pytest.approx(32.0)


def test_percentile_is_none_when_nothing_was_observed():
    same = _latency({f'{32.0:.4f}': 19})
    assert _read('engine.queue_wait_p95_ms',
                 {'stats_open': same, 'stats_close': same}) is None
    assert _read('engine.queue_wait_p95_ms',
                 {'stats_open': {'decode_calls': 1},
                  'stats_close': {'decode_calls': 2}}) is None
    only_inf = {'stats_open': _latency({}),
                'stats_close': _latency({'inf': 3})}
    assert _read('engine.queue_wait_p95_ms', only_inf) is None


def test_every_new_metric_file_names_a_reader_that_loads():
    manifest = manifest_lib.load()
    spans = [m for m in manifest['per_layer']
             if m['source'] == 'program_span'
             and m['name'] not in ('train.step_ms',
                                   'train.first_step_s')]
    assert len(spans) == 11
    for m in spans:
        spec = _spec(m['name'])
        assert spec['name'] == m['name']
        assert hasattr(manifest_lib.reader(spec['reader']), 'read')
