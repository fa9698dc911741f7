"""The benchmark's files for `lfm2-24b-a2b-l10` and its cell: the
configuration against its published source, the sizes against
`jax.eval_shape`, the two roofline files on synthetic records, which
metrics the cell reports, a CPU rehearsal of the cell, and the same
rehearsal with a planted fault."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

CELL = 'lfm2-24b-a2b-l10.chat-long-answers-128'
MANIFEST = manifest.load()
CFG = manifest.config(MANIFEST, 'lfm2-24b-a2b-l10')
MIX = manifest.mix('chat-long-answers-128')

LAYER_TYPES = ['full_attention' if i % 4 == 2 else 'conv'
               for i in range(40)]
#: LiquidAI/LFM2-24B-A2B's config.json (huggingface.co/LiquidAI/
#: LFM2-24B-A2B), every key the catalog row of the model-configs guide
#: holds.
PUBLISHED = {
    'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
    'intermediate_size': 11776, 'layer_types': LAYER_TYPES,
    'max_position_embeddings': 128000, 'model_type': 'lfm2_moe',
    'moe_intermediate_size': 1536, 'norm_eps': 1e-05,
    'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_dense_layers': 2, 'num_experts': 64, 'num_experts_per_tok': 4,
    'num_hidden_layers': 40, 'num_key_value_heads': 8,
    'rope_parameters': {'rope_theta': 1000000, 'rope_type': 'default'},
    'routed_scaling_factor': 1, 'use_expert_bias': True,
    'vocab_size': 65536}
REDUCED = {'num_hidden_layers': 10, 'layer_types': LAYER_TYPES[:10]}


def test_the_file_holds_every_published_key_but_the_reduced_ones():
    assert LAYER_TYPES.count('full_attention') == 10
    assert sorted(CFG['reduced']) == sorted(REDUCED)
    entry = next(c for c in MANIFEST['configs']
                 if c['name'] == 'lfm2-24b-a2b-l10')
    assert entry['reduced'] == CFG['reduced']
    assert entry['source'] == CFG['source'] == (
        'https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json')
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert CFG[key] == REDUCED[key], key
            assert CFG['reduced_from'][key] == value, key
        else:
            assert CFG[key] == value, key
    # The published layers 0-9: the two dense layers, then two whole
    # periods [full_attention, conv, conv, conv].
    assert REDUCED['layer_types'][2:] == 2 * [
        'full_attention', 'conv', 'conv', 'conv']
    assert CFG['experts_held'] == CFG['num_experts'] == 64
    assert '4 pipeline stages' in CFG['deployment']
    assert 'no layer is shared across chips' in CFG['deployment']
    assert set(CFG['assumed']) >= {
        'head_dim', 'tie_embedding', 'conv_cache', 'qk_norm', 'rope',
        'intermediate_size', 'routing', 'expert_bias', 'bias', 'weights'}
    assert CFG['score_margin_why'] and CFG['family'] == 'lfm2'
    assert CFG['serve_lm'] == [
        '--continuous-batching', '--num-slots', '128', '--max-total-len',
        '2560', '--kv-pool-bytes', '1610612736']


def test_the_mix_is_the_one_the_cell_was_defined_with():
    # serve_open_loop, with the share of positions off the reference's
    # best compared too.
    assert MIX['driver'] == 'serve_open_loop_share'
    assert MIX['arrivals'] == {'process': 'poisson'}
    assert MIX['prompt_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.8, 'min': 32, 'max': 1536}
    assert MIX['output_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.6, 'min': 32, 'max': 1024}
    # chat-long-answers' lengths, at this cell's own rate.
    other = manifest.mix('chat-long-answers')
    assert {k: MIX[k] for k in ('prompt_tokens', 'output_tokens')} == {
        k: other[k] for k in ('prompt_tokens', 'output_tokens')}
    assert {k: MIX[k] for k in ('drain_s', 'score_rows', 'score_max_tokens',
                                'trace_span_s', 'warmup_new_tokens')} == {
        'drain_s': 45, 'score_rows': 4, 'score_max_tokens': 2048,
        'trace_span_s': 3.0, 'warmup_new_tokens': 8}
    assert 'rehearse' in MIX and MIX['rate_note']
    cell = manifest.cell(MANIFEST, CELL)
    assert (cell['chips'], cell['traffic'], cell['config']) == (
        1, 'chat-long-answers-128', 'lfm2-24b-a2b-l10')
    reports = {m['name'] for m in manifest.end_to_end(MANIFEST, CELL)}
    assert reports == {'itl_p95_ms', 'serve_tokens_per_s', 'setup_s'}


@pytest.mark.parametrize('preset, want', [
    ('published widths', 5267090176), ('rehearse', 2017984)])
def test_sizes_equal_what_the_program_builds(preset, want):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.recipes.train_lm import _build_model
    sizes = manifest.sizes('lfm2')
    cfg = CFG if preset != 'rehearse' else dict(CFG, **CFG['rehearse'])
    model, _, _ = _build_model(cfg['serve_model'], 64, False)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))['params']
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert sizes.params(cfg) == built == want
    layout = model.config.page_layout()
    assert sizes.state_bytes_per_slot(cfg) == layout.slot_bytes(2)
    if preset != 'rehearse':
        # 10.53 GB of bf16, counted by part.
        assert sizes.matrices(cfg) == (
            2 * (16783360 - 6144 + 72351744)
            + 2 * (10485760 + 64 * 9437184 + 131072)
            + 6 * (16777216 + 64 * 9437184 + 131072) + 134217728)
        assert sizes.state_bytes_per_slot(cfg) == 8 * 2 * 2048 * 2
        # 8 conv mixers' two projections, 2 attention layers' four, the
        # dense SwiGLUs, and of 8 expert layers the router and 4
        # experts: no head.
        assert sizes.serve_flops_per_token(cfg) == 2.0 * (
            8 * 16777216 + 2 * 10485760 + 2 * 72351744
            + 8 * (131072 + 4 * 9437184))
        with pytest.raises(NotImplementedError):
            sizes.train_flops_per_token(cfg, 1024)


def _sources(**extra):
    records = [
        # 3 tokens in the span after token 0
        {'prompt_tokens': 1000, 'arrivals': [9.0, 10.5, 11.0, 11.5]},
        # k = 1 arrives before the span's edge
        {'prompt_tokens': 500, 'arrivals': [8.0, 10.1, 12.0, 14.0]},
        {'prompt_tokens': 700, 'arrivals': []}]
    return dict({'records': records, 'trace_t0': 10.0, 'trace_t1': 13.0,
                 'config': CFG}, **extra)


#: A trace's rows by path: each conv layer's two projections in scope
#: `short_conv` of the decode program, 83 traced calls of 8 conv layers
#: less one call's `out_proj` at the span's edge, and operations the
#: count must pass over (XLA's product in the prefill program, the
#: taps).
TRACE = {'by_path': [
    ['jit_decode', 'jit(decode)/Lfm2/layer_N/conv/short_conv/in_proj/'
     'jit(_streamed_call)/streamed_matmul/pallas_call', 0.03, 664],
    ['jit_decode', 'jit(decode)/Lfm2/layer_N/conv/short_conv/out_proj/'
     'jit(_streamed_call)/streamed_matmul/pallas_call', 0.01, 663],
    ['jit_prefill_suffix', 'jit(prefill_suffix)/Lfm2/layer_N/conv/'
     'short_conv/in_proj/dot_general', 0.005, 160],
    ['jit_decode', 'jit(decode)/Lfm2/layer_N/conv/short_conv/mul',
     0.001, 664]]}


def test_short_conv_decode_cost_against_a_hand_count():
    cost = manifest.roofline('short_conv_decode').cost(
        _sources(trace=TRACE))
    assert cost['tokens'] == 4
    assert cost['weight_reads'] == [664, 663]
    h = 2048
    # Each traced projection reads its matrix once (in_proj with the 3
    # taps); each token in the span reads and writes its tail of 2
    # inputs in each of the 8 conv layers.
    assert cost['bytes'] == (664 * (3 * h * h + 3 * h) * 2
                             + 663 * h * h * 2 + 8 * 4 * 2 * 2 * h * 2)
    assert cost['flops'] == 4 * 8 * (8 * h * h + 6 * h)
    assert manifest.roofline('short_conv_decode').cost(
        _sources(trace=TRACE, trace_t0=None)) is None
    # A trace where XLA's products hold the projections (no read in the
    # scope's time) gives no cost, nor does a run with no trace.
    xla = {'by_path': [[row[0], row[1].replace(
        'jit(_streamed_call)/streamed_matmul/pallas_call', 'dot_general'),
        row[2], row[3]] for row in TRACE['by_path']]}
    assert manifest.roofline('short_conv_decode').cost(
        _sources(trace=xla)) is None
    assert manifest.roofline('short_conv_decode').cost(_sources()) is None
    # A configuration without conv layers has nothing to count.
    assert manifest.roofline('short_conv_decode').cost(
        _sources(trace=TRACE, config={'hidden_size': 8})) is None


def test_paged_decode_by_layer_type_counts_the_attention_layers_only():
    cost = manifest.roofline('paged_decode_by_layer_type').cost(_sources())
    context = (1001 + 1002 + 1003) + 502
    assert (cost['tokens'], cost['context_tokens']) == (4, context)
    # 2 attention layers of 10, 8 K/V heads of 64, bf16.
    assert cost['bytes'] == context * 2 * 8 * 64 * 2 * 2
    assert cost['flops'] == 4 * context * 32 * 64 * 2
    # What rooflines/paged_decode.py counts for every layer: five times.
    every = manifest.roofline('paged_decode').cost(_sources())
    assert every['bytes'] == 5 * cost['bytes']
    assert manifest.roofline('paged_decode_by_layer_type').cost(
        _sources(config={'hidden_size': 8})) is None


def test_every_new_metric_has_its_files_and_names_the_cell():
    names = {m['name']: m for m in manifest.per_layer(MANIFEST, CELL)}
    for name in ('kernel.short_conv_decode_roofline',
                 'kernel.hybrid_paged_decode_roofline'):
        metric = names[name]
        assert metric['workloads'] == [CELL]
        assert (metric['moves'], metric['unit'], metric['source']) == (
            'serve_tokens_per_s', '%', 'device_trace')
        assert metric['spec']['args']['program'] == '^jit_decode$'
        manifest.reader(metric['spec']['reader'])
        manifest.roofline(metric['spec']['args']['costs'])
    assert names['kernel.short_conv_decode_roofline']['spec']['args'][
        'scope'] == 'short_conv'
    assert names['kernel.hybrid_paged_decode_roofline']['spec']['args'][
        'scope'] == 'paged_attention'
    # Counted over every layer as attention, paged_decode would read
    # five times over here; the state-space and latent counts are
    # other models'. The expert counts would read here, but an older
    # test of the benchmark holds their cells to DeepSeek's alone
    # (PERF.md section 7).
    for name in ('kernel.paged_decode_roofline',
                 'kernel.expert_mlp_roofline',
                 'moe.expert_load_max_over_mean',
                 'kernel.ssm_decode_roofline', 'kernel.ssm_scan_roofline',
                 'kernel.latent_expert_mlp_roofline',
                 'engine.ssm_lanes_live_pct',
                 'kernel.indexer_decode_roofline'):
        assert name not in names
    assert {'serve.mfu_pct', 'device.idle_pct.serve',
            'engine.batch_occupancy_pct', 'engine.decode_round_ms',
            'engine.host_busy_pct', 'runtime.ready_s',
            'runtime.gc_pause_pct'} <= set(names)
    assert not [n for n, m in names.items() if m['moves'] == 'ttft_p95_ms']


class _Reference:
    """A reference whose log-probabilities put the best token at 0 and
    token t at -t / 100, and whose control picks token 3 everywhere."""

    @staticmethod
    def log_probs(params, cfg, tokens):
        import numpy as np
        return np.tile(-np.arange(8) / 100.0, (len(tokens), 1))

    @staticmethod
    def control_gaps(params, cfg, tokens, first, last):
        import numpy as np
        return np.full(last - first, 0.03)


def test_the_share_driver_holds_the_off_best_share(monkeypatch):
    """`drivers/serve_open_loop_share.py`: a configuration's share limit
    parsed with its reason; the scored positions read once, the worst
    gap as `serve_open_loop` reads it and the share off by more than
    `score_position_nats`; the control's share on the same rows; the
    share compared beside the others in the result."""
    from perfbench import harness
    driver = manifest.driver('serve_open_loop_share')
    base = driver.base
    assert driver.off_best_limit({}) is None
    with pytest.raises(manifest.ManifestError, match='without'):
        driver.off_best_limit({'score_off_best_share': 0.1,
                               'score_position_nats': 0.05})
    with pytest.raises(manifest.ManifestError, match='out of'):
        driver.off_best_limit({'score_off_best_share': 1.5,
                               'score_position_nats': 0.05,
                               'score_off_best_why': 'x'})
    cfg = {'reference': 'fake', 'score_off_best_share': 0.3,
           'score_position_nats': 0.015, 'score_off_best_why': 'x'}
    assert driver.off_best_limit(cfg)['share'] == 0.3
    assert driver.share_off([], 0.1) == 1.0
    assert driver.share_off([[0.0, float('inf'), 0.2, 0.05]], 0.1) == 0.5
    finished = []
    monkeypatch.setattr(harness, 'finish', lambda ctx, *, compared, **kw:
                        finished.append(compared))
    monkeypatch.setattr(base, 'score_rows', base.score_rows)
    monkeypatch.setattr(base, 'control_reading', base.control_reading)
    monkeypatch.setattr(manifest, 'reference', lambda name: _Reference)
    driver.install(cfg)
    # Two rows of a prompt of 2: tokens 0 and 1 | 1 and 2 | 5 served.
    rows = [{'prompt_tokens': 2, 'tokens': [4, 4, 0, 1], 'cap': 8},
            {'prompt_tokens': 2, 'tokens': [4, 4, 2, 5], 'cap': 8}]
    scored = base.score_rows(cfg, None, rows, 0.04)
    assert scored['positions'] == 4
    assert scored['worst_shortfall_nats'] == pytest.approx(0.05)
    assert scored['ok'] is False
    # Off by more than 0.015: tokens 2 and 5.
    assert scored['off_best_share'] == 0.5
    control = base.control_reading(cfg, None, rows)
    assert control == {'rows': 2, 'control_shortfall_nats': 0.03,
                       'control_off_best_share': 1.0}
    harness.finish(None, compared={'rows_scored': {'value': 2,
                                                   'at_least': 1}})
    assert finished == [{'rows_scored': {'value': 2, 'at_least': 1},
                         'off_best_share': {'value': 0.5, 'at_most': 0.3}}]


def _rehearse(argv, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_cpu_multi_thread_eigen=false',
               OMP_NUM_THREADS='1')
    out = subprocess.run(
        [sys.executable, *argv, '--workload', CELL, '--seed', '2147483651',
         '--seconds', '4', '--trace', '0', '--rehearse'], cwd=ROOT, env=env,
        timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith('[rehearsal] ')
    return json.loads(last[len('[rehearsal] '):])


def test_rehearsal_of_the_cell_ends_correct():
    result = _rehearse([os.path.join(ROOT, 'perfbench', 'run.py')])
    assert result['correct'] is True and result['failed'] == 0
    assert result['compared']['paged_cache']['value'] == 1
    assert result['compared']['shortfall_nats']['at_most'] == \
        CFG['rehearse']['score_margin_nats']
    assert result['compared']['off_best_share']['at_most'] == \
        CFG['rehearse']['score_off_best_share']
    assert set(result['metrics']) == {
        'rehearsal.itl_p95_ms', 'rehearsal.serve_tokens_per_s',
        'rehearsal.setup_s'}


PLANTED = '''
import os, runpy, sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from skypilot_tpu.models import lfm2
from skypilot_tpu.ops import ssm
conv, step = ssm.causal_conv, lfm2.conv_step
# The planted fault: the convolution's tail is never carried, not into
# a prompt's later chunk nor into a decode round (pages, the K/V and
# every count stay right).
ssm.causal_conv = lambda x, tail, weight, bias, lengths: conv(
    x, jnp.zeros_like(tail), weight, bias, lengths)
lfm2.conv_step = lambda tail, u, weight, live: step(
    jnp.zeros_like(tail), u, weight, live)
sys.argv = [os.path.join({root!r}, 'perfbench', 'run.py')] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name='__main__')
'''


def test_a_zeroed_tail_ends_not_correct(tmp_path):
    """The same rehearsal with the conv tails zeroed wherever they are
    read: every request is served, every length is right, and the run
    ends `correct` false by the two scores of the engine's tokens."""
    script = tmp_path / 'planted.py'
    script.write_text(PLANTED.format(root=ROOT), encoding='utf-8')
    result = _rehearse([str(script)])
    assert result['failed'] == 0 and result['correct'] is False
    bad = [name for name, entry in result['compared'].items()
           if not entry['ok']]
    assert bad == ['shortfall_nats', 'off_best_share']
    assert result['compared']['shortfall_nats']['value'] > \
        CFG['rehearse']['score_margin_nats']
