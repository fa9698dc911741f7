"""The benchmark's files for `nemotron3-super-l11-ep4` and its cell: the
configuration against its published source, the sizes against
`jax.eval_shape`, the three roofline files on synthetic records and
counters, the lanes-live reader's arguments, a CPU rehearsal of the
cell, and the same rehearsal with a planted fault."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

CELL = 'nemotron3-super-l11-ep4.chat-long-answers'
MANIFEST = manifest.load()
CFG = manifest.config(MANIFEST, 'nemotron3-super-l11-ep4')
MIX = manifest.mix('chat-long-answers')

#: NVIDIA-Nemotron-3-Super-120B-A12B-BF16's config.json
#: (huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16), every
#: key the catalog row of the model-configs guide holds.
PUBLISHED = {
    'attention_bias': False, 'chunk_size': 128, 'conv_kernel': 4,
    'expand': 2, 'head_dim': 128, 'hidden_size': 4096,
    'hybrid_override_pattern':
        'MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*'
        'EMEMEMEMEM*EMEMEMEM*EMEMEMEME',
    'intermediate_size': 2688, 'layer_norm_epsilon': 1e-05,
    'mamba_head_dim': 64, 'mamba_hidden_act': 'silu',
    'mamba_num_heads': 128, 'mamba_proj_bias': False,
    'max_position_embeddings': 262144, 'mlp_bias': False,
    'mlp_hidden_act': 'relu2', 'model_type': 'nemotron_h',
    'moe_intermediate_size': 2688, 'moe_latent_size': 1024,
    'moe_shared_expert_intermediate_size': 5376,
    'moe_shared_expert_overlap': False,
    'mtp_hybrid_override_pattern': '*E', 'n_group': 1, 'n_groups': 8,
    'n_routed_experts': 512, 'n_shared_experts': 1, 'norm_eps': 1e-05,
    'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 22, 'num_hidden_layers': 88,
    'num_key_value_heads': 2, 'num_logits_to_keep': 1,
    'num_nextn_predict_layers': 1, 'partial_rotary_factor': 1,
    'rescale_prenorm_residual': True, 'residual_in_fp32': False,
    'rope_theta': 10000, 'routed_scaling_factor': 5,
    'sliding_window': None, 'ssm_state_size': 128,
    'tie_word_embeddings': False, 'time_step_floor': 0.0001,
    'time_step_max': 0.1, 'time_step_min': 0.001, 'topk_group': 1,
    'use_bias': False, 'use_conv_bias': True, 'use_mamba_kernels': True,
    'vocab_size': 131072}
REDUCED = {'num_hidden_layers': 11,
           'hybrid_override_pattern': 'MEMEMEMEM*E',
           'n_routed_experts': 128, 'vocab_size': 32768,
           'num_nextn_predict_layers': 0}


def test_the_file_holds_every_published_key_but_the_reduced_ones():
    assert len(PUBLISHED['hybrid_override_pattern']) == 88
    assert sorted(CFG['reduced']) == sorted(REDUCED)
    entry = next(c for c in MANIFEST['configs']
                 if c['name'] == 'nemotron3-super-l11-ep4')
    assert entry['reduced'] == CFG['reduced']
    assert entry['source'] == CFG['source']
    assert entry['source'].endswith(
        'NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json')
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert CFG[key] == REDUCED[key], key
            assert CFG['reduced_from'][key] == value, key
        else:
            assert CFG[key] == value, key
    # The slice is one period of the published pattern, in its ratio.
    assert REDUCED['hybrid_override_pattern'] == \
        PUBLISHED['hybrid_override_pattern'][27:38]
    assert [REDUCED['hybrid_override_pattern'].count(c) for c in 'ME*'] \
        == [5, 5, 1]
    assert [PUBLISHED['hybrid_override_pattern'].count(c) for c in 'ME*'] \
        == [40, 40, 8]
    assert (CFG['experts_held'], CFG['expert_offset']) == (128, 0)
    assert '4 chips share each layer' in CFG['deployment']
    assert '1/4 of the tokens' in CFG['deployment']
    assert set(CFG['assumed']) >= {'rope', 'float32', 'latent',
                                   'num_nextn_predict_layers',
                                   'max_seq_len', 'weights'}
    assert CFG['score_margin_why'] and CFG['family'] == 'nemotron_h'
    assert CFG['serve_lm'] == [
        '--continuous-batching', '--num-slots', '128', '--max-total-len',
        '4096', '--prefill-chunk', '512', '--kv-pool-bytes', '536870912']


def test_the_mix_is_the_one_the_cell_was_defined_with():
    assert MIX['arrivals'] == {'process': 'poisson'}
    assert MIX['prompt_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.8, 'min': 32, 'max': 1536}
    assert MIX['prompt_tokens'] == manifest.mix('chat-r2')['prompt_tokens']
    assert MIX['output_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.6, 'min': 32, 'max': 1024}
    assert {k: MIX[k] for k in ('drain_s', 'score_rows', 'score_max_tokens',
                                'trace_span_s', 'warmup_new_tokens')} == {
        'drain_s': 45, 'score_rows': 4, 'score_max_tokens': 2048,
        'trace_span_s': 3.0, 'warmup_new_tokens': 8}
    cell = manifest.cell(MANIFEST, CELL)
    assert (cell['chips'], cell['traffic']) == (1, 'chat-long-answers')
    reports = {m['name'] for m in manifest.end_to_end(MANIFEST, CELL)}
    # Not `ttft_p95_ms`: 13 requests stand beyond the percentile and
    # six seeds spread by 5.3%, over half its bound (PERF.md, PR 35).
    assert reports == {'itl_p95_ms', 'serve_tokens_per_s', 'setup_s'}


@pytest.mark.parametrize('preset, want', [
    ('published widths', 4648163712), ('rehearse', 648144)])
def test_sizes_equal_what_the_program_builds(preset, want):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.recipes.train_lm import _build_model
    sizes = manifest.sizes('nemotron_h')
    cfg = CFG if preset != 'rehearse' else dict(CFG, **CFG['rehearse'])
    model, _, _ = _build_model(cfg['serve_model'], 64, False)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))['params']
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert sizes.params(cfg) == built == want
    layout = model.config.page_layout()
    assert sizes.state_bytes_per_slot(cfg) == layout.slot_bytes(2)
    if preset != 'rehearse':
        assert sizes.matrices(cfg) == 4647813120          # 9.30 GB bf16
        assert sizes.state_bytes_per_slot(cfg) == 5 * (4194304 + 61440)
        # 5 mixers' two projections, attention's four, and of 5 expert
        # layers the router, the latent's two and the shared expert:
        # nothing routed, no head.
        assert sizes.serve_flops_per_token(cfg) == 2.0 * (
            5 * 109576192 + 35651584 + 5 * (2097152 + 8388608 + 44040192))
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


def test_ssm_decode_cost_against_a_hand_count():
    cost = manifest.roofline('ssm_decode').cost(_sources())
    assert cost['tokens'] == 4
    # A slot's state and tail, read once and written once, 5 layers.
    assert cost['bytes'] == 4 * 5 * 2 * (4194304 + 61440)
    assert cost['flops'] == 4 * 5 * 6 * 128 * 64 * 128
    assert manifest.roofline('ssm_decode').cost(
        _sources(trace_t0=None)) is None
    # A configuration without Mamba layers has nothing to count.
    assert manifest.roofline('ssm_decode').cost(
        _sources(config={'hidden_size': 8})) is None


def test_ssm_scan_cost_scales_to_the_chunks_in_the_span():
    a = {'prefill_chunks_run': 10,
         'ssm_scan_tokens': {'layer_0/mixer': 1000, 'layer_2/mixer': 1000}}
    b = {'prefill_chunks_run': 50,
         'ssm_scan_tokens': {'layer_0/mixer': 9000, 'layer_2/mixer': 9000}}
    trace = {'by_program': {'jit_decode': [0.5, 20.0],
                            'jit_prefill_suffix': [1.0, 3.0],
                            'jit_prefill_paged': [0.2, 1.0]}}
    cost = manifest.roofline('ssm_scan').cost(
        _sources(stats_open=a, stats_close=b, trace=trace))
    state = 128 * 64 * 128
    # 4 of 40 chunks ran in the span; 16,000 layer-tokens scanned.
    assert cost['flops'] == pytest.approx(0.1 * 16000 * 6 * state)
    assert cost['bytes'] == pytest.approx(
        0.1 * 16000 * (10240 * 2 + 128 * 4 + 8192 * 4)
        + 4 * 2 * 2 * state * 4)
    trace['by_program']['jit_prefill_suffix'][1] = 40.0
    assert manifest.roofline('ssm_scan').cost(
        _sources(stats_open=a, stats_close=b, trace=trace)) is None
    assert manifest.roofline('ssm_scan').cost(_sources(
        stats_open={'decode_calls': 1}, stats_close={'decode_calls': 9},
        trace=trace)) is None


def test_latent_expert_cost_counts_two_matrices_in_the_latent():
    def stats(decode_calls, chunks, tokens, touched):
        return {'decode_calls': decode_calls, 'prefill_chunks_run': chunks,
                'expert_tokens': {'layer_1/mixer': tokens},
                'expert_calls_touched': {'layer_1/mixer': touched}}
    a = stats(100, 10, [[5, 5], [100, 100]], [[2, 2], [10, 10]])
    b = stats(300, 50, [[25, 45], [500, 1300]], [[12, 22], [50, 30]])
    trace = {'by_program': {'jit_decode': [0.5, 20.0],
                            'jit_prefill_suffix': [1.0, 3.0],
                            'jit_prefill_paged': [0.2, 1.0]}}
    cost = manifest.roofline('latent_expert_mlp').cost(
        _sources(stats_open=a, stats_close=b, trace=trace))
    matrix = 1024 * 2688
    assert cost['flops'] == pytest.approx(
        (0.1 * 60 + 0.1 * 1600) * 4 * matrix)
    assert cost['bytes'] == pytest.approx(
        (0.1 * 30 + 0.1 * 60) * 2 * matrix * 2)
    # A configuration whose experts work at the full width is another
    # file's (rooflines/expert_mlp.py): nothing here.
    other = {k: v for k, v in CFG.items() if k != 'moe_latent_size'}
    assert manifest.roofline('latent_expert_mlp').cost(_sources(
        stats_open=a, stats_close=b, trace=trace, config=other)) is None


def test_lanes_live_reads_the_mamba_layers_of_the_cell():
    """Growth of `ssm_update_tokens` in the five Mamba layers over the
    rounds x 5 x the configuration's slots: the share of state rows a
    round really advances."""
    spec = next(m['spec'] for m in manifest.per_layer(MANIFEST, CELL)
                if m['name'] == 'engine.ssm_lanes_live_pct')
    pattern = CFG['hybrid_override_pattern']
    blocks = [f'layer_{i}/mixer' for i, c in enumerate(pattern) if c == 'M']
    assert spec['args']['plus'] == [['ssm_update_tokens', b]
                                    for b in blocks]
    slots = int(CFG['serve_lm'][CFG['serve_lm'].index('--num-slots') + 1])
    assert spec['args']['scale'] == 100.0 / (len(blocks) * slots)
    read = manifest.reader('stats_growth_ratio').read
    a = {'decode_calls': 100, 'ssm_update_tokens': {b: 0 for b in blocks}}
    b = {'decode_calls': 300,
         'ssm_update_tokens': {b: 200 * 64 for b in blocks}}
    assert read({'stats_open': a, 'stats_close': b},
                **spec['args']) == pytest.approx(50.0)


def test_every_new_metric_has_its_files_and_names_the_cell():
    names = {m['name']: m for m in manifest.per_layer(MANIFEST, CELL)}
    for name in ('kernel.ssm_decode_roofline', 'kernel.ssm_scan_roofline',
                 'kernel.latent_expert_mlp_roofline',
                 'engine.ssm_lanes_live_pct'):
        metric = names[name]
        assert metric['workloads'] == [CELL]
        assert metric['moves'] == 'serve_tokens_per_s'
        assert metric['unit'] == '%'
        manifest.reader(metric['spec']['reader'])
        if 'costs' in metric['spec']['args']:
            manifest.roofline(metric['spec']['args']['costs'])
    # Their cost files count another model's work: 11 layers of K/V for
    # 1, three matrices of the hidden width for two in the latent.
    for name in ('kernel.paged_decode_roofline',
                 'kernel.expert_mlp_roofline',
                 'kernel.indexer_decode_roofline',
                 'kernel.sparse_latent_decode_roofline',
                 'engine.sparse_decode_share_pct'):
        assert name not in names
    assert {'serve.mfu_pct', 'device.idle_pct.serve',
            'engine.batch_occupancy_pct', 'engine.decode_round_ms',
            'engine.host_busy_pct', 'runtime.ready_s'} <= set(names)
    # What moves the first token's tail goes with it.
    assert not [n for n, m in names.items() if m['moves'] == 'ttft_p95_ms']


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
    assert result['compared']['shortfall_nats']['at_most'] == 0.05
    assert set(result['metrics']) == {
        'rehearsal.itl_p95_ms', 'rehearsal.serve_tokens_per_s',
        'rehearsal.setup_s'}


PLANTED = '''
import os, runpy, sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from skypilot_tpu.ops import ssm
scan = ssm.ssm_scan
# The planted fault: a prompt's later chunk does not get the state its
# earlier chunks left (pages, tails and every count stay right).
ssm.ssm_scan = lambda x, dt, a, b, c, d, state, lengths, chunk_size: scan(
    x, dt, a, b, c, d, jnp.zeros_like(state), lengths, chunk_size)
sys.argv = [os.path.join({root!r}, 'perfbench', 'run.py')] + sys.argv[1:]
runpy.run_path(sys.argv[0], run_name='__main__')
'''


def test_state_not_carried_between_chunks_ends_not_correct(tmp_path):
    """The same rehearsal with the recurrence's state dropped at every
    chunk boundary: every request is served, every length is right,
    and the run ends `correct` false by `shortfall_nats` alone."""
    script = tmp_path / 'planted.py'
    script.write_text(PLANTED.format(root=ROOT), encoding='utf-8')
    result = _rehearse([str(script)])
    assert result['failed'] == 0 and result['correct'] is False
    bad = [name for name, entry in result['compared'].items()
           if not entry['ok']]
    assert bad == ['shortfall_nats']
    assert result['compared']['shortfall_nats']['value'] > 0.05
