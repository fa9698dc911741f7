"""The benchmark's files for `deepseek-v32-l5-ep16` and its cell: the
configuration against its published source, the sizes against
`jax.eval_shape`, the three roofline files on synthetic records and
counters, the expert-load reader, and a CPU rehearsal of the cell."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

CELL = 'deepseek-v32-l5-ep16.longctx-saturated'
MANIFEST = manifest.load()
CFG = manifest.config(MANIFEST, 'deepseek-v32-l5-ep16')
MIX = manifest.mix('longctx-saturated')

#: DeepSeek-V3.2's config.json (huggingface.co/deepseek-ai/DeepSeek-V3.2),
#: the numbers that say something about its shape.
PUBLISHED = {
    'first_k_dense_replace': 3, 'hidden_size': 7168, 'index_head_dim': 128,
    'index_n_heads': 64, 'index_topk': 2048, 'intermediate_size': 18432,
    'kv_lora_rank': 512, 'max_position_embeddings': 163840,
    'moe_intermediate_size': 2048, 'moe_layer_freq': 1, 'n_group': 8,
    'n_routed_experts': 256, 'n_shared_experts': 1,
    'num_attention_heads': 128, 'num_experts_per_tok': 8,
    'num_hidden_layers': 61, 'num_key_value_heads': 128,
    'num_nextn_predict_layers': 1, 'q_lora_rank': 1536,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_theta': 10000, 'routed_scaling_factor': 2.5, 'topk_group': 4,
    'v_head_dim': 128, 'vocab_size': 129280, 'ep_size': 1}
REDUCED = {'num_hidden_layers': 5, 'first_k_dense_replace': 1,
           'n_routed_experts': 16, 'vocab_size': 16160,
           'num_nextn_predict_layers': 0}


def test_the_file_holds_every_published_number_but_the_reduced_ones():
    assert sorted(CFG['reduced']) == sorted(REDUCED)
    entry = next(c for c in MANIFEST['configs']
                 if c['name'] == 'deepseek-v32-l5-ep16')
    assert entry['reduced'] == CFG['reduced']
    assert entry['source'] == CFG['source']
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert CFG[key] == REDUCED[key], key
            assert CFG['reduced_from'][key] == value, key
        else:
            assert CFG[key] == value, key
    assert CFG['rope_scaling'] == {
        'beta_fast': 32, 'beta_slow': 1, 'factor': 40, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 4096,
        'type': 'yarn'}
    assert (CFG['experts_held'], CFG['expert_offset']) == (16, 0)
    assert '16 chips share each layer' in CFG['deployment']
    assert CFG['score_margin_why'] and CFG['assumed']


def test_the_mix_is_the_one_the_cell_was_defined_with():
    assert MIX['arrivals'] == {'process': 'poisson'}
    assert MIX['prompt_tokens'] == {'dist': 'lognormal', 'median': 6144,
                                    'sigma': 0.5, 'min': 3072, 'max': 14336}
    assert MIX['output_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.6, 'min': 32, 'max': 1024}
    assert {k: MIX[k] for k in ('drain_s', 'score_rows', 'score_max_tokens',
                                'trace_span_s', 'warmup_new_tokens')} == {
        'drain_s': 60, 'score_rows': 4, 'score_max_tokens': 4096,
        'trace_span_s': 3.0, 'warmup_new_tokens': 8}
    cell = manifest.cell(MANIFEST, CELL)
    assert (cell['chips'], cell['traffic']) == (1, 'longctx-saturated')
    reports = {m['name'] for m in manifest.end_to_end(MANIFEST, CELL)}
    assert reports == {'itl_p95_ms', 'serve_tokens_per_s', 'setup_s'}


@pytest.mark.parametrize('preset, want', [
    ('published widths', 4635518208), ('rehearse', 1475200)])
def test_sizes_equal_what_the_program_builds(preset, want):
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.recipes.train_lm import _build_model
    sizes = manifest.sizes('deepseek_v32')
    cfg = CFG if preset != 'rehearse' else dict(CFG, **CFG['rehearse'])
    name = cfg['serve_model']
    model, _, _ = _build_model(name, 64, False)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))['params']
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert sizes.params(cfg) == built == want
    if preset != 'rehearse':
        assert sizes.matrices(cfg) == 4635426816
        # attention with its indexer in 5 layers, the dense SwiGLU,
        # 4 shared experts and 4 routers: nothing routed, no head.
        assert sizes.serve_flops_per_token(cfg) == 2.0 * (
            5 * 201064448 + 396361728 + 4 * (44040192 + 1835008))


def _sources(**extra):
    records = [
        # 3 tokens in the span after token 0, contexts 1001..1003
        {'prompt_tokens': 1000, 'arrivals': [9.0, 10.5, 11.0, 11.5]},
        # context 5000 + k; k = 1 arrives before the span opens
        {'prompt_tokens': 5000, 'arrivals': [8.0, 10.1, 12.0, 14.0]},
        {'prompt_tokens': 700, 'arrivals': []}]
    return dict({'records': records, 'trace_t0': 10.0, 'trace_t1': 13.0,
                 'config': CFG}, **extra)


def test_indexer_decode_cost_against_a_hand_count():
    from skypilot_tpu.models import deepseek
    layout = deepseek.DeepseekConfig.v32_l5_ep16().page_layout()
    stats = {'page_pool': {'row_layout': layout.describe(5, 2)}}
    cost = manifest.roofline('indexer_decode').cost(
        _sources(stats_close=stats))
    context = 1001 + 1002 + 1003 + 5002
    assert cost['tokens'] == 4 and cost['context_tokens'] == context
    # A cached key as the program's layout has it: 128 float32 values.
    assert cost['bytes'] == context * 128 * 4 * 5
    assert cost['flops'] == context * 64 * 128 * 2 * 5
    assert manifest.roofline('indexer_decode').cost(
        _sources(stats_close=stats, trace_t0=None)) is None
    # A program that does not say what a key takes: no cost, no guess.
    assert manifest.roofline('indexer_decode').cost(_sources()) is None


def test_sparse_latent_decode_cost_stops_at_index_topk():
    cost = manifest.roofline('sparse_latent_decode').cost(_sources())
    rows = 1001 + 1002 + 1003 + 2048
    assert cost['selected_rows'] == rows
    assert cost['bytes'] == rows * 576 * 2 * 5
    assert cost['flops'] == rows * 128 * (576 + 512) * 2 * 5


def _stats(decode_calls, chunks, tokens, touched):
    return {'decode_calls': decode_calls, 'prefill_chunks_run': chunks,
            'expert_tokens': {'layer_1/mlp': tokens},
            'expert_calls_touched': {'layer_1/mlp': touched}}


def test_expert_mlp_cost_scales_each_phase_to_its_calls_in_the_span():
    a = _stats(100, 10, [[5, 5], [100, 100]], [[2, 2], [10, 10]])
    b = _stats(300, 50, [[25, 45], [500, 1300]], [[12, 22], [50, 30]])
    trace = {'by_program': {'jit_decode': [0.5, 20.0],
                            'jit_prefill_suffix': [1.0, 3.0],
                            'jit_prefill_paged': [0.2, 1.0],
                            'jit__stash_first_token': [0.0, 7.0]}}
    cost = manifest.roofline('expert_mlp').cost(
        _sources(stats_open=a, stats_close=b, trace=trace))
    matrix = 7168 * 2048
    # decode: 20 of 200 calls; prefill: 4 of 40 chunks.
    assert cost['flops'] == pytest.approx(
        (0.1 * 60 + 0.1 * 1600) * 6 * matrix)
    assert cost['bytes'] == pytest.approx(
        (0.1 * 30 + 0.1 * 60) * 3 * matrix * 2)
    # More calls in the span than in the window that holds it is a
    # miscount: no cost, and no share capped at 100.
    trace['by_program']['jit_decode'][1] = 201.0
    assert manifest.roofline('expert_mlp').cost(
        _sources(stats_open=a, stats_close=b, trace=trace)) is None
    trace['by_program']['jit_decode'][1] = 20.0
    # A model that routes nothing, or a run without a trace: nothing.
    assert manifest.roofline('expert_mlp').cost(_sources(
        stats_open={'decode_calls': 1}, stats_close={'decode_calls': 9},
        trace=trace)) is None
    assert manifest.roofline('expert_mlp').cost(
        _sources(stats_open=a, stats_close=b, trace=None)) is None


def test_expert_load_reads_the_busiest_expert_over_the_mean():
    read = manifest.reader('expert_load').read
    a = {'expert_tokens': {'layer_1/mlp': [[1, 1, 1, 1], [0, 0, 0, 0]]}}
    b = {'expert_tokens': {'layer_1/mlp': [[3, 1, 1, 1], [10, 2, 2, 2]],
                           'layer_2/mlp': [[0, 0, 0, 0], [4, 4, 4, 4]]}}
    # grown: 16, 6, 6, 6 -> busiest 16 over a mean of 8.5
    assert read({'stats_open': a, 'stats_close': b}) == pytest.approx(
        16 / 8.5)
    assert read({'stats_open': {}, 'stats_close': {'decode_calls': 3}}) \
        is None
    assert read({'stats_open': a, 'stats_close': a}) is None


def test_every_new_metric_has_its_files_and_names_the_cell():
    names = {m['name']: m for m in manifest.per_layer(MANIFEST, CELL)}
    for name in ('kernel.indexer_decode_roofline',
                 'kernel.sparse_latent_decode_roofline',
                 'kernel.expert_mlp_roofline',
                 'moe.expert_load_max_over_mean',
                 'engine.sparse_decode_share_pct'):
        metric = names[name]
        assert metric['workloads'] == [CELL]
        assert metric['moves'] == 'serve_tokens_per_s'
        manifest.reader(metric['spec']['reader'])
        if 'costs' in metric['spec']['args']:
            manifest.roofline(metric['spec']['args']['costs'])
    assert 'kernel.paged_decode_roofline' not in names
    assert 'serve.mfu_pct' in names


def test_rehearsal_of_the_cell_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_cpu_multi_thread_eigen=false',
               OMP_NUM_THREADS='1')
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'perfbench', 'run.py'),
         '--workload', CELL, '--seed', '2147483651', '--seconds', '4',
         '--trace', '0', '--rehearse'], cwd=ROOT, env=env, timeout=420,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith('[rehearsal] ')
    result = json.loads(last[len('[rehearsal] '):])
    assert result['correct'] is True and result['failed'] == 0
    assert result['compared']['paged_cache']['value'] == 1
    assert set(result['metrics']) == {
        'rehearsal.itl_p95_ms', 'rehearsal.serve_tokens_per_s',
        'rehearsal.setup_s'}
