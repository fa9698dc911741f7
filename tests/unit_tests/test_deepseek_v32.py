"""DeepSeek-V3.2 at the tiny size on the CPU (`deepseek-v32-tiny`: one
dense and two expert layers, 16 routed experts in 4 groups, `index_topk`
16, YaRN on), seeded weights: the program against the plain reference
(perfbench/references/deepseek_v32.py), through the page pool and
without it; the shares of an expert-parallel deployment add up;
dropless under skew; the faults the benchmark's comparison must catch.
"""
import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import deepseek as ds
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import sparse_latent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

REF = manifest.reference('deepseek_v32')
with open(os.path.join(ROOT, 'perfbench', 'configs',
                       'deepseek-v32-l5-ep16.json'), encoding='utf-8') as f:
    _FILE = json.load(f)
#: The configuration file at its tiny preset: what the reference reads.
FILE_CFG = dict(_FILE, **_FILE['rehearse'])
CFG = ds.DeepseekConfig.v32_tiny(dtype=jnp.float32)
#: What the tiny size's comparison allows: float32 through the pool
#: reads 0; every fault below and the int8 control read 0.09 or more.
TINY_MARGIN = 0.05


def _init(cfg):
    return nn.meta.unbox(jax.jit(ds.Deepseek(cfg).init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])


def _forward(cfg, params, toks):
    """Logits [T, V] of the uncached forward pass, as one program (an
    eager pass compiles every operation by itself)."""
    return jax.jit(ds.Deepseek(cfg).apply)({'params': params},
                                           jnp.asarray([toks]))[0]


@pytest.fixture(scope='module')
def params():
    return _init(CFG)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, 512, size=n).tolist()


def _shortfall(ref_lp, got_logits):
    """How far below the reference's best the other's first choice
    scores, at the worst position (the benchmark's comparison)."""
    pick = jnp.argmax(got_logits, axis=-1)
    chosen = jnp.take_along_axis(ref_lp, pick[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(ref_lp, axis=-1) - chosen))


def _through_the_pool(cfg, params, toks, chunk, n_prefill):
    """Logits [T, V] of `toks` served as the engine serves them:
    `n_prefill` tokens in chunks of `chunk` (the first from an empty
    row, later ones behind their history), then one token a call."""
    model = ds.Deepseek(cfg)
    toks = jnp.asarray([toks])
    pages = -(-toks.shape[1] // cfg.kv_page_size)
    page_row = jnp.arange(1, 1 + pages)[None, :]
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), toks[:, :1],
        positions=jnp.zeros((1, 1), jnp.int32), decode=True,
        page_indices=page_row)['cache']))
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    step = jax.jit(
        lambda cache, t, pos, prefill: model.apply(
            {'params': params, 'cache': cache}, t, positions=pos,
            decode=True, page_indices=page_row, prefill=prefill,
            page_aligned=t.shape[1] > 1, mutable=['cache'],
            live=jnp.ones(t.shape, bool)), static_argnums=3)
    out = []
    starts = list(range(0, n_prefill, chunk)) + list(
        range(n_prefill, toks.shape[1]))
    for lo in starts:
        hi = min(lo + chunk, n_prefill) if lo < n_prefill else lo + 1
        logits, mutated = step(cache, toks[:, lo:hi],
                               jnp.arange(lo, hi)[None, :], lo == 0)
        cache = mutated['cache']
        out.append(logits[0])
    return jnp.concatenate(out, axis=0), cache


def test_reference_equals_the_uncached_forward_pass(params):
    toks = _tokens(0, 96)
    got = jax.nn.log_softmax(_forward(CFG, params, toks), axis=-1)
    want = REF.log_probs(params, FILE_CFG, toks)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize('dtype, tolerance, why', [
    (jnp.float32, 2e-5, 'float32 both sides: rounding order only'),
    (jnp.bfloat16, None,
     'bf16 rounds every activation to 8 bits, and at this size a rounded '
     'index score flips one of 16 selected positions of some 80, or a '
     'rounded router score one of 4 experts, which moves a logit by '
     'tenths: held to the shortfall the benchmark compares, under 0.6 '
     'nats (0.07 to 0.34 over three seeds), not to a distance'),
])
def test_prefill_chunks_then_decode_through_the_pool(dtype, tolerance, why):
    """Two 32-token chunks (a prompt longer than a chunk), then decode
    to 96: every context past 16 tokens selects."""
    cfg = dataclasses.replace(CFG, dtype=dtype, kv_total_pages=16)
    p = jax.tree.map(lambda x: x.astype(dtype).astype(jnp.float32),
                     _init(cfg))
    toks = _tokens(1, 96)
    got, cache = _through_the_pool(cfg, p, toks, chunk=32, n_prefill=64)
    want = REF.log_probs(p, FILE_CFG, toks)
    if tolerance is not None:
        err = jnp.abs(jax.nn.log_softmax(got.astype(jnp.float32), -1)
                      - want)
        assert float(jnp.max(err)) < tolerance, why
    else:
        assert _shortfall(want[20:], got[20:]) < 0.6, why
    # 32 decoded tokens, each behind more than `index_topk` others.
    assert int(cache['sparse_decode_tokens']) == 32


def test_prefill_chunks_take_the_chunk_kernel_where_it_fits(monkeypatch):
    """The tiny model with a row the kernel of ops/pallas_latent.py
    takes (128 summed values in a 256-wide row; `deepseek-v32-tiny`'s
    112 are refused and keep the walk), chunks and decode through the
    pool: with the backend steered to a TPU's answer and the kernel
    run by the Pallas interpreter, the logits are the walk's."""
    from skypilot_tpu.ops import pallas_latent, pallas_paged
    cfg = dataclasses.replace(CFG, kv_lora_rank=128, kv_total_pages=16)
    p = _init(cfg)
    toks = _tokens(3, 128)       # 8 pages a row: a block of 128 keys
    walk, _ = _through_the_pool(cfg, p, toks, chunk=32, n_prefill=96)
    calls = []
    compiled = pallas_latent._chunk_call

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return compiled(*args, **dict(kw, interpret=True))

    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    monkeypatch.setattr(pallas_latent, '_chunk_call', interpreted)
    kernel, cache = _through_the_pool(cfg, p, toks, chunk=32, n_prefill=96)
    # Traced once a layer for the chunk behind a history (the first
    # chunk's program is another trace): [heads, chunk, width].
    assert calls and set(calls) == {(4, 32, 256)}, calls
    assert float(jnp.max(jnp.abs(kernel - walk))) < 2e-5
    assert int(cache['sparse_decode_tokens']) == 32


def test_the_engine_serves_it_through_the_page_pool(params):
    """Admission, chunked prefill, the pipelined loop and the first
    token's handoff over the model's own page layout."""
    engine = ContinuousBatchingEngine(
        ds.Deepseek(CFG), params, num_slots=4, max_total_len=160,
        prefill_chunk=32)
    try:
        assert engine.paged and engine.page_layout.kind == 'latent'
        assert engine.attention_impl() == 'sparse_latent_xla'
        # Off a TPU, and 112 summed values are no whole lane tile.
        assert engine.chunk_attention_impl() == 'sparse_latent_xla'
        prompts = [_tokens(10 + i, n) for i, n in enumerate((70, 40, 100))]
        futs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        rows = [f.result(timeout=300) for f in futs]
        for prompt, row in zip(prompts, rows):
            assert row[:len(prompt)] == prompt and len(row) == len(prompt) + 12
            lp = REF.log_probs(params, FILE_CFG, row)
            for i in range(len(prompt), len(row)):
                assert float(lp[i - 1].max() - lp[i - 1, row[i]]) < 1e-4
        counters = engine.model_counters()
        prefill = sum(sum(block[1]) for block in
                      counters['expert_tokens'].values())
        # Every prompt token routed 4 ways in both expert layers, and
        # no padded tail among them (the live mask).
        assert prefill == 2 * 4 * sum(len(p) for p in prompts)
        # A live lane of a decode round is a token that gets committed:
        # the trailing round of the pipelined loop counts nothing.
        assert counters['sparse_decode_tokens'][''] == 3 * 12 == \
            engine.tokens_committed
        assert engine.first_tokens_synced == 0
        assert engine.pool_copy_lines() == {'decode': [],
                                            'prefill_suffix_32': []}
        with pytest.raises(ValueError, match='no wire form'):
            engine.export_chain(prompts[0])
    finally:
        engine.stop()


@pytest.mark.parametrize('kwargs, name', [
    ({'speculative_k': 2}, 'speculative decoding'),
    ({'decode_chunk': 2}, 'decode chunks'),
    ({'kv_spill_bytes': 1 << 20}, 'the spill tier'),
])
def test_what_cannot_take_latent_pages_refuses_by_name(params, kwargs,
                                                       name):
    with pytest.raises(ValueError, match=f'latent.*{name}'):
        ContinuousBatchingEngine(ds.Deepseek(CFG), params, num_slots=2,
                                 max_total_len=64, **kwargs)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips of four experts each: their parts, the shared expert
    counted once, are the whole layer's output."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, CFG.embed_dim))
    p = params['layer_1']['mlp']
    whole = jax.jit(ds.MoEByShare(CFG).apply)({'params': p}, x)
    no_routed = {k: v for k, v in p.items() if not k.startswith('expert_')}
    shared = ds.SwiGLU(dataclasses.replace(CFG, mlp_dim=CFG.moe_dim)).apply(
        {'params': p['shared']}, x)
    total = shared
    for offset in (0, 4, 8, 12):
        share = dataclasses.replace(CFG, experts_held=4,
                                    expert_offset=offset)
        mine = dict(no_routed, **{f'expert_{e}': p[f'expert_{e}']
                                  for e in range(offset, offset + 4)})
        total = total + jax.jit(ds.MoEByShare(share).apply)(
            {'params': mine}, x) - shared
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5
    # And the reference's share is the program's.
    share = dataclasses.replace(CFG, experts_held=4, expert_offset=4)
    mine = dict(no_routed, **{f'expert_{e}': p[f'expert_{e}']
                              for e in range(4, 8)})
    sizes = dict(REF.sizes_of(dict(FILE_CFG, experts_held=4,
                                   expert_offset=4)))
    want = jax.jit(lambda p, h: REF.experts(p, h, sizes, 'float32'))(
        mine, x[0])
    got = jax.jit(ds.MoEByShare(share).apply)({'params': mine}, x[:1])[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize('length, selects', [(16, False), (64, True)])
def test_sparse_equals_dense_up_to_index_topk_and_not_beyond(
        params, length, selects):
    toks = _tokens(4, length)
    sparse = _forward(CFG, params, toks)
    dense = _forward(dataclasses.replace(CFG, index_n_heads=0), params,
                     toks)
    apart = float(jnp.max(jnp.abs(sparse - dense)))
    if selects:
        want = REF.log_probs(params, FILE_CFG, toks)
        got = jax.nn.log_softmax(sparse, axis=-1)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        assert apart > 1e-2
    else:
        assert apart < 1e-5


def test_dropless_when_every_token_goes_to_one_held_expert(params):
    """A bias that makes expert 5 every token's first choice: all 200
    tokens reach it (more than a row block of the grouped pass), none
    is dropped, and the output is the dense reference's."""
    p = dict(params['layer_1']['mlp'])
    p['e_score_correction_bias'] = jnp.zeros((16,)).at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 200, CFG.embed_dim))
    share = dataclasses.replace(CFG, experts_held=8, expert_offset=0)
    mine = {k: v for k, v in p.items()
            if not k.startswith('expert_') or int(k[7:]) < 8}
    got, mutated = jax.jit(
        lambda v, x: ds.MoEByShare(share).apply(
            v, x, None, True, mutable=['cache']))({'params': mine}, x)
    counts = mutated['cache']['expert_tokens'][1]
    assert int(counts[5]) == 200
    assert mutated['cache']['expert_calls_touched'][1, 5] == 1
    sizes = dict(REF.sizes_of(dict(FILE_CFG, experts_held=8)))
    want = jax.jit(lambda p, h: REF.experts(p, h, sizes, 'float32'))(
        mine, x[0])
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5


@pytest.mark.parametrize('fault', [
    {'index_topk': 10 ** 6},            # the selection skipped
    {'index_topk': 8},                  # index_topk halved
    {'routed_scaling_factor': 1.0},     # the scaling 2.5 dropped
    'int8',                             # the control
    None,                               # and the program itself
])
def test_the_comparison_catches_the_faults_it_must(params, fault):
    toks = _tokens(6, 128)
    want = REF.log_probs(params, FILE_CFG, toks)[40:-1]
    if fault == 'int8':
        read = REF.control_shortfall(params, FILE_CFG, toks, 41, 128)
    else:
        cfg = dataclasses.replace(CFG, **(fault or {}))
        read = _shortfall(want, _forward(cfg, params, toks)[40:-1])
    if fault is None:
        assert read < 1e-4
    else:
        assert read > TINY_MARGIN


def test_kth_largest_and_the_tie_rule():
    x = jnp.asarray([[3.0, -1.0, 0.0, 0.0, 7.5, 0.0, -jnp.inf, 2.0],
                     [-jnp.inf] * 6 + [1.0, -2.0]])
    assert sparse_latent.kth_largest(x, 3).tolist() == [2.0, -jnp.inf]
    mask = sparse_latent.topk_mask(x, 5)
    # Equal scores: the earlier position first, as lax.top_k has it.
    assert mask[0].tolist() == [True, False, True, True, True, False,
                                False, True]
    assert mask[1].tolist() == [False] * 6 + [True, True]
    _, idx = jax.lax.top_k(x[0], 5)
    assert sorted(idx.tolist()) == [0, 2, 3, 4, 7]


@pytest.mark.parametrize('lengths', [
    [37, 0, 160, 1, 0, 0, 0, 0, 0, 129, 0],   # more rows than a step's
    [0, 0, 0],                                # no row holds a request
    [160, 160],                               # every page of every row
], ids=['ragged', 'all_dead', 'full'])
def test_the_decode_reads_follow_the_live_rows(lengths, monkeypatch):
    """The decode round's index read and its attention over the
    selection walk a work list of the live rows' blocks: against the
    whole-table formulas, with rows of length 0, lengths that end
    inside a block and a batch that is no multiple of a step's rows.
    Index keys past a row's length are NaN and must not reach a score;
    latent rows there are large, and a position that is not selected
    weighs 0."""
    monkeypatch.setattr(sparse_latent, 'DECODE_BLOCK_PAGES', 4)
    monkeypatch.setattr(sparse_latent, 'DECODE_ITEMS', 3)
    monkeypatch.setattr(sparse_latent, 'DECODE_ROWS', 4)
    batch, pages_per_row, page, heads, dim, width = (
        len(lengths), 10, 16, 3, 8, 24)
    lengths = jnp.asarray(lengths, jnp.int32)
    total = batch * pages_per_row + 1
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    table = (jax.random.permutation(keys[0], total - 1) + 1).reshape(
        batch, pages_per_row).astype(jnp.int32)
    position = jnp.arange(pages_per_row * page)
    written = (position[None] < lengths[:, None])     # [B, T]

    def pool(key, last, junk):
        flat = jnp.full((total * page, last), junk)
        at = (table[:, position // page] * page + position % page)
        rows = jax.random.normal(key, (batch, position.size, last))
        return flat.at[jnp.where(written, at, 0)].set(
            jnp.where(written[..., None], rows, junk)).at[:page].set(
                junk).reshape(1, total, page, last), rows

    index_pages, index_rows = pool(keys[1], dim, jnp.nan)
    latent_pages, latent_rows = pool(keys[2], width, 1e4)
    q_idx = jax.random.normal(keys[3], (batch, heads, dim))
    w_idx = jax.random.normal(keys[4], (batch, heads))
    scores = jax.jit(sparse_latent.index_scores_decode)(
        q_idx, w_idx, index_pages, table, lengths)
    want = jnp.einsum('bh,bht->bt', w_idx, jax.nn.relu(jnp.einsum(
        'bhd,btd->bht', q_idx, jnp.where(written[..., None], index_rows,
                                         0.0))))
    want = jnp.where(written, want, -jnp.inf)
    np.testing.assert_allclose(scores, want, rtol=1e-5, atol=1e-5)

    topk = 48
    idx, valid = sparse_latent.select_topk(scores, topk)
    assert valid.sum(axis=1).tolist() == jnp.minimum(lengths,
                                                     topk).tolist()
    q = jax.random.normal(keys[5], (batch, 5, width))
    got = jax.jit(lambda *a: sparse_latent.sparse_latent_decode(
        *a, scale=0.3, value_dim=16))(q, latent_pages, table, idx, valid)
    picked = jnp.zeros_like(written).at[
        jnp.arange(batch)[:, None], idx].max(valid)
    rows = jnp.where(written[..., None], latent_rows, 0.0)
    s = jnp.where(picked[:, None], jnp.einsum('bhw,btw->bht', q, rows) * 0.3,
                  -jnp.inf)
    want = jnp.einsum('bht,btc->bhc', jax.nn.softmax(s, axis=-1),
                      rows[..., :16])
    want = jnp.where((lengths > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_registry_names_and_page_layouts():
    from skypilot_tpu.recipes.train_lm import _build_model
    model, vocab, _ = _build_model('deepseek-v32-l5-ep16', 16384, False)
    cfg = model.config
    assert (vocab, cfg.num_layers, cfg.num_held, cfg.n_routed_experts) == (
        16160, 5, 16, 256)
    layout = cfg.page_layout()
    assert layout.kind == 'latent' and layout.row_values == 640 + 128
    # 640 bf16 values and a float32 key of 128, in each of 5 layers
    assert layout.describe(5, 2)['bytes_per_token'] == 8960
    tiny, _, _ = _build_model('deepseek-v32-tiny', 64, False)
    assert tiny.config.index_topk == 16
    lite, _, _ = _build_model('deepseek-tiny', 64, False)
    assert [a.name for a in lite.config.page_layout().arrays] == [
        'latent_pages']
    llama, _, _ = _build_model('llama-tiny', 64, False)
    assert [a.name for a in llama.config.page_layout().arrays] == [
        'k_pages', 'v_pages']
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))['params']
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 4635518208
