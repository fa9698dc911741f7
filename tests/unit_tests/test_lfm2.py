"""LFM2 at the tiny size on the CPU (`lfm2-tiny`: a dense conv layer,
then routed attention and conv layers, 16 experts of which 4 a token,
heads of 32 packed two to a pool row), seeded weights, float32: the
program against the plain reference (perfbench/references/lfm2.py),
without a cache and through the engine's (packed K/V pages for the
attention layers, a convolution's tail by slot for the conv layers); a
chunked prefill against an unchunked one; a round's dead lanes; planted
faults that the comparison must catch; the engine serving it.
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
from skypilot_tpu.models import lfm2
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

REF = manifest.reference('lfm2')
with open(os.path.join(ROOT, 'perfbench', 'configs',
                       'lfm2-24b-a2b-l10.json'), encoding='utf-8') as f:
    _FILE = json.load(f)
#: The configuration file at its tiny preset: what the reference reads.
FILE_CFG = dict(_FILE, **_FILE['rehearse'])
CFG = lfm2.Lfm2Config.tiny(dtype=jnp.float32)
SLOTS = 4
#: float32 on both sides, the sums in other orders (the convolution's
#: taps, paged and chunk-local attention, the experts one at a time in
#: the reference): what the comparisons through the cache allow, on
#: log-probabilities of size 6.
TOL = 1e-4


@pytest.fixture(scope='module')
def params():
    return nn.meta.unbox(jax.jit(lfm2.Lfm2(CFG).init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, 512, size=n).tolist()


def _fresh_cache(model, pages_per_row):
    """The cache as the engine makes it: one token a slot. The tails
    hold ones, not zeros: a sequence's start must not lean on what its
    slot's row held."""
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((SLOTS, 1), jnp.int32),
        positions=jnp.zeros((SLOTS, 1), jnp.int32), decode=True,
        page_indices=jnp.zeros((SLOTS, pages_per_row), jnp.int32))['cache']))
    return jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.ones if path[-1].key == 'conv_state'
                         else jnp.zeros)(s.shape, s.dtype), shapes)


def _through_the_cache(cfg, params, toks, chunk, n_prefill, slot=2):
    """Logits [T, V] of `toks` served as the engine serves them, in
    slot `slot` of `SLOTS`: `n_prefill` tokens in chunks of `chunk`,
    each padded to a power of two with the tail marked dead (the first
    from an empty sequence, later ones behind their history), then one
    token a round with the other lanes dead."""
    model = lfm2.Lfm2(cfg)
    pages = -(-(len(toks) + chunk) // cfg.kv_page_size)
    table = np.zeros((SLOTS, pages), np.int32)
    table[slot] = np.arange(1, 1 + pages)
    table = jnp.asarray(table)
    cache = _fresh_cache(model, pages)
    apply = jax.jit(
        lambda cache, t, pos, live, rows, slots, prefill: model.apply(
            {'params': params, 'cache': cache}, t, positions=pos,
            decode=True, page_indices=rows, prefill=prefill,
            page_aligned=t.shape[1] > 1 and chunk % cfg.kv_page_size == 0,
            live=live, slots=slots, mutable=['cache']),
        static_argnums=6)
    out = []
    for lo in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - lo)
        shape = 1 << (n - 1).bit_length()
        padded = toks[lo:lo + n] + [0] * (shape - n)
        pos = jnp.arange(lo, lo + shape)[None, :]
        logits, mutated = apply(
            cache, jnp.asarray([padded]), pos, pos < lo + n,
            table[slot:slot + 1], jnp.asarray([slot]), lo == 0)
        cache = mutated['cache']
        out.append(logits[0, :n])
    live = jnp.arange(SLOTS) == slot
    for i in range(n_prefill, len(toks)):
        cur = jnp.zeros((SLOTS, 1), jnp.int32).at[slot, 0].set(toks[i])
        logits, mutated = apply(
            cache, cur, jnp.full((SLOTS, 1), i, jnp.int32),
            live[:, None], table, None, False)
        cache = mutated['cache']
        out.append(logits[slot])
    return jax.nn.log_softmax(jnp.concatenate(out, axis=0), axis=-1), cache


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)))


def test_reference_equals_the_uncached_forward_pass(params):
    """float32 both sides, the same sums but for the experts' order and
    the convolution's (the program's taps in ops/ssm.causal_conv): 2e-6
    on log-probabilities of size 6."""
    toks = _tokens(0, 45)
    with jax.default_matmul_precision('highest'):
        got = jax.nn.log_softmax(jax.jit(lfm2.Lfm2(CFG).apply)(
            {'params': params}, jnp.asarray([toks]))[0], axis=-1)
    want = REF.log_probs(params, FILE_CFG, toks)
    assert _err(got, want) < 2e-6


@pytest.mark.parametrize('length, chunk, n_prefill', [
    (61, 32, 45),      # a ladder tail of 13 in a chunk of 16
    (50, 16, 37),      # three chunks, the last a tail of 5
    (30, 32, 23),      # one padded chunk, then decode
])
def test_prefill_chunks_then_decode_through_the_cache(params, length,
                                                      chunk, n_prefill):
    """Prompt lengths that are multiples of neither the chunk nor the
    ladder's sizes, every later chunk starting from the tails the one
    before left and every round from the slot's; `TOL`. The other
    slots' tails are what they were (ones), to the bit."""
    toks = _tokens(length, length)
    got, cache = _through_the_cache(CFG, params, toks, chunk, n_prefill)
    assert _err(got, REF.log_probs(params, FILE_CFG, toks)) < TOL
    for i, kind in enumerate(CFG.layer_types):
        if kind != 'conv':
            continue
        rows = np.asarray(cache[f'layer_{i}']['conv']['conv_state'])
        assert rows.shape == (SLOTS, 2 * CFG.embed_dim)
        assert (rows[[0, 1, 3]] == 1).all() and not (rows[2] == 1).all()


def test_a_chunked_prefill_equals_an_unchunked_one(params):
    """96 prompt tokens in one chunk and in six of 16: the tails and the
    pages carry the history across chunk boundaries; float32, other
    sums' order in the paged chunk attention: 1e-5."""
    toks = _tokens(7, 100)
    whole, _ = _through_the_cache(CFG, params, toks, 128, 96)
    chunked, _ = _through_the_cache(CFG, params, toks, 16, 96)
    assert _err(whole, chunked) < 1e-5


def test_a_dead_lanes_tail_is_unchanged_by_a_round():
    """A round's step on the live lanes equals the chunk path's on one
    token (ops/ssm.causal_conv), to the bit; a dead lane's tail is
    what it was, to the bit."""
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    width = 24
    tail = jax.random.normal(k[0], (4, 2, width))
    u = jax.random.normal(k[1], (4, width))
    w = jax.random.normal(k[2], (3, width))
    live = jnp.asarray([True, False, True, False])
    conv, new = lfm2.conv_step(tail.reshape(4, 2 * width), u, w, live)
    want, want_tail = ssm.causal_conv(u[:, None], tail, w, None,
                                      jnp.ones(4, jnp.int32))
    new = np.asarray(new).reshape(4, 2, width)
    for lane in range(4):
        assert (np.asarray(conv[lane]) == np.asarray(want[lane, 0])).all()
        assert (new[lane] == np.asarray(
            want_tail[lane] if live[lane] else tail[lane])).all()


class _Unnormed(nn.Module):
    """The planted fault's stand-in for the q and k norms: nothing."""
    eps: float = 1e-5
    dtype: object = jnp.float32

    def __call__(self, x):
        return x.astype(self.dtype)


RMSNORM, ROUTE = lfm2.RMSNorm, ds.route


def _no_qk_norm(eps, dtype, name=None):
    if name in ('q_layernorm', 'k_layernorm'):
        return _Unnormed(eps, dtype, name=name)
    return RMSNORM(eps, dtype, name=name)


def _route_without_bias(cfg, logits, bias):
    return ROUTE(cfg, logits, jnp.zeros_like(bias))


def _route_weighting_with_bias(cfg, logits, bias):
    chosen, _ = ROUTE(cfg, logits, bias)
    picked = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias, chosen,
                                 axis=1)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


@pytest.mark.parametrize('fault', ['zeroed_tail', 'no_qk_norm',
                                   'bias_not_in_choice',
                                   'bias_in_weights'])
def test_planted_faults_are_caught(params, monkeypatch, fault):
    """Each fault moves the served log-probabilities by three times
    `TOL` or more, where the sound program reads 1.4e-6 here. Read on
    these 80 tokens (64 in two chunks, then 16 rounds): a later chunk
    started from a zeroed tail, 0.96 (the first chunk as it was: the
    conv mixers carry signal at this size, their projections seeded at
    1 / sqrt(fan-in)); q and k not normed, 0.061; the expert bias left
    out of the choice, 0.0092; the bias put into the weights, 5.9e-4
    (a bias of 0.02 against scores near 0.5)."""
    if fault == 'zeroed_tail':
        conv = ssm.causal_conv
        monkeypatch.setattr(
            ssm, 'causal_conv', lambda x, tail, weight, bias, lengths: conv(
                x, jnp.zeros_like(tail), weight, bias, lengths))
    elif fault == 'no_qk_norm':
        monkeypatch.setattr(lfm2, 'RMSNorm', _no_qk_norm)
    elif fault == 'bias_not_in_choice':
        monkeypatch.setattr(ds, 'route', _route_without_bias)
    else:
        monkeypatch.setattr(ds, 'route', _route_weighting_with_bias)
    toks = _tokens(5, 80)
    got, _ = _through_the_cache(CFG, params, toks, 32, 64)
    want = REF.log_probs(params, FILE_CFG, toks)
    if fault == 'zeroed_tail':
        assert _err(got[:32], want[:32]) < TOL
        assert _err(got[32:64], want[32:64]) > 10 * TOL
    assert _err(got, want) > 3 * TOL


def _greedy_under_the_reference(params, row, n_prompt):
    lp = REF.log_probs(params, FILE_CFG, row)
    return max(float(lp[i - 1].max() - lp[i - 1, row[i]])
               for i in range(n_prompt, len(row)))


def test_the_engine_serves_it_with_tails_by_slot(params):
    """Admission, chunked prefill with ladder tails, the pipelined loop
    and the first token's handoff; three requests interleaved in one
    batch of two slots, so the third takes a slot a finished request
    left: each as if served alone (the reference serves it alone). The
    engine's /stats routes and state pool, and no copy of a pool array
    or of the tails in its programs."""
    engine = ContinuousBatchingEngine(
        lfm2.Lfm2(CFG), params, num_slots=2, max_total_len=160,
        prefill_chunk=32)
    try:
        assert engine.paged and engine.slot_state
        assert engine.page_layout.kind == 'kv_packed'
        assert engine.prefix_cache is None
        assert engine.attention_impl() == 'packed_heads_xla'
        assert engine.chunk_attention_impl() == 'packed_heads_xla'
        assert engine.expert_impl() == 'switch_loop'
        assert engine.ssm_update_impl() == 'none'
        prompts = [_tokens(10 + i, n) for i, n in enumerate((70, 41, 100))]
        futs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        rows = [f.result(timeout=300) for f in futs]
        for prompt, row in zip(prompts, rows):
            assert row[:len(prompt)] == prompt and len(row) == len(prompt) + 12
            assert _greedy_under_the_reference(params, row,
                                               len(prompt)) < TOL
        counters = engine.model_counters()
        routed = [f'layer_{i}/feed_forward' for i in range(1, 5)]
        assert sorted(counters['expert_tokens']) == routed
        prefill = sum(sum(block[1]) for block in
                      counters['expert_tokens'].values())
        assert prefill == 4 * 4 * sum(len(p) for p in prompts)
        assert engine.first_tokens_synced == 0
        # The pages and a chunk's tail rows are written where they lie.
        # XLA:CPU copies the tails around a round's select; the program
        # compiled for the chip copies none into HBM
        # (test_pool_write_aot.py).
        copies = engine.pool_copy_lines()
        assert copies['prefill_suffix_32'] == []
        assert all(' f32[2,256]' in line for line in copies['decode'])
        assert engine.state_pool_stats() == {
            'arrays': {'conv_state': [256]}, 'layers': 3, 'slots': 2,
            'bytes_per_slot': 3 * 256 * 4, 'bytes': 2 * 3 * 256 * 4}
    finally:
        engine.stop()


def test_a_request_preempted_by_page_pressure_is_regenerated(params):
    """A pool too small for three requests at once: one is preempted,
    re-queued and prefilled again from zeros in whatever slot is free;
    its tokens are the ones it would have had alone."""
    cfg = dataclasses.replace(CFG, kv_page_size=4, kv_total_pages=20)
    engine = ContinuousBatchingEngine(
        lfm2.Lfm2(cfg), params, num_slots=3, max_total_len=40,
        prefill_chunk=8)
    try:
        prompts = [_tokens(20 + i, n) for i, n in enumerate((13, 9, 11))]
        futs = [engine.submit(p, max_new_tokens=18) for p in prompts]
        rows = [f.result(timeout=300) for f in futs]
        assert engine.preemptions >= 1
    finally:
        engine.stop()
    for prompt, row in zip(prompts, rows):
        assert row[:len(prompt)] == prompt and len(row) == len(prompt) + 18
        assert _greedy_under_the_reference(params, row, len(prompt)) < TOL


def test_registry_names_and_the_layout():
    from skypilot_tpu.recipes.train_lm import _build_model
    model, vocab, _ = _build_model('lfm2-24b-a2b-l10', 2560, False)
    cfg = model.config
    assert vocab == 65536 and cfg.layer_types == \
        lfm2.LFM2_24B_LAYERS[:10]
    assert cfg.layer_types.count('conv') == 8
    assert (cfg.num_held, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_dim, cfg.mlp_dim, cfg.head_dim) == (
                64, 64, 4, 1536, 11776, 64)
    layout = cfg.page_layout()
    assert layout.kind == 'kv_packed' and layout.layers == 2
    assert [(a.name, a.heads, a.width) for a in layout.arrays] == [
        ('k_pages', 1, 512), ('v_pages', 1, 512)]
    assert [(a.name, a.shape) for a in layout.slot_arrays] == [
        ('conv_state', (4096,))]
    assert layout.slot_layers == 8 and layout.slot_bytes(2) == 65536
    assert layout.describe(10, 2)['bytes_per_token'] == 4096
    tiny, vocab, _ = _build_model('lfm2-tiny', 64, False)
    assert vocab == 512 and set(tiny.config.layer_types) == {
        'conv', 'full_attention'}
    with pytest.raises(ValueError, match='layer_types'):
        lfm2.Lfm2(dataclasses.replace(CFG, layer_types=('conv', 'mamba'))
                  ).init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))


def test_a_layer_without_a_shared_expert_holds_none(params):
    """`n_shared_experts` 0: MoEByShare builds no shared expert and its
    output is the routed experts' alone."""
    p = params['layer_1']['feed_forward']
    assert 'shared' not in p and 'router' in p
    assert sorted(k for k in p if k.startswith('expert_')) == sorted(
        f'expert_{e}' for e in range(16))


def test_a_round_through_the_streamed_projections_equals_xlas(monkeypatch):
    """A decode round of a conv mixer at 16 slots in bf16, its two
    projections through ops/pallas_streamed (interpret mode, the route
    a TPU takes) and through XLA's product: the same output and the
    same tails, and the same parameters (one init for both)."""
    import functools
    from skypilot_tpu.ops import pallas_streamed
    cfg = lfm2.Lfm2Config.tiny()
    conv = lfm2.ShortConv(cfg)
    slots = 16
    h = jax.random.normal(jax.random.PRNGKey(3), (slots, 1, 128),
                          jnp.bfloat16)
    variables = conv.init(jax.random.PRNGKey(4), h, decode=True)
    variables = {'params': variables['params'], 'cache': jax.tree.map(
        lambda t: jax.random.normal(jax.random.PRNGKey(5), t.shape,
                                    t.dtype), variables['cache'])}
    live = jnp.arange(slots)[:, None] % 3 != 0

    def round_():
        return conv.apply(variables, h, decode=True, live=live,
                          mutable=['cache'])

    assert not lfm2._streams(cfg, slots)
    want, want_cache = round_()
    monkeypatch.setattr(pallas_streamed, 'available', lambda: True)
    shapes = []
    monkeypatch.setattr(
        pallas_streamed, 'streamed_matmul',
        functools.partial(_interpreted, shapes,
                          pallas_streamed.streamed_matmul))
    assert lfm2._streams(cfg, slots) and not lfm2._streams(cfg, 4)
    got, got_cache = round_()
    assert shapes == [((slots, 128), (128, 384)), ((slots, 128), (128, 128))]
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(
        np.asarray(got_cache['cache']['conv_state'], np.float32),
        np.asarray(want_cache['cache']['conv_state'], np.float32))


def _interpreted(shapes, streamed_matmul, x, w):
    shapes.append((x.shape, w.shape))
    return streamed_matmul(x, w, interpret=True)


def test_decode_rounds_on_the_packed_kernel_route(params):
    """The rounds of `test_prefill_chunks_then_decode_through_the_cache`
    (one lane live of four, the dead lanes at length 0) with the decode
    read on the kernel of ops/pallas_packed.py, in interpret mode: the
    reference's log-probabilities to `TOL`, as on the gather's route."""
    from skypilot_tpu.ops import pallas_paged
    toks = _tokens(61, 61)
    with pallas_paged.impl_scope('packed_decode_interpret'):
        got, _ = _through_the_cache(CFG, params, toks, 32, 45)
    assert _err(got, REF.log_probs(params, FILE_CFG, toks)) < TOL


def _served(params, impl):
    from skypilot_tpu.ops import pallas_paged
    with pallas_paged.impl_scope(impl):
        engine = ContinuousBatchingEngine(
            lfm2.Lfm2(CFG), params, num_slots=2, max_total_len=96,
            prefill_chunk=32)
        try:
            assert engine.attention_impl() == (
                'packed_heads_xla' if impl == 'xla' else impl)
            assert engine.chunk_attention_impl() == 'packed_heads_xla'
            prompts = [_tokens(30 + i, n) for i, n in enumerate((37, 20, 45))]
            futs = [engine.submit(p, max_new_tokens=10) for p in prompts]
            return [f.result(timeout=300) for f in futs]
        finally:
            engine.stop()


def test_the_engine_serves_the_same_tokens_on_the_packed_kernel(params):
    """Three requests through two slots, so that rounds run with a lane
    dead and a slot is taken over: the kernel's route (interpret mode)
    gives the gather's tokens."""
    kernel = _served(params, 'packed_decode_interpret')
    assert kernel == _served(params, 'xla')
    assert len({tuple(row) for row in kernel}) == 3


@pytest.mark.parametrize('heads, want', [(4, 'packed_heads_xla'),
                                         (2, 'packed_decode')],
                         ids=['rows_of_64', 'rows_of_128'])
def test_the_engine_names_the_packed_route_its_decode_holds(
        monkeypatch, heads, want):
    """`attention_impl()` (/stats, the gauge) where the compiled routes
    exist: the kernel for a packed row of whole lane tiles (two K/V
    heads of 64), the gather for lfm2-tiny's row of 64 lanes; a chunk
    reads by the gather either way."""
    from skypilot_tpu.ops import pallas_paged
    cfg = dataclasses.replace(CFG, num_heads=heads, num_kv_heads=2)
    model = lfm2.Lfm2(cfg)
    p = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    engine = ContinuousBatchingEngine(model, p, num_slots=2,
                                      max_total_len=64)
    try:
        assert engine.attention_impl() == 'packed_heads_xla'   # this CPU
        monkeypatch.setattr(pallas_paged, 'available', lambda: True)
        assert engine.attention_impl() == want
        assert engine.chunk_attention_impl() == 'packed_heads_xla'
    finally:
        engine.stop()
