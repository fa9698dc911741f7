"""Nemotron-H at the tiny size on the CPU (`nemotron-h-tiny`: pattern
`ME*EM`, `chunk_size` 8, 16 experts of which 4 a token, in a latent),
seeded weights, float32: the program against the plain reference
(perfbench/references/nemotron_h.py), without a cache and through the
engine's (pages for the attention layer, state by slot for the Mamba
layers); the scan against the step-by-step recurrence; requests
interleaved, a slot reused, a request preempted and regenerated; the
shares of an expert-parallel deployment add up; what assumes that a
sequence is its pages refuses by name.
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
from skypilot_tpu.models import nemotron_h as nh
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import manifest  # noqa: E402

REF = manifest.reference('nemotron_h')
with open(os.path.join(ROOT, 'perfbench', 'configs',
                       'nemotron3-super-l11-ep4.json'),
          encoding='utf-8') as f:
    _FILE = json.load(f)
#: The configuration file at its tiny preset: what the reference reads.
FILE_CFG = dict(_FILE, **_FILE['rehearse'])
CFG = nh.NemotronHConfig.tiny(dtype=jnp.float32)
SLOTS = 4


@pytest.fixture(scope='module')
def params():
    return nn.meta.unbox(jax.jit(nh.NemotronH(CFG).init)(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, 512, size=n).tolist()


def _fresh_cache(model, pages_per_row):
    """The cache as the engine makes it: one token a slot."""
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((SLOTS, 1), jnp.int32),
        positions=jnp.zeros((SLOTS, 1), jnp.int32), decode=True,
        page_indices=jnp.zeros((SLOTS, pages_per_row), jnp.int32))['cache']))
    # The state's rows hold ones, not zeros: a sequence's start must
    # not lean on what its slot's rows held.
    return jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.ones if path[-1].key in (
            'ssm_state', 'conv_state') else jnp.zeros)(s.shape, s.dtype),
        shapes)


def _through_the_cache(cfg, params, toks, chunk, n_prefill, slot=2):
    """Logits [T, V] of `toks` served as the engine serves them, in
    slot `slot` of `SLOTS`: `n_prefill` tokens in chunks of `chunk`,
    each padded to a power of two with the tail marked dead (the first
    from an empty sequence, later ones behind their history), then one
    token a round with the other lanes dead."""
    model = nh.NemotronH(cfg)
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
    return jnp.concatenate(out, axis=0), cache


def test_reference_equals_the_uncached_forward_pass(params):
    """float32 both sides and the same order of sums but inside the
    scan's sub-chunks: 1e-6 on log-probabilities of size 6."""
    toks = _tokens(0, 45)
    with jax.default_matmul_precision('highest'):
        got = jax.nn.log_softmax(jax.jit(nh.NemotronH(CFG).apply)(
            {'params': params}, jnp.asarray([toks]))[0], axis=-1)
    want = REF.log_probs(params, FILE_CFG, toks)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


@pytest.mark.parametrize('length, chunk, n_prefill', [
    (61, 32, 45),      # a ladder tail of 13 in a chunk of 16
    (50, 32, 37),      # 37 = 32 + 5: a tail under one sub-chunk
    (30, 32, 23),      # one padded chunk, then decode
])
def test_prefill_chunks_then_decode_through_the_cache(params, length,
                                                      chunk, n_prefill):
    """Prompt lengths that are multiples of neither the chunk, nor
    `chunk_size` 8, nor the ladder's sizes; float32 both sides, other
    sums' order in the chunked scan and the paged attention: 1e-4."""
    toks = _tokens(length, length)
    got, cache = _through_the_cache(CFG, params, toks, chunk, n_prefill)
    want = REF.log_probs(params, FILE_CFG, toks)
    err = jnp.abs(jax.nn.log_softmax(got, axis=-1) - want)
    assert float(jnp.max(err)) < 1e-4
    mixer = cache['layer_0']['mixer']
    assert int(mixer['ssm_scan_tokens']) == n_prefill
    assert int(mixer['ssm_update_tokens']) == length - n_prefill
    # The other slots' rows are as they were (ones), in both arrays.
    for name in ('ssm_state', 'conv_state'):
        rows = np.asarray(mixer[name])
        assert (rows[[0, 1, 3]] == 1).all() and not (rows[2] == 1).all()


def test_the_state_not_carried_between_chunks_is_caught(params,
                                                        monkeypatch):
    """The planted fault of the benchmark's rehearsal, at the logits: a
    later chunk's scan started from zeros (its pages and its
    convolution's tail intact) moves them by thousands of times the
    tolerance above. (With D at the published initialisation's ones
    the same fault moved them by 0.005: models/nemotron_h.py seeds D
    with zeros so that the recurrence is what the comparison guards.)"""
    scan = ssm.ssm_scan
    monkeypatch.setattr(
        ssm, 'ssm_scan',
        lambda x, dt, a, b, c, d, state, lengths, chunk_size: scan(
            x, dt, a, b, c, d, jnp.zeros_like(state), lengths, chunk_size))
    toks = _tokens(5, 96)
    got, _ = _through_the_cache(CFG, params, toks, 32, 96)
    got = jax.nn.log_softmax(got, axis=-1)
    want = REF.log_probs(params, FILE_CFG, toks)
    assert float(jnp.max(jnp.abs(got[:32] - want[:32]))) < 1e-4
    assert float(jnp.max(jnp.abs(got[32:] - want[32:]))) > 0.1


def _scan_inputs(seed, batch, seq, heads=8, hd=4, groups=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (batch, seq, heads, hd)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads))),
        a=-jnp.exp(jax.random.normal(k[2], (heads,))),
        b=jax.random.normal(k[3], (batch, seq, groups, n)),
        c=jax.random.normal(k[4], (batch, seq, groups, n)),
        d=jax.random.normal(k[5], (heads,)),
        state=jax.random.normal(k[6], (batch, heads, hd, n)))


@pytest.mark.parametrize('seq, chunk_size', [(29, 8), (64, 16), (5, 8)])
def test_scan_equals_the_recurrence_from_a_random_state(seq, chunk_size):
    """Any length, any start; float32, the sums in another order: 2e-5
    on values of size 10."""
    args = _scan_inputs(seq, 2, seq)
    lengths = jnp.asarray([seq, max(seq - 12, 1)])
    y, h = ssm.ssm_scan(**args, lengths=lengths, chunk_size=chunk_size)
    y_ref, h_ref = ssm.ssm_reference(**args, lengths=lengths)
    valid = (jnp.arange(seq)[None, :] < lengths[:, None])[..., None, None]
    assert float(jnp.max(jnp.abs(jnp.where(valid, y - y_ref, 0)))) < 2e-5
    assert float(jnp.max(jnp.abs(h - h_ref))) < 2e-5


def test_a_padded_chunk_leaves_state_and_tail_as_the_unpadded_one():
    """To the bit: a padded position has dt = 0 (it neither decays nor
    feeds the state) and stays out of the convolution's tail."""
    args = _scan_inputs(3, 1, 32)
    cut = {k: (v[:, :19] if v.ndim > 1 and v.shape[1] == 32 else v)
           for k, v in args.items()}
    y_pad, h_pad = ssm.ssm_scan(**args, lengths=jnp.asarray([19]),
                                chunk_size=8)
    y_cut, h_cut = ssm.ssm_scan(**cut, lengths=jnp.asarray([19]),
                                chunk_size=8)
    assert (np.asarray(h_pad) == np.asarray(h_cut)).all()
    assert (np.asarray(y_pad[:, :19]) == np.asarray(y_cut)).all()
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(k[0], (1, 32, 24))
    tail = jax.random.normal(k[1], (1, 3, 24))
    w, bias = jax.random.normal(k[2], (4, 24)), jax.random.normal(k[3], (24,))
    out_pad, tail_pad = ssm.causal_conv(x, tail, w, bias, jnp.asarray([19]))
    out_cut, tail_cut = ssm.causal_conv(x[:, :19], tail, w, bias,
                                        jnp.asarray([19]))
    assert (np.asarray(tail_pad) == np.asarray(tail_cut)).all()
    assert (np.asarray(tail_pad) == np.asarray(x[:, 16:19])).all()
    assert (np.asarray(out_pad[:, :19]) == np.asarray(out_cut)).all()
    # A chunk of two valid tokens keeps one row of the old tail.
    _, short = ssm.causal_conv(x, tail, w, bias, jnp.asarray([2]))
    assert (np.asarray(short[0, 0]) == np.asarray(tail[0, 2])).all()
    assert (np.asarray(short[0, 1:]) == np.asarray(x[0, :2])).all()


def test_a_dead_lanes_rows_are_unchanged_by_a_round():
    """A round's step on the live lanes equals the chunk path's on one
    token; a dead lane's state and tail are what they were, to the
    bit, and its output zeros."""
    heads, hd, groups, n = 8, 4, 2, 16
    width = heads * hd + 2 * groups * n
    k = jax.random.split(jax.random.PRNGKey(6), 8)
    state = jax.random.normal(k[0], (4, heads, hd, n))
    tail = jax.random.normal(k[1], (4, 3, width))
    xbc = jax.random.normal(k[2], (4, width))
    dt = jax.nn.softplus(jax.random.normal(k[3], (4, heads)))
    a, d = -jnp.exp(jax.random.normal(k[4], (heads,))), jnp.ones((heads,))
    w, bias = jax.random.normal(k[5], (4, width)), jnp.ones((width,))
    live = jnp.asarray([True, False, True, False])
    # In the slot's layout (ops/ssm.slot_shapes), and back.
    y, h, new = ssm.ssm_update(ssm.state_to_slot(state),
                               ssm.tail_to_slot(tail, heads), xbc, dt, a, d,
                               w, bias, live, groups=groups)
    h, new = ssm.state_from_slot(h), ssm.tail_from_slot(new, 4)
    conv, want_tail = ssm.causal_conv(xbc[:, None], tail, w, bias,
                                      jnp.ones(4, jnp.int32))
    act = jax.nn.silu(conv)
    inner, bc = heads * hd, groups * n
    y_ref, h_ref = ssm.ssm_reference(
        act[..., :inner].reshape(4, 1, heads, hd), dt[:, None], a,
        act[..., inner:inner + bc].reshape(4, 1, groups, n),
        act[..., inner + bc:].reshape(4, 1, groups, n), d, state,
        jnp.ones(4, jnp.int32))
    for lane in range(4):
        if live[lane]:
            assert float(jnp.max(jnp.abs(h[lane] - h_ref[lane]))) < 1e-5
            assert float(jnp.max(jnp.abs(y[lane] - y_ref[lane, 0]))) < 1e-5
            assert (np.asarray(new[lane])
                    == np.asarray(want_tail[lane])).all()
        else:
            assert (np.asarray(h[lane]) == np.asarray(state[lane])).all()
            assert (np.asarray(new[lane]) == np.asarray(tail[lane])).all()
            assert not np.asarray(y[lane]).any()


def _greedy_under_the_reference(params, row, n_prompt):
    lp = REF.log_probs(params, FILE_CFG, row)
    return max(float(lp[i - 1].max() - lp[i - 1, row[i]])
               for i in range(n_prompt, len(row)))


def test_the_engine_serves_it_with_state_by_slot(params):
    """Admission, chunked prefill with ladder tails, the pipelined loop
    and the first token's handoff; three requests interleaved in one
    batch of two slots, so the third takes a slot a finished request
    left: each as if served alone (the reference serves it alone)."""
    engine = ContinuousBatchingEngine(
        nh.NemotronH(CFG), params, num_slots=2, max_total_len=160,
        prefill_chunk=32)
    try:
        assert engine.paged and engine.slot_state
        assert engine.page_layout.kind == 'kv'
        assert engine.prefix_cache is None
        prompts = [_tokens(10 + i, n) for i, n in enumerate((70, 41, 100))]
        futs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        rows = [f.result(timeout=300) for f in futs]
        for prompt, row in zip(prompts, rows):
            assert row[:len(prompt)] == prompt and len(row) == len(prompt) + 12
            assert _greedy_under_the_reference(params, row,
                                               len(prompt)) < 1e-4
        counters = engine.model_counters()
        # Two Mamba layers saw every prompt token once and nothing of a
        # padded tail; a live lane-step is a token that gets committed.
        assert set(counters['ssm_scan_tokens']) == {'layer_0/mixer',
                                                    'layer_4/mixer'}
        for block in counters['ssm_scan_tokens'].values():
            assert block == sum(len(p) for p in prompts)
        for block in counters['ssm_update_tokens'].values():
            assert block == 3 * 12 == engine.tokens_committed
        prefill = sum(sum(block[1]) for block in
                      counters['expert_tokens'].values())
        assert prefill == 2 * 4 * sum(len(p) for p in prompts)
        assert engine.first_tokens_synced == 0
        # XLA:CPU copies what a loop carries (the live rows' loop of
        # ops/ssm.ssm_update) at the loop's ends; the program compiled
        # for the chip has none (test_pool_write_aot.py, chip_smoke.py).
        # The pages and a chunk's slot rows are written in place here
        # too.
        copies = engine.pool_copy_lines()
        assert copies['prefill_suffix_32'] == []
        assert all('f32[2,16,16,8]' in line or 'f32[2,72,8]' in line
                   for line in copies['decode'])
        state = engine.state_pool_stats()
        assert state == {
            'arrays': {'ssm_state': [16, 16, 8], 'conv_state': [72, 8]},
            'layers': 2, 'slots': 2,
            'bytes_per_slot': 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4),
            'bytes': 2 * 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4)}
        assert engine.kv_cache_bytes() == 2 * 2 * 128 * 16 * 128 * 4 + \
            sum(4 * n for n in (2 * 2 * 16 * 2, 2, 2))
        for what, call in (
                ('export_chain', lambda: engine.export_chain(prompts[0])),
                ('import_chain', lambda: engine.import_chain(b'')),
                ('live migration', engine.evacuate_chains)):
            with pytest.raises(ValueError,
                               match=f'state by slot.*{what}'):
                call()
    finally:
        engine.stop()


def test_a_request_preempted_by_page_pressure_is_regenerated(params):
    """A pool too small for three requests at once: one is preempted,
    re-queued and prefilled again from zeros in whatever slot is free;
    its tokens are the ones it would have had alone."""
    cfg = dataclasses.replace(CFG, kv_page_size=4, kv_total_pages=20)
    engine = ContinuousBatchingEngine(
        nh.NemotronH(cfg), params, num_slots=3, max_total_len=40,
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
        assert _greedy_under_the_reference(params, row, len(prompt)) < 1e-4


@pytest.mark.parametrize('kwargs, name', [
    ({'speculative_k': 2}, r'speculative decoding \(--speculative\)'),
    ({'decode_chunk': 2}, r'decode chunks \(--decode-chunk\)'),
    ({'kv_spill_bytes': 1 << 20}, r'the spill tier \(--kv-spill-bytes'),
    ({'kv_cold_dir': '/nonexistent'}, r'the spill tier'),
    ({'paged': False}, r'the dense per-slot cache'),
])
def test_what_assumes_a_sequence_is_its_pages_refuses_by_name(
        params, kwargs, name):
    with pytest.raises(ValueError,
                       match=f'recurrent state by slot.*{name}'):
        ContinuousBatchingEngine(nh.NemotronH(CFG), params, num_slots=2,
                                 max_total_len=64, **kwargs)


def test_an_int8_pool_and_a_mesh_refuse_by_name(params):
    cfg = dataclasses.replace(CFG, kv_dtype='int8')
    with pytest.raises(ValueError, match=r'state by slot.*--kv-dtype int8'):
        ContinuousBatchingEngine(nh.NemotronH(cfg), params, num_slots=2,
                                 max_total_len=64)
    from skypilot_tpu.parallel import mesh as mesh_lib
    for axes, name in (({'tensor': 2}, r'a tensor mesh \(--tensor\)'),
                       ({'stage': 2}, r'pipeline stages \(--stages\)')):
        mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(**axes),
                                  devices=jax.devices()[:2])
        with pytest.raises(ValueError, match=f'state by slot.*{name}'):
            ContinuousBatchingEngine(nh.NemotronH(CFG), params,
                                     num_slots=2, max_total_len=64,
                                     mesh=mesh)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips of four experts each (offsets 0, 4, 8, 12: the
    deployment's 0, 128, 256, 384 at the tiny counts): their routed
    parts, the shared expert counted once, are the whole layer's
    output; `latent_up` is linear, so the sums commute."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, CFG.embed_dim))
    p = params['layer_1']['mixer']
    kw = dict(expert_act='relu2', latent_dim=CFG.moe_latent_dim,
              shared_dim=CFG.moe_shared_dim)
    whole = jax.jit(ds.MoEByShare(CFG, **kw).apply)({'params': p}, x)
    no_routed = {k: v for k, v in p.items() if not k.startswith('expert_')}
    shared = ds.Relu2MLP(CFG.embed_dim, CFG.moe_shared_dim, CFG.dtype).apply(
        {'params': p['shared']}, x)
    total = shared
    for offset in (0, 4, 8, 12):
        share = dataclasses.replace(CFG, experts_held=4,
                                    expert_offset=offset)
        mine = dict(no_routed, **{f'expert_{e}': p[f'expert_{e}']
                                  for e in range(offset, offset + 4)})
        total = total + jax.jit(ds.MoEByShare(share, **kw).apply)(
            {'params': mine}, x) - shared
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5
    # And the reference's uncut layer (x + the mixer on the normed x;
    # here the norm's scale is ones and x stands for the normed input).
    sizes = REF.sizes_of(FILE_CFG)
    unit = {'norm': {'scale': jnp.ones((CFG.embed_dim,))}, 'mixer': p}
    normed = REF.rms_norm(x[0], unit['norm']['scale'], sizes['eps'])
    want = REF.experts(unit, x[0], sizes, 'float32') - x[0]
    got = jax.jit(ds.MoEByShare(CFG, **kw).apply)({'params': p},
                                                  normed[None])[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_registry_names_and_the_layout():
    from skypilot_tpu.recipes.train_lm import _build_model
    model, vocab, _ = _build_model('nemotron3-super-l11-ep4', 4096, False)
    cfg = model.config
    assert vocab == 32768 and cfg.pattern == 'MEMEMEMEM*E'
    assert (cfg.experts_held, cfg.n_routed_experts,
            cfg.num_experts_per_tok) == (128, 512, 22)
    layout = cfg.page_layout()
    assert layout.kind == 'kv' and layout.layers == 1
    assert [(a.name, a.heads, a.width) for a in layout.arrays] == [
        ('k_pages', 2, 128), ('v_pages', 2, 128)]
    assert [(a.name, a.shape) for a in layout.slot_arrays] == [
        ('ssm_state', (64, 128, 128)), ('conv_state', (240, 128))]
    assert layout.slot_layers == 5
    # 4,194,304 B of state and 61,440 B of tail, a slot and layer.
    assert layout.slot_bytes(2) == 5 * (4194304 + 61440)
    assert layout.describe(11, 2)['bytes_per_token'] == 1024
    tiny, vocab, _ = _build_model('nemotron-h-tiny', 64, False)
    assert vocab == 512 and tiny.config.pattern == 'ME*EM'
    assert set(tiny.config.pattern) == set('M*E')
    with pytest.raises(ValueError, match='letter'):
        nh.NemotronH(dataclasses.replace(CFG, pattern='M-')).init(
            jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
