"""Tensor × pipeline serving (parallel/serving.py stage split +
models/batching.py staged engine): the checklist for PR 19.

  - layer split: contiguous [lo, hi) ranges, remainder front-loaded,
    stage 0 owns the embedding and the last stage the head;
  - page math: a per-chip byte budget buys ~stages x the pages on
    top of the kv-heads shard split (each stage stores only its own
    layers' pages), widest-stage bound when layers don't divide;
  - bubble: the closed-form prefill fill/drain fraction
    (S-1)/(M+S-1) from the inference schedule;
  - zero resharding PER STAGE: every stage's compiled decode
    dispatch contains NO all-gather/all-to-all over a pool-shaped
    operand, and the guard still detects forced violations on a
    stage submesh (non-vacuous);
  - bit identity: greedy outputs of a (stage=2, tensor=2) engine
    equal single-device across paged bf16, int8 KV, chunked
    prefill, speculative decode, and an active LoRA adapter;
  - handoff: a chain exported from a staged pool imports into a
    single-device pool (and back) with byte-identical re-export —
    the wire format never sees the stage split;
  - guardrails: the staged engine rejects configurations it cannot
    serve bit-identically (dense cache, decode chunks, ragged slot
    groups, int8 weights).
"""
import dataclasses
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from skypilot_tpu.inference import kv_transfer, quant
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.models.llama import Llama, LlamaConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel.pipeline_schedule import \
    make_inference_schedule
from skypilot_tpu.parallel.serving import (
    build_staged_serving, pool_collective_lines, stage_layer_ranges)


@pytest.fixture(scope='module')
def setup():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=40)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshConfig(stage=2, tensor=2),
        devices=jax.devices()[:4])
    return model, params, mesh


# -- layer split + schedule units -------------------------------------------
def test_stage_layer_ranges():
    assert stage_layer_ranges(4, 2) == [(0, 2), (2, 4)]
    assert stage_layer_ranges(2, 2) == [(0, 1), (1, 2)]
    # Remainder front-loads: earlier stages take the extra layer.
    assert stage_layer_ranges(7, 3) == [(0, 3), (3, 5), (5, 7)]
    assert stage_layer_ranges(5, 1) == [(0, 5)]
    with pytest.raises(ValueError):
        stage_layer_ranges(2, 3)        # more stages than layers
    with pytest.raises(ValueError):
        stage_layer_ranges(2, 0)


def test_prefill_bubble_closed_form():
    # (S-1)/(M+S-1): one microbatch through 2 stages idles each
    # stage half the time; a deep stream amortizes the fill/drain.
    assert make_inference_schedule(2, 1).bubble_fraction == 0.5
    sched = make_inference_schedule(2, 3)
    assert sched.bubble_fraction == pytest.approx(0.25)
    assert make_inference_schedule(1, 4).bubble_fraction == 0.0
    deep = make_inference_schedule(4, 61)
    assert deep.bubble_fraction == pytest.approx(3 / 64)


def test_staged_page_math():
    """Splitting layers over stages divides the per-chip page cost —
    the same budget buys ~stages x the pages, multiplying with the
    kv-heads shard split."""
    cfg = LlamaConfig.tiny(kv_page_size=8, kv_total_pages=40)
    assert cfg.num_layers == 2 and cfg.num_kv_heads == 2
    full = quant.kv_page_bytes(cfg, 'bf16', 1)
    assert quant.kv_page_bytes(cfg, 'bf16', 1, stages=2) == full // 2
    # Compose with the tensor shard: S=2 x T=2 -> quarter the bytes.
    assert quant.kv_page_bytes(cfg, 'bf16', 2, stages=2) == full // 4
    budget = 64 * full
    assert quant.pool_pages_for_bytes(cfg, 'bf16', budget) == 64
    assert quant.pool_pages_for_bytes(cfg, 'bf16', budget,
                                      stages=2) == 128
    assert quant.pool_pages_for_bytes(cfg, 'bf16', budget, 2,
                                      stages=2) == 256
    # int8 scale rows replicate across the head shard but DO split
    # by stage (each stage stores scales for its own layers only).
    i8_full = quant.kv_page_bytes(cfg, 'int8', 1)
    assert quant.kv_page_bytes(cfg, 'int8', 1, stages=2) == \
        i8_full // 2
    # Widest stage bounds the cost: 3 layers over 2 stages price 2.
    cfg3 = dataclasses.replace(cfg, num_layers=3)
    assert quant.kv_page_bytes(cfg3, 'bf16', 1, stages=2) == full
    with pytest.raises(ValueError):
        quant.kv_page_bytes(cfg, 'bf16', 1, stages=3)


# -- param split + placement ------------------------------------------------
def test_build_staged_serving_partition(setup):
    model, params, mesh = setup
    stage_models, stage_params, submeshes, ranges = \
        build_staged_serving(model, params, mesh)
    assert ranges == [(0, 1), (1, 2)]
    assert sorted(stage_params[0]) == ['layer_0', 'tok_embed']
    assert sorted(stage_params[1]) == ['final_norm', 'layer_1',
                                       'lm_head']
    # Disjoint top-level partition whose union is the full tree.
    assert set(stage_params[0]) | set(stage_params[1]) == set(params)
    # Each stage's devices are one row of the (stage, tensor) grid,
    # and TP sharding applies within the row.
    grid = np.asarray(mesh.devices).reshape(2, 2)
    for s, sub in enumerate(submeshes):
        assert list(np.asarray(sub.devices).ravel()) == list(grid[s])
    wq = stage_params[0]['layer_0']['attn']['wq']['kernel']
    assert 'tensor' in str(wq.sharding.spec)
    head = jax.tree.leaves(stage_params[1]['lm_head'])[0]
    assert 'tensor' in str(head.sharding.spec)


def test_staged_rejects_unsupported(setup):
    model, params, mesh = setup
    for kw in ({'paged': False}, {'decode_chunk': 4},
               {'num_slots': 3}):
        base = {'num_slots': 2, 'max_total_len': 48, 'mesh': mesh}
        base.update(kw)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, params, **base)
    qparams = quant.quantize_params(params)
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(quant.QuantizedModel(model), qparams,
                                 num_slots=2, max_total_len=48,
                                 mesh=mesh)


# -- the per-stage zero-resharding guard ------------------------------------
def test_staged_decode_has_no_pool_resharding(setup):
    """Compile each stage's decode dispatch and fail on any
    pool-shaped all-gather/all-to-all: the donated per-stage cache's
    explicit out_shardings keep EVERY stage's pool in place step
    over step (PR 15's guard, now per stage)."""
    model, params, mesh = setup
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    try:
        assert eng.stages == 2 and eng.kv_shard_ways == 2
        cfg = model.config
        z = jnp.zeros((2, 1), jnp.int32)
        pt = jnp.zeros((2, eng.pages_per_seq), jnp.int32)
        hid = jnp.zeros((2, 1, cfg.embed_dim), cfg.dtype)
        for s in range(eng.stages):
            fn = eng._stage_decode_fn(s)  # pylint: disable=protected-access
            if s == eng.stages - 1:
                lowered = fn.lower(
                    eng.params[s], eng.cache[s], hid, z,
                    jnp.zeros((2,), jnp.float32),
                    jnp.zeros((2,), jnp.int32),
                    jnp.ones((2,), jnp.float32),
                    jax.random.PRNGKey(0), pt)
            else:
                lowered = fn.lower(eng.params[s], eng.cache[s], z, z,
                                   pt)
            compiled = lowered.compile()
            hits = pool_collective_lines(
                compiled, eng.cache[s], eng._stage_submeshes[s])  # pylint: disable=protected-access
            assert hits == [], (s, hits)
    finally:
        eng.stop()


def test_staged_guard_detects_forced_reshard(setup):
    """Non-vacuous: forcing a stage's pool off its sharding on the
    stage SUBMESH (replicate = all-gather) is detected by the same
    guard the green path runs."""
    model, params, mesh = setup
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    try:
        s = 0
        sub = eng._stage_submeshes[s]  # pylint: disable=protected-access
        good_sh = eng._cache_shardings[s]  # pylint: disable=protected-access

        def bump(c):
            return jax.tree.map(lambda x: x + 1, c)

        bad_sh = jax.tree.map(
            lambda _: NamedSharding(sub, P()), good_sh)
        bad = jax.jit(bump, out_shardings=bad_sh).lower(
            eng.cache[s]).compile()
        assert pool_collective_lines(bad, eng.cache[s], sub)
        good = jax.jit(bump, out_shardings=good_sh).lower(
            eng.cache[s]).compile()
        assert pool_collective_lines(good, eng.cache[s], sub) == []
    finally:
        eng.stop()


def test_staged_pool_split_accounting(setup):
    """The per-chip KV figure is the widest stage's single shard —
    S=2 stages x 2-way heads store a quarter of the single-device
    pool per chip — and /stats' per-stage view shows each stage
    holding the full page count for only its own layers."""
    model, params, mesh = setup
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    ref = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48)
    try:
        assert eng.kv_cache_bytes_per_device() * 4 == \
            ref.kv_cache_bytes_per_device()
        stats = eng.stage_pool_stats()
        assert [st['layers'] for st in stats] == [[0, 1], [1, 2]]
        assert all(st['pages'] == eng.total_pages for st in stats)
        assert ref.stage_pool_stats() == []
        # Roofline inputs follow the split: per-stage weights and a
        # per-stage layer count shrink bytes_per_token_model's
        # amortized terms.
        bpt = eng.attention_bytes_per_token()
        assert bpt['total_bytes_per_token'] > 0
        assert bpt['weight_bytes_amortized'] < \
            ref.attention_bytes_per_token()['weight_bytes_amortized']
    finally:
        eng.stop()
        ref.stop()


# -- bit identity single-device vs staged -----------------------------------
PROMPTS = ([5, 9, 2, 17], [30, 31, 32], [5, 9, 2, 17, 40])


def _run_engine(model, params, prompts, *, mesh=None, n=8, slots=2,
                **kw):
    eng = ContinuousBatchingEngine(model, params, num_slots=slots,
                                   max_total_len=48, mesh=mesh, **kw)
    try:
        assert (eng.stages == 2) == (mesh is not None)
        futs = [eng.submit(list(p), max_new_tokens=n) for p in prompts]
        return [f.result(timeout=300) for f in futs]
    finally:
        eng.stop()


def test_staged_ring_keeps_the_blocking_first_token_fetch(setup):
    """The S-deep ring has in-flight rounds of its own a group, so it
    does not take the plain pipelined loop's first-token handoff on
    the device (ROADMAP S5b): a finished prompt's token is fetched,
    and counted as synced."""
    model, params, mesh = setup
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    try:
        assert eng.pipeline_decode and eng.stages == 2
        row = eng.submit(list(PROMPTS[0]), max_new_tokens=2).result(
            timeout=300)
        assert len(row) == len(PROMPTS[0]) + 2
        assert (eng.first_tokens_deferred, eng.first_tokens_synced) \
            == (0, 1)
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.parametrize('variant', ['bf16', 'int8kv', 'chunk_prefill',
                                     'spec'])
def test_staged_engine_bit_identical(setup, variant):
    """Greedy outputs of the (stage=2, tensor=2) engine equal
    single-device, across KV storage formats and decode modes — the
    group decode ring and the pipelined prefill chain change only
    WHEN work runs, never what it computes."""
    model, params, mesh = setup
    kw = {}
    prompts = PROMPTS
    if variant == 'int8kv':
        model = Llama(dataclasses.replace(model.config,
                                          kv_dtype='int8'))
    elif variant == 'chunk_prefill':
        kw['prefill_chunk'] = 4
        prompts = PROMPTS + ([5, 9, 2, 17, 40, 41, 42, 43, 44],)
    elif variant == 'spec':
        kw['speculative_k'] = 3
        prompts = ([5, 9, 2, 5, 9, 2, 5, 9], [30, 31, 30, 31, 30])
    ref = _run_engine(model, params, prompts, slots=4, **kw)
    got = _run_engine(model, params, prompts, mesh=mesh, slots=4,
                      **kw)
    assert got == ref


@pytest.mark.slow
def test_staged_lora_bit_identical(setup, tmp_path):
    """An active LoRA adapter rides the stage chain: the uncommitted
    host-backed stacks feed every stage's submesh dispatch, and
    outputs stay bit-identical to single-device LoRA serving."""
    from skypilot_tpu.inference.adapters import AdapterRegistry
    from skypilot_tpu.models import lora as lora_lib
    model, params, mesh = setup
    spec = lora_lib.LoraSpec(rank=4, alpha=8.0)
    lp = lora_lib.random_adapter_params(0, model.config, spec)
    lora_lib.save_adapter(str(tmp_path / 'ad0'), lp, spec,
                          base_model='llama-tiny')

    def run(eng_mesh):
        reg = AdapterRegistry(str(tmp_path), model, max_adapters=2,
                              mesh=None)
        eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                       max_total_len=48,
                                       adapter_store=reg,
                                       mesh=eng_mesh)
        try:
            return [eng.submit(list(p), max_new_tokens=8,
                               adapter='ad0').result(timeout=300)
                    for p in PROMPTS[:2]]
        finally:
            eng.stop()

    assert run(mesh) == run(None)


# -- chain handoff across stage splits --------------------------------------
def _wire_payload(data):
    off = len(kv_transfer.MAGIC)
    hlen = int.from_bytes(data[off:off + 8], 'big')
    return data[off + 8 + hlen:]


@pytest.mark.slow
def test_chain_export_import_across_stage_split(setup):
    """KV page chains are mesh-agnostic across stage splits: export
    from a staged pool, import into a single-device pool, serve
    bit-identically, re-export BYTE-identically, and import back
    into a second staged pool — the wire format addresses layers by
    path, never by stage."""
    model, params, mesh = setup
    prompt = list(range(2, 34))
    src = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    dst = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48)
    try:
        ref = src.submit(prompt, max_new_tokens=8).result(timeout=300)
        data = src.export_chain(prompt)
        assert data is not None
        stats = dst.import_chain(data)
        assert stats['imported'] > 0
        assert dst.submit(prompt, max_new_tokens=8).result(
            timeout=300) == ref
        back = dst.export_chain(prompt)
        assert _wire_payload(back) == _wire_payload(data)
        src2 = ContinuousBatchingEngine(model, params, num_slots=2,
                                        max_total_len=48, mesh=mesh)
        try:
            src2.import_chain(back)
            assert src2.submit(prompt, max_new_tokens=8).result(
                timeout=300) == ref
            assert _wire_payload(src2.export_chain(prompt)) == \
                _wire_payload(data)
        finally:
            src2.stop()
    finally:
        src.stop()
        dst.stop()


def test_chain_header_rejects_layer_mismatch(setup):
    """The chain header now pins num_layers like num_kv_heads: a
    payload from a different depth fails validation instead of
    corrupting the pool."""
    model, params, _ = setup
    src = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48)
    deep_cfg = dataclasses.replace(model.config, num_layers=3)
    deep = Llama(deep_cfg)
    deep_params = nn.meta.unbox(deep.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    dst = ContinuousBatchingEngine(deep, deep_params, num_slots=2,
                                   max_total_len=48)
    try:
        prompt = list(range(2, 34))
        src.submit(prompt, max_new_tokens=4).result(timeout=300)
        data = src.export_chain(prompt)
        assert data is not None
        with pytest.raises(ValueError, match='num_layers'):
            dst.import_chain(data)
    finally:
        src.stop()
        dst.stop()
