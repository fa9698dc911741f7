"""Mesh-sharded KV page pool (parallel/serving.py + the engine's
explicit dispatch shardings, PR 15).

The contracts under test:

  - placement: paged pool values shard their kv-heads axis over
    `tensor`, scale pages and unknown leaves replicate, and the GQA
    remainder rule replicates when heads don't divide;
  - capacity: a per-chip --kv-pool-bytes budget buys ~shard_ways
    more pages (int8 slightly less — scales replicate);
  - zero resharding: the compiled decode step contains NO
    all-gather/all-to-all over a pool-shaped operand (the guard that
    keeps N-chip serving from silently re-materializing the pool
    every token), and the guard itself detects forced violations;
  - bit identity: the sharded engine's greedy outputs equal
    single-device across paged bf16, int8 KV, int8 weights, LoRA,
    speculative, and chunked decode;
  - handoff: a chain exported from a tensor-2 pool imports into a
    single-device pool (and back) with byte-identical re-export.
"""
import dataclasses

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
from skypilot_tpu.parallel.serving import (
    kv_shard_ways, pool_collective_lines, serving_cache_shardings,
    shard_params_for_serving)


@pytest.fixture(scope='module')
def setup():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=40)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    return model, params, mesh


# -- placement rules --------------------------------------------------------
def test_kv_shard_ways_gqa_remainder():
    assert kv_shard_ways(2, 2) == 2
    assert kv_shard_ways(8, 4) == 4
    assert kv_shard_ways(2, 4) == 1     # remainder -> replicate
    assert kv_shard_ways(3, 2) == 1
    assert kv_shard_ways(0, 2) == 1     # MLA: no kv-heads axis
    assert kv_shard_ways(4, 1) == 1     # single device


def test_cache_shardings_layout(setup):
    _, _, mesh = setup
    cache = {'layers_0': {'attn': {
        'k_pages': jnp.zeros((2, 40, 8, 32), jnp.float32),
        'v_pages': jnp.zeros((2, 40, 8, 32), jnp.float32),
        'k_scales': jnp.zeros((40, 8), jnp.float32),
        'cached_key': jnp.zeros((2, 48, 2, 32), jnp.float32),
        'cache_index': jnp.zeros((2,), jnp.int32),
    }}}
    sh = serving_cache_shardings(cache, mesh)
    attn = sh['layers_0']['attn']
    assert attn['k_pages'].spec == P('tensor')
    assert attn['v_pages'].spec == P('tensor')
    assert attn['k_scales'].spec == P()         # scales replicate
    assert attn['cached_key'].spec == P(None, None, 'tensor')
    assert attn['cache_index'].spec == P()      # unknown leaves too


def test_cache_shardings_replicate_on_remainder():
    """2 kv heads over tensor=4: the pool replicates (all-or-nothing
    axis split), it never half-shards."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=4),
                              devices=jax.devices()[:4])
    cache = {'attn': {'k_pages': jnp.zeros((2, 40, 8, 32),
                                           jnp.float32)}}
    sh = serving_cache_shardings(cache, mesh)
    assert sh['attn']['k_pages'].spec == P()


# -- per-chip capacity math -------------------------------------------------
def test_page_bytes_per_chip():
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16, kv_page_size=16,
                           kv_total_pages=64)
    # bf16: value bytes halve exactly -> same budget buys 2x pages.
    assert quant.kv_page_bytes(cfg, 'bf16', 2) * 2 == \
        quant.kv_page_bytes(cfg, 'bf16', 1)
    budget = 1 << 20
    assert quant.pool_pages_for_bytes(cfg, 'bf16', budget, 2) == \
        2 * quant.pool_pages_for_bytes(cfg, 'bf16', budget, 1)
    # int8: scale rows replicate, so the per-chip page is MORE than
    # half a full page (ratio strictly < 2x).
    full = quant.kv_page_bytes(cfg, 'int8', 1)
    half = quant.kv_page_bytes(cfg, 'int8', 2)
    assert full // 2 < half < full
    # The GQA remainder rule is the caller's job: a non-dividing
    # shard request is a bug, not a rounding.
    with pytest.raises(ValueError):
        quant.kv_page_bytes(cfg, 'bf16', 3)


# -- the zero-resharding guard ----------------------------------------------
def test_decode_step_has_no_pool_resharding(setup):
    """Tier-1 guard: compile ONE decode step of the sharded engine
    and fail on any pool-shaped all-gather/all-to-all. This is the
    compiled-HLO proof that the donated cache's explicit
    out_shardings keep the pool in place step over step."""
    model, params, mesh = setup
    tp = shard_params_for_serving(model, params, mesh)
    eng = ContinuousBatchingEngine(model, tp, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    try:
        assert eng.kv_shard_ways == 2
        z = jnp.zeros((2,), jnp.int32)
        zf = jnp.zeros((2,), jnp.float32)
        of = jnp.ones((2,), jnp.float32)
        pt = jnp.zeros((2, eng.pages_per_seq), jnp.int32)
        compiled = eng._decode.lower(  # pylint: disable=protected-access
            eng.params, eng.cache, z, z, zf, z, of,
            jax.random.PRNGKey(0), pt).compile()
        assert pool_collective_lines(compiled, eng.cache, mesh) == []
    finally:
        eng.stop()


@pytest.mark.parametrize('kv_heads,tensor,ways',
                         [(4, 2, 2), (4, 4, 4), (2, 4, 1)])
def test_engine_guards_on_its_own_programs(kv_heads, tensor, ways):
    """The engine's own bring-up checks (what `GET
    /debug/pool_collectives` serves and chip_smoke.py asserts on the
    chip): the decode dispatch it compiled moves no pool-shaped
    operand between devices with the in-place KV write (every
    update spans the sharded kv-heads axis whole; where the heads do
    not divide the mesh and the pool replicates, only the few new
    rows are gathered, never the pool), and the copy guard reports on
    the decode dispatch and one page-aligned prefill chunk."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                         kv_total_pages=40), num_kv_heads=kv_heads)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=tensor),
                              devices=jax.devices()[:tensor])
    tp = shard_params_for_serving(model, params, mesh)
    eng = ContinuousBatchingEngine(model, tp, num_slots=2,
                                   max_total_len=48, prefill_chunk=16,
                                   mesh=mesh)
    try:
        assert eng.kv_shard_ways == ways
        assert eng.decode_pool_collectives() == []
        copies = eng.pool_copy_lines()
        assert sorted(copies) == ['decode', 'prefill_suffix_16']
        assert all(isinstance(v, list) for v in copies.values())
        # The engine still serves after lowering on its live cache.
        row = eng.submit([3, 1, 4, 1, 5], max_new_tokens=3).result(
            timeout=300)
        assert len(row) == 8
    finally:
        eng.stop()


def test_collective_guard_reads_the_applied_op_only():
    """A fusion that writes a few all-gathered rows into the pool
    names `%all-gather.N` among its operands and is no resharding; an
    all-gather that PRODUCES a pool-shaped array is."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    cache = {'attn': {'k_pages': jnp.zeros((2, 40, 8, 32),
                                           jnp.float32)}}
    write = ('%dus_fusion = f32[2,40,8,32]{3,2,1,0} fusion(%param.11, '
             '%all-gather.5, %gather_fusion), kind=kLoop')
    small = ('%all-gather.5 = f32[2,1,2,32]{3,1,0,2} all-gather('
             '%copy.14), dimensions={2}')
    regather = ('%all-gather.7 = f32[2,40,8,32]{3,2,1,0} all-gather('
                '%copy.15), dimensions={0}')
    hlo = '\n'.join(['  ' + write, '  ' + small, '  ' + regather])
    assert pool_collective_lines(hlo, cache, mesh) == [regather]


def test_copy_guard_matches_pool_and_head_shards():
    """`pool_copy_lines` flags a copy that produces the pool's shape
    or a kv-head shard of it, in any layout, and nothing else."""
    from skypilot_tpu.parallel.serving import pool_copy_lines
    cache = {'layer_0': {'attn': {
        'k_pages': jax.ShapeDtypeStruct((8, 5120, 16, 128),
                                        jnp.bfloat16),
        'k_scales': jax.ShapeDtypeStruct((5120, 16), jnp.float32)}}}
    hlo = """
  %copy.1 = bf16[8,5120,16,128]{3,0,2,1:T(8,128)(2,1)} copy(%p), x
  ROOT %copy.2 = bf16[2,5120,16,128]{3,2,1,0} copy(%fusion)
  %copy.3 = f32[5120,16]{0,1} copy(%scales)
  %copy.4 = bf16[32,8,128]{2,1,0} copy(%new)
  %dus = bf16[8,5120,16,128]{3,2,1,0} dynamic-update-slice(%p, %u)
  %copy.5 = bf16[3,5120,16,128]{3,2,1,0} copy(%odd)
"""
    hits = pool_copy_lines(hlo, cache)
    assert [h.split(' = ')[0] for h in hits] == ['%copy.1',
                                                 'ROOT %copy.2']


def test_pool_guard_detects_forced_reshard(setup):
    """The guard is not vacuous: forcing the pool off its sharding
    (replicate = all-gather; axis move = all-to-all, whose per-shard
    chunks are size/ways^2) is detected."""
    _, _, mesh = setup
    cache = {'attn': {'k_pages': jnp.zeros((2, 40, 8, 32),
                                           jnp.float32)}}
    sh = serving_cache_shardings(cache, mesh)
    pinned = jax.device_put(cache, sh)

    def bump(c):
        return jax.tree.map(lambda x: x + 1.0, c)

    for forced in (P(), P(None, 'tensor')):
        bad_sh = jax.tree.map(
            lambda s, f=forced: NamedSharding(mesh, f), sh)
        bad = jax.jit(bump, out_shardings=bad_sh).lower(
            pinned).compile()
        assert pool_collective_lines(bad, cache, mesh)
    good = jax.jit(bump, out_shardings=sh).lower(pinned).compile()
    assert pool_collective_lines(good, cache, mesh) == []


# -- bit identity single-device vs sharded ----------------------------------
PROMPTS = ([5, 9, 2, 17], [30, 31, 32], [5, 9, 2, 17, 40])


def _run_engine(model, params, prompts, *, mesh=None, n=8, **kw):
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48, mesh=mesh, **kw)
    try:
        assert (eng.kv_shard_ways == 2) == (mesh is not None)
        return [eng.submit(list(p), max_new_tokens=n).result(
            timeout=300) for p in prompts]
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.parametrize('variant', ['bf16', 'dense', 'int8kv',
                                     'chunk', 'spec'])
def test_sharded_engine_bit_identical(setup, variant):
    """Greedy outputs off the head-sharded pool equal single-device,
    across storage formats and decode modes."""
    model, params, mesh = setup
    kw = {}
    prompts = PROMPTS
    if variant == 'int8kv':
        cfg = dataclasses.replace(model.config, kv_dtype='int8')
        model = Llama(cfg)
    elif variant == 'dense':
        # The per-slot dense cache shards its kv-heads axis (axis 2)
        # the same way the pool does.
        kw['paged'] = False
    elif variant == 'chunk':
        kw['decode_chunk'] = 4
    elif variant == 'spec':
        kw['speculative_k'] = 3
        # Repetitive prompts: the regime prompt-lookup actually
        # drafts in (correctness must hold either way).
        prompts = ([5, 9, 2, 5, 9, 2, 5, 9], [30, 31, 30, 31, 30])
    tp = shard_params_for_serving(model, params, mesh)
    ref = _run_engine(model, params, prompts, **kw)
    got = _run_engine(model, tp, prompts, mesh=mesh, **kw)
    assert got == ref


@pytest.mark.slow
def test_sharded_engine_int8_weights_bit_identical(setup):
    """int8 per-channel weights + sharded pool == the same quantized
    model on one device (scales shard with their output channel)."""
    model, params, mesh = setup
    qparams = quant.quantize_params(params)
    qmodel = quant.QuantizedModel(model)
    qtp = quant.shard_quantized_for_serving(qmodel, qparams, mesh)
    ref = _run_engine(qmodel, qparams, PROMPTS)
    got = _run_engine(qmodel, qtp, PROMPTS, mesh=mesh)
    assert got == ref


@pytest.mark.slow
def test_sharded_engine_lora_bit_identical(setup, tmp_path):
    """An active adapter rides the sharded engine unchanged: the
    replicated factor store gathers per-slot rows without touching
    the pool's sharding."""
    from skypilot_tpu.inference.adapters import AdapterRegistry
    from skypilot_tpu.models import lora as lora_lib
    model, params, mesh = setup
    spec = lora_lib.LoraSpec(rank=4, alpha=8.0)
    lp = lora_lib.random_adapter_params(0, model.config, spec)
    lora_lib.save_adapter(str(tmp_path / 'ad0'), lp, spec,
                          base_model='llama-tiny')
    tp = shard_params_for_serving(model, params, mesh)

    def run(engine_params, eng_mesh):
        reg = AdapterRegistry(str(tmp_path), model, max_adapters=2,
                              mesh=eng_mesh)
        eng = ContinuousBatchingEngine(model, engine_params,
                                       num_slots=2, max_total_len=48,
                                       adapter_store=reg,
                                       mesh=eng_mesh)
        try:
            return [eng.submit(list(p), max_new_tokens=8,
                               adapter='ad0').result(timeout=300)
                    for p in PROMPTS]
        finally:
            eng.stop()

    assert run(tp, mesh) == run(params, None)


# -- cross-mesh chain handoff -----------------------------------------------
def _wire_payload(data: bytes) -> bytes:
    off = len(kv_transfer.MAGIC)
    hlen = int.from_bytes(data[off:off + 8], 'big')
    return data[off + 8 + hlen:]


@pytest.mark.slow
def test_export_import_across_mesh_sizes(setup):
    """A chain exported from a tensor-2 sharded pool (blobs carry
    GLOBAL page rows) imports into a single-device pool, serves
    bit-identically, and re-exports byte-identical payload bytes —
    the disaggregated-handoff contract across mesh sizes."""
    model, params, mesh = setup
    prompt = list(range(2, 34))      # 4 full 8-token pages
    tp = shard_params_for_serving(model, params, mesh)
    src = ContinuousBatchingEngine(model, tp, num_slots=2,
                                   max_total_len=48, mesh=mesh)
    dst = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=48)
    try:
        ref = src.submit(prompt, max_new_tokens=8).result(timeout=300)
        data = src.export_chain(prompt)
        assert data is not None
        meta, _ = kv_transfer.unpack_pages(data)
        # The header records kv-head geometry for cross-mesh import
        # validation (PR-13 payloads lack it and still import).
        assert meta['num_kv_heads'] == model.config.num_kv_heads
        assert meta['head_dim'] == model.config.head_dim
        summary = dst.import_chain(data)
        assert summary['imported'] == 4 and summary['dropped'] == 0
        out = dst.submit(prompt, max_new_tokens=8).result(timeout=300)
        assert out == ref
        data2 = dst.export_chain(prompt)
        assert _wire_payload(data2) == _wire_payload(data)
    finally:
        src.stop()
        dst.stop()
