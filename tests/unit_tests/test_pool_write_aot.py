"""The KV write compiled for the chip, without the chip: no whole-pool
copy around it.

XLA:TPU gave the scatter form of the write a page-major layout of its
own, so every scatter was wrapped in two copies of the whole pool
array (PERF.md, PR 26). These tests compile the write as it is today,
and the decode read behind it (PR 30), for a described v5e at the
benchmark cell's shapes
(`mistral-7b-l16`: pool bf16[8, 5120, 16, 128], 32 slots, 128 pages a
row) and hold `parallel/serving.pool_copy_lines` to zero. Nothing runs:
a compile says nothing about results or times.

All ahead-of-time compiles of the repo live in THIS file: the TPU
library belongs to one process, so only the worker that is given this
file loads it (from the fixture, never at import).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.ops import paged_attention as pa
from skypilot_tpu.parallel.serving import pool_copy_lines

POOL = (8, 5120, 16, 128)      # [Hkv, pages, page, D]
SLOTS, PAGES_PER_ROW, HQ = 32, 128, 32
CHUNK = 256
# GPT-2 124M's pool (12 heads of 64, no GQA) at 8 slots of 1024 tokens:
# a shape neither Pallas read compiles, so its read is the XLA gather.
POOL_D64 = (12, 513, 16, 64)
SLOTS_D64, PAGES_PER_ROW_D64 = 8, 64


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def compile_for_chip(one_chip):
    """compile(fn, donate, (shape, dtype)...) -> compiled, with the
    persistent compile cache off around it (an entry written for a
    described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    def compile_(fn, donate, *avals):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in avals]
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield compile_
    finally:
        jax.config.update('jax_enable_compilation_cache', was)
        compilation_cache.reset_cache()


def _cache(dtype):
    pool = jax.ShapeDtypeStruct(POOL, dtype)
    return {'layer_0': {'attn': {'k_pages': pool, 'v_pages': pool}}}


def _assert_in_place(compiled, dtype):
    text = compiled.as_text()
    assert pool_copy_lines(compiled, _cache(dtype)) == []
    # The write is there, in the form that aliases: not optimised
    # away, and no scatter but the two of an int8 pool's small
    # [pages, page] scale arrays.
    assert ' dynamic-update-slice(' in text
    assert text.count(' scatter(') == (2 if dtype == jnp.int8 else 0)


def _decode_layer(read):
    """One layer of a decode round: the token-wise write, then `read`
    of the pool as the model's block calls it."""
    def layer(k_pages, v_pages, q, k_new, v_new, positions, table):
        k_pages, v_pages = pa.write_kv(k_pages, v_pages, k_new, v_new,
                                       positions, table)
        out = read(q, k_pages, v_pages, positions + 1, table)
        return k_pages, v_pages, out
    return layer


def _decode_layer_avals(pool, slots, q_heads, pages_per_row):
    new = ((slots, pool[0], pool[3]), jnp.bfloat16)
    return ((pool, jnp.bfloat16), (pool, jnp.bfloat16),
            ((slots, q_heads, pool[3]), jnp.bfloat16), new, new,
            ((slots,), jnp.int32), ((slots, pages_per_row), jnp.int32))


def test_decode_write_and_decode_kernel_copy_no_pool(compile_for_chip,
                                                     monkeypatch):
    """The route 'auto' takes on the chip at the cell's shapes, through
    the wrapper the models call: the in-repo decode read, held by its
    `name=`, with the pool operands where they lie (no pool-shaped
    copy) and the output in the query's dtype. The backend is the one
    thing steered: this process compiles for a chip it does not have."""
    from skypilot_tpu.ops import pallas_paged
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    compiled = compile_for_chip(
        _decode_layer(pa.paged_decode_attention), (0, 1),
        *_decode_layer_avals(POOL, SLOTS, HQ, PAGES_PER_ROW))
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert '/paged_attention/' in calls[0]               # the scope
    assert 'paged_decode_attention' in calls[0]          # the name=
    assert f'= bf16[{SLOTS},{HQ},{POOL[3]}]' in calls[0]
    _assert_in_place(compiled, jnp.bfloat16)


def test_decode_layer_of_64_wide_heads_compiles_as_the_gather(
        compile_for_chip, monkeypatch):
    """A pool the decode kernel refuses (GPT-2's 64-wide heads: a page
    of one head is half a lane tile) through the same wrapper, the
    backend steered as above: the read is the XLA gather, so the layer
    compiles for the chip (the Pallas reads do not, at this shape) and
    holds no Pallas call; the write in front of it is still the
    aliasing form. NOT held to `pool_copy_lines`: XLA:TPU gives a
    gather over 64-wide rows a page-major layout of the pool, a
    whole-pool copy in and, behind the write, one out per pool array
    (PERF.md section 7); no cell serves such a pool."""
    from skypilot_tpu.ops import pallas_paged
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    pool = jax.ShapeDtypeStruct(POOL_D64, jnp.bfloat16)
    assert pallas_paged.decode_kernel_refusal(pool) is not None
    assert pallas_paged.resolve_impl(decode_pool=pool) == 'xla'
    compiled = compile_for_chip(
        _decode_layer(pa.paged_decode_attention), (0, 1),
        *_decode_layer_avals(POOL_D64, SLOTS_D64, POOL_D64[0],
                             PAGES_PER_ROW_D64))
    text = compiled.as_text()
    assert 'tpu_custom_call' not in text
    assert f'bf16[{SLOTS_D64},{POOL_D64[0]},{POOL_D64[3]}]' in text
    assert ' dynamic-update-slice(' in text and ' scatter(' not in text


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.int8],
                         ids=['bf16', 'int8'])
def test_aligned_chunk_write_copies_no_pool(compile_for_chip, dtype):
    """One layer's write of a page-aligned 256-token prefill chunk
    (16 whole pages an array), bf16 and int8 pools."""

    def write(k_pages, v_pages, k_scales, v_scales, k_new, v_new,
              offset, table):
        positions = (offset + jnp.arange(CHUNK, dtype=jnp.int32))[None]
        if dtype == jnp.int8:
            return pa.write_kv_chunk_quant(
                k_pages, v_pages, k_scales, v_scales, k_new, v_new,
                positions, table, page_aligned=True)
        return pa.write_kv_chunk(k_pages, v_pages, k_new, v_new,
                                 positions, table, page_aligned=True)

    new = ((1, CHUNK, POOL[0], POOL[3]), jnp.bfloat16)
    scales = (POOL[1:3], jnp.float32)
    compiled = compile_for_chip(
        write, (0, 1, 2, 3), (POOL, dtype), (POOL, dtype), scales,
        scales, new, new, ((), jnp.int32),
        ((1, PAGES_PER_ROW), jnp.int32))
    _assert_in_place(compiled, dtype)
    assert compiled.as_text().count(' dynamic-update-slice(') == \
        2 * CHUNK // POOL[2]


def test_guard_sees_a_pool_copy(compile_for_chip):
    """The guard is not blind: the scatter form of the same write (what
    `write_kv` was) still compiles to pool-shaped copies."""

    def scatter(pages, new, physical, slot):
        return pages.at[:, physical, slot, :].set(
            jnp.swapaxes(new, 0, 1))

    compiled = compile_for_chip(
        scatter, (0,), (POOL, jnp.bfloat16),
        ((SLOTS, POOL[0], POOL[3]), jnp.bfloat16),
        ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32))
    assert len(pool_copy_lines(compiled, _cache(jnp.bfloat16))) == 2


# DeepSeek-V3.2's latent page layout at its cell's shapes
# (`deepseek-v32-l5-ep16`: a 4 GiB pool of 29,959 pages, 48 slots,
# 1,024 pages a row, 512-token chunks).
LATENT_POOL = (1, 29959, 16, 640)     # 512 + 64 values in 640, bf16
INDEX_POOL = (1, 29959, 16, 128)      # float32
LATENT_SLOTS, LATENT_PAGES_PER_ROW, LATENT_CHUNK = 48, 1024, 512


def _latent_layer(chunk):
    """One layer over a latent pool: the write, then a decode round's
    three reads (chunk 1) or a page-aligned prefill chunk's."""
    from skypilot_tpu.ops import sparse_latent as sl
    kw = dict(scale=0.1, value_dim=512)

    def layer(latent, index_k, new_latent, new_key, q, q_idx, w_idx,
              positions, table):
        latent, index_k = sl.write_rows(
            latent, index_k, new_latent, new_key, positions, table,
            page_aligned=chunk > 1)
        if chunk > 1:
            out = sl.sparse_latent_chunk(q, q_idx, w_idx, latent, index_k,
                                         positions, table, topk=2048, **kw)
        else:
            scores = sl.index_scores_decode(
                q_idx[:, 0], w_idx[:, 0], index_k, table,
                positions[:, 0] + 1)
            idx, valid = sl.select_topk(scores, 2048)
            out = sl.sparse_latent_decode(q[:, 0], latent, table, idx,
                                          valid, **kw)
        return latent, index_k, out
    return layer


def _latent_layer_avals(chunk):
    rows = LATENT_SLOTS if chunk == 1 else 1
    bf16 = jnp.bfloat16
    return ((LATENT_POOL, bf16), (INDEX_POOL, jnp.float32),
            ((rows, chunk, 640), bf16), ((rows, chunk, 128), jnp.float32),
            ((rows, chunk, 128, 640), bf16),
            ((rows, chunk, 64, 128), jnp.float32),
            ((rows, chunk, 64), jnp.float32), ((rows, chunk), jnp.int32),
            ((rows, LATENT_PAGES_PER_ROW), jnp.int32))


LATENT_CACHE = {'layer_0': {'attn': {
    'latent_pages': jax.ShapeDtypeStruct(LATENT_POOL, jnp.bfloat16),
    'index_k_pages': jax.ShapeDtypeStruct(INDEX_POOL, jnp.float32)}}}
# A result the size of a block of scores: float32 [.., 128 heads, 512
# queries, 512 keys]. (A chunk's output in latent terms, 512 values a
# query and head, has the same shape: the kernel's one result.)
SCORES_SHAPED = re.compile(r'= f32\[(\d+,)*128,512,512\]')


@pytest.mark.parametrize('chunk', [1, LATENT_CHUNK],
                         ids=['decode', 'prefill_chunk'])
def test_latent_layout_write_and_reads_copy_no_pool(compile_for_chip,
                                                    chunk):
    """The other page layout (a latent row and an indexer key a token,
    models/deepseek.py): one layer's write and its three reads
    (ops/sparse_latent.py), a decode round and a page-aligned prefill
    chunk, compile for the chip with both pool arrays written where
    they lie. Plain XLA as this backend resolves them (the chunk's
    walk: the route a shape the kernel refuses takes on the chip), and
    the walk is what carries blocks of scores through HBM."""
    compiled = compile_for_chip(_latent_layer(chunk), (0, 1),
                                *_latent_layer_avals(chunk))
    assert pool_copy_lines(compiled, LATENT_CACHE) == []
    text = compiled.as_text()
    assert ' dynamic-update-slice(' in text and ' scatter(' not in text
    assert 'tpu_custom_call' not in text         # plain XLA, all of it
    # The chunk's walk: a dozen results a block of keys, in its loop.
    assert len(SCORES_SHAPED.findall(text)) >= (10 if chunk > 1 else 0)
    assert bool(SCORES_SHAPED.search(text)) == (chunk > 1)


def test_latent_chunk_takes_the_kernel_and_keeps_scores_out_of_hbm(
        compile_for_chip, monkeypatch):
    """The route a prefill chunk takes on the chip at the cell's shapes
    (`deepseek-v32-l5-ep16`: 128 heads over a 640-wide row, a 512-token
    chunk, 1,024 pages a row), the backend steered as above: its
    attention is the kernel of ops/pallas_latent.py, held by its
    `name=` inside the scope the trace is read by; both pool arrays are
    still written where they lie, the row's pages reach the kernel as
    a gather of the row and no copy of the pool; and the one
    instruction of the program with a result the size of a block of
    scores is the kernel itself, whose result is the chunk's output."""
    from skypilot_tpu.ops import pallas_paged
    from skypilot_tpu.ops import sparse_latent as sl
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    assert sl.chunk_route(
        jax.ShapeDtypeStruct((1, LATENT_CHUNK, 128, 640), jnp.bfloat16),
        jax.ShapeDtypeStruct(LATENT_POOL, jnp.bfloat16),
        LATENT_PAGES_PER_ROW, 512) == 'sparse_latent_pallas'
    compiled = compile_for_chip(_latent_layer(LATENT_CHUNK), (0, 1),
                                *_latent_layer_avals(LATENT_CHUNK))
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert '/latent_attention/' in calls[0]               # the scope
    assert 'latent_chunk_attention' in calls[0]           # the name=
    assert f'= f32[128,{LATENT_CHUNK},512]' in calls[0]
    assert pool_copy_lines(compiled, LATENT_CACHE) == []
    assert ' dynamic-update-slice(' in text and ' scatter(' not in text
    assert [line for line in text.splitlines()
            if SCORES_SHAPED.search(line)] == calls


# -- state by slot (models/nemotron_h.py, ops/ssm.py) -------------------------
STATE_SLOTS, STATE_CHUNK = 128, 512


def _mamba_layer(chunk):
    """One Mamba-2 mixer of `nemotron3-super-l11-ep4` through the
    engine's cache: a decode round over 128 slots, or a 512-token
    prefill chunk behind its history in a slot given at run time."""
    import flax.linen as nn
    from skypilot_tpu.models import nemotron_h as nh
    mixer = nh.Mamba2Mixer(nh.NemotronHConfig.super_l11_ep4())
    rows = STATE_SLOTS if chunk == 1 else 1
    u = jax.ShapeDtypeStruct((rows, chunk, 4096), jnp.bfloat16)
    variables = nn.meta.unbox(jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(
            (STATE_SLOTS, 1, 4096), jnp.bfloat16), decode=True)))
    params = jax.tree.map(
        lambda s: (s.shape, jnp.bfloat16 if len(s.shape) > 1 else s.dtype),
        variables['params'])
    cache = jax.tree.map(lambda s: (s.shape, s.dtype), variables['cache'])

    def fn(cache, params, u, live, slot):
        out, mutated = mixer.apply(
            {'params': params, 'cache': cache}, u, decode=True,
            prefill=False, live=live,
            slots=None if chunk == 1 else slot[None], mutable=['cache'])
        return mutated['cache'], out

    flat_cache, cache_def = jax.tree.flatten(
        cache, is_leaf=lambda x: isinstance(x, tuple))
    flat_params, params_def = jax.tree.flatten(
        params, is_leaf=lambda x: isinstance(x, tuple))

    def flat_fn(*args):
        n, m = len(flat_cache), len(flat_params)
        return fn(jax.tree.unflatten(cache_def, args[:n]),
                  jax.tree.unflatten(params_def, args[n:n + m]),
                  *args[n + m:])

    avals = (*flat_cache, *flat_params, (u.shape, u.dtype),
             ((rows, chunk), jnp.bool_), ((), jnp.int32))
    return flat_fn, tuple(range(len(flat_cache))), avals, variables['cache']


@pytest.mark.parametrize('chunk', [1, STATE_CHUNK],
                         ids=['decode', 'prefill_chunk'])
def test_state_by_slot_is_updated_where_it_lies(compile_for_chip,
                                                monkeypatch, chunk):
    """The second kind of cache at the cell's shapes (128 slots of
    f32[64, 128, 128] state, 512 MiB a layer, and of bf16[240, 128]
    convolution tail: ops/ssm.slot_shapes), the backend steered to the
    chip's route: a decode round's state update is ONE call of the
    kernel of ops/pallas_ssm.py, by its `name=` inside the scope the
    trace is read by, with the state and the tail in and out as one
    buffer each; a prefill chunk's row, read at its slot and written
    back, is plain XLA. Both compile for the chip with no copy of
    either array and, on one chip, no collective."""
    from skypilot_tpu.ops import pallas_ssm
    from skypilot_tpu.parallel.serving import pool_collective_lines
    monkeypatch.setattr(pallas_ssm, 'available', lambda: True)
    fn, donate, avals, cache = _mamba_layer(chunk)
    compiled = compile_for_chip(fn, donate, *avals)
    assert {k: tuple(v.shape) for k, v in cache.items()
            if k.endswith('_state')} == {
        'ssm_state': (STATE_SLOTS, 64, 128, 128),
        'conv_state': (STATE_SLOTS, 240, 128)}
    assert pool_copy_lines(compiled, cache) == []
    mesh = jax.sharding.Mesh([jax.devices()[0]], ('tensor',))
    assert pool_collective_lines(compiled, cache, mesh) == []
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert ' scatter(' not in text
    if chunk > 1:
        assert calls == []                       # plain XLA, all of it
        assert ' dynamic-update-slice(' in text
        # Each array goes in and comes out as the same buffer.
        assert text.count('f32[128,64,128,128]') >= 2
        return
    assert len(calls) == 1, calls
    assert '/ssm_update/' in calls[0]                      # the scope
    assert 'ssm_state_update' in calls[0]                  # the name=
    # The kernel's results: y, then the state and the tail, each the
    # buffer of its operand (aliased), and no other state-sized value
    # (the layouts, and the memory space XLA gives y, left aside).
    results = calls[0].split(' custom-call(')[0].split(' = ', 1)[1]
    assert re.findall(r'\w+\[[\d,]*\]', results) == [
        'f32[128,64,128]', 'f32[128,64,128,128]', 'bf16[128,240,128]']
    assert 'output_to_operand_aliasing={{1}: (7, {}), {2}: (8, {})}' \
        in calls[0]
    assert ' dynamic-update-slice(' not in text


# -- the grouped expert pass (models/deepseek.MoEByShare, ops/pallas_experts.py)
@pytest.mark.parametrize('model', ['nemotron3-super-l11-ep4',
                                   'deepseek-v32-l5-ep16'])
def test_expert_layer_takes_the_kernel_and_no_switch(compile_for_chip,
                                                     monkeypatch, model):
    """One routed layer of each cell's decode round at its published
    widths (128 held `relu2` experts 1,024 x 2,688 in the latent at 128
    slots; 16 held `swiglu` experts 7,168 x 2,048 at 48 slots), the
    backend steered to the chip's route: the held experts are ONE call
    of the kernel, by its `name=` inside the scope the trace is read by,
    and the program holds no conditional (the `lax.switch` loop's 128
    branches are gone)."""
    import flax.linen as nn
    from skypilot_tpu.models import deepseek as ds
    from skypilot_tpu.models import nemotron_h as nh
    from skypilot_tpu.ops import pallas_experts
    monkeypatch.setattr(pallas_experts, 'available', lambda: True)
    if model.startswith('nemotron'):
        cfg, slots = nh.NemotronHConfig.super_l11_ep4(), 128
        layer = ds.MoEByShare(cfg, expert_act='relu2',
                              latent_dim=cfg.moe_latent_dim,
                              shared_dim=cfg.moe_shared_dim,
                              stacked_init=True)
    else:
        cfg, slots = ds.DeepseekConfig.v32_l5_ep16(), 48
        layer = ds.MoEByShare(cfg)
    x = jax.ShapeDtypeStruct((slots, 1, cfg.embed_dim), jnp.bfloat16)
    live = jax.ShapeDtypeStruct((slots, 1), jnp.bool_)
    params = jax.eval_shape(lambda: nn.unbox(layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype),
        jnp.ones(live.shape, bool))))['params']
    flat, tree = jax.tree.flatten(params)

    def fn(*args):
        *leaves, x, live = args
        return layer.apply({'params': jax.tree.unflatten(tree, leaves)},
                           x, live)

    compiled = compile_for_chip(
        fn, (), *[(a.shape, a.dtype) for a in flat],
        (x.shape, x.dtype), (live.shape, live.dtype))
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert '/experts/' in calls[0] and 'grouped_experts' in calls[0]
    assert ' conditional(' not in text


# -- 64-wide heads packed a token a row, and a short convolution's tail by
# slot (models/lfm2.py) -----------------------------------------------------
#: `lfm2-24b-a2b-l10`'s pool: 8 K/V heads of 64 side by side in one
#: 512-wide row, 1.5 GiB over its 2 attention layers (24,576 pages), 128
#: slots of 2,560 tokens (160 pages a row).
LFM2_SLOTS, LFM2_PAGES_PER_ROW, LFM2_CHUNK = 128, 160, 256


def _lfm2_program(module, rows, chunk, **call):
    """One module of `lfm2-24b-a2b-l10` through the engine's cache, as a
    flat function of (cache leaves, params, x, positions, live, slot,
    table) whose cache leaves are donated, and its avals."""
    import flax.linen as nn
    x = jax.ShapeDtypeStruct((rows, chunk, 2048), jnp.bfloat16)
    # The cache as the engine makes it: one token a slot.
    variables = nn.meta.unbox(jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0),
            jnp.zeros((LFM2_SLOTS, 1, 2048), x.dtype),
            **_lfm2_init_args(module))))
    params = jax.tree.map(
        lambda s: (s.shape, jnp.bfloat16 if len(s.shape) > 1 else s.dtype),
        variables['params'])
    cache = jax.tree.map(lambda s: (s.shape, s.dtype), variables['cache'])
    is_aval = lambda x: isinstance(x, tuple)  # noqa: E731
    flat_cache, cache_def = jax.tree.flatten(cache, is_leaf=is_aval)
    flat_params, params_def = jax.tree.flatten(params, is_leaf=is_aval)

    def fn(*args):
        n, m = len(flat_cache), len(flat_params)
        x, positions, live, slot, table = args[n + m:]
        out, mutated = module.apply(
            {'params': jax.tree.unflatten(params_def, args[n:n + m]),
             'cache': jax.tree.unflatten(cache_def, args[:n])},
            x, **_lfm2_call_args(module, positions, live, slot, table,
                                 chunk, call), mutable=['cache'])
        return mutated['cache'], out

    avals = (*flat_cache, *flat_params, (x.shape, x.dtype),
             ((rows, chunk), jnp.int32), ((rows, chunk), jnp.bool_),
             ((), jnp.int32), ((rows, LFM2_PAGES_PER_ROW), jnp.int32))
    return fn, tuple(range(len(flat_cache))), avals, variables['cache']


def _lfm2_init_args(module):
    from skypilot_tpu.models import lfm2
    if isinstance(module, lfm2.ShortConv):
        return {'decode': True}
    return {'positions': jnp.zeros((LFM2_SLOTS, 1), jnp.int32),
            'decode': True,
            'page_indices': jnp.zeros((LFM2_SLOTS, LFM2_PAGES_PER_ROW),
                                      jnp.int32)}


def _lfm2_call_args(module, positions, live, slot, table, chunk, call):
    from skypilot_tpu.models import lfm2
    if isinstance(module, lfm2.ShortConv):
        return dict(decode=True, live=live,
                    slots=None if chunk == 1 else slot[None], **call)
    return dict(positions=positions, decode=True, page_indices=table,
                **call)


def _lfm2_cache(**leaves):
    return {'layer_0': {'mixer': leaves}}


#: What the XLA route's gather of every slot's whole page table makes
#: at the cell's 128 slots of 160 pages: [20480, 16, 512] (as XLA:TPU
#: folds it), [128, 160, 16, 512] or [128, 2560, 512].
FULL_TABLE_GATHER = re.compile(
    r'= bf16\[(20480,16|128,160,16|128,2560),512\]\S* (gather|fusion)\(')


@pytest.mark.parametrize('chunk', [1, LFM2_CHUNK],
                         ids=['decode', 'later_chunk'])
def test_packed_64_wide_heads_are_read_where_they_lie(compile_for_chip,
                                                      monkeypatch, chunk):
    """An attention layer of `lfm2-24b-a2b-l10` at the cell's shapes (a
    [1, 24,576, 16, 512] pool a K and a V, 128 slots; a 256-token chunk
    behind its history), the backend steered to the chip's routes: the
    write and the read by page compile for the chip with
    `pool_copy_lines` at zero, where GPT-2's [12, ..., 64] pool is
    copied whole (`test_decode_layer_of_64_wide_heads_compiles_as_the_
    gather`); never the 128-lane decode kernel, which would take the
    packed row for one head. A decode round's read is the kernel of
    ops/pallas_packed.py, one call in scope `paged_attention` that takes
    the pools where they lie, and no gather of the whole tables; a
    chunk's is the gather of its row's 160 pages, split into heads of
    64 after."""
    from skypilot_tpu.models import lfm2
    from skypilot_tpu.ops import pallas_paged
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    cfg = lfm2.Lfm2Config.l10(kv_total_pages=24576)
    layout = cfg.page_layout()
    assert layout.kind == 'kv_packed'
    assert pallas_paged.resolve_impl(layout=layout.kind) == \
        'packed_heads_xla'
    # The shape alone would have taken the decode kernel.
    pool = jax.ShapeDtypeStruct(layout.shape(layout.arrays[0]),
                                jnp.bfloat16)
    assert pool.shape == (1, 24576, 16, 512)
    assert pallas_paged.decode_kernel_refusal(pool) is None
    assert pallas_paged.resolve_impl(layout=layout.kind,
                                     decode_pool=pool) == 'packed_decode'
    rows = LFM2_SLOTS if chunk == 1 else 1
    fn, donate, avals, cache = _lfm2_program(
        lfm2.Attention(cfg), rows, chunk, prefill=False,
        page_aligned=chunk > 1)
    assert {k: tuple(v.shape) for k, v in cache.items()} == {
        'k_pages': pool.shape, 'v_pages': pool.shape}
    compiled = compile_for_chip(fn, donate, *avals)
    assert pool_copy_lines(compiled, _lfm2_cache(**cache)) == []
    text = compiled.as_text()
    assert ' dynamic-update-slice(' in text and ' scatter(' not in text
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    if chunk == 1:
        assert len(calls) == 1
        assert re.search(r'/paged_attention/\S*/packed_decode_attention/',
                         calls[0]), calls[0][:300]
        # No page is gathered (the write gathers its page ids only).
        assert not re.search(r'= bf16\[[0-9,]+\]\S* gather\(', text)
        assert not FULL_TABLE_GATHER.search(text)
    else:
        assert calls == []
        # A row's 160 pages gathered whole, [160, 16, 512] a row (the
        # rows' axis folded away for one), and split into heads of 64.
        assert re.search(rf'= bf16\[({rows},)?160,16,512\]\S* gather\(',
                         text)


def test_the_decode_round_reads_no_whole_page_table(one_chip, monkeypatch):
    """A decode round of `lfm2-24b-a2b-l10` cut to an attention and a
    conv layer (dense, a small vocabulary) at the cell's 128 slots of
    160 pages and its 24,576-page pool, the backend steered to the
    chip's routes: the attention layer's read is one
    `packed_decode_attention` call, the program holds no gather or
    fusion of every slot's whole table (`bf16[20480,16,512]`, which the
    traced round of the XLA route listed at 0.13 s over a span) and no
    pool-shaped copy, and the live mask reaches the read as the
    lengths."""
    import flax.linen as nn
    from skypilot_tpu.models import lfm2
    from skypilot_tpu.ops import pallas_paged
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    cfg = lfm2.Lfm2Config.l10(layer_types=('full_attention', 'conv'),
                              vocab_size=1024, kv_total_pages=24576)
    model = lfm2.Lfm2(cfg)
    slots, pages = LFM2_SLOTS, LFM2_PAGES_PER_ROW
    variables = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
        positions=jnp.zeros((slots, 1), jnp.int32), decode=True,
        page_indices=jnp.zeros((slots, pages), jnp.int32))))

    def aval(s, dtype=None):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                    sharding=one_chip)

    def step(params, cache, tokens, positions, table, live):
        logits, mutated = model.apply(
            {'params': params, 'cache': cache}, tokens,
            positions=positions, decode=True, page_indices=table,
            live=live, mutable=['cache'])
        return mutated['cache'], logits

    rows = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    lowered = jax.jit(step, donate_argnums=(1,)).lower(
        jax.tree.map(lambda s: aval(s, jnp.bfloat16), variables['params']),
        jax.tree.map(aval, variables['cache']), rows, rows,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, 1), jnp.bool_, sharding=one_chip))
    # The live mask is one of the kernel's inputs: the lengths a select.
    assert 'select' in str(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and '/packed_decode_attention/' in line]
    assert len(calls) == 1
    assert not FULL_TABLE_GATHER.search(text)
    assert pool_copy_lines(compiled, variables['cache']) == []


@pytest.mark.parametrize('chunk', [1, LFM2_CHUNK],
                         ids=['decode', 'later_chunk'])
def test_the_conv_tail_is_updated_where_it_lies(compile_for_chip, chunk):
    """A conv mixer of `lfm2-24b-a2b-l10` (128 slots of a tail of two
    inputs of 2,048 one after the other, bf16[4,096]: ops/paged_attention
    .SlotArray `conv_state`): a decode round shifts the live lanes'
    tails and a later chunk reads its slot's row and writes it back,
    both with no copy of the tail array, no scatter, the array donated
    and handed back as the same buffer, and the mixer's work under the
    scope the trace is read by. (Without the barrier in `conv_step`
    XLA:TPU may order the shift before the taps and copy the old array
    for them.)"""
    from skypilot_tpu.models import lfm2
    cfg = lfm2.Lfm2Config.l10()
    rows = LFM2_SLOTS if chunk == 1 else 1
    fn, donate, avals, cache = _lfm2_program(lfm2.ShortConv(cfg), rows,
                                             chunk, prefill=False)
    assert {k: tuple(v.shape) for k, v in cache.items()} == {
        'conv_state': (LFM2_SLOTS, 4096)}
    compiled = compile_for_chip(fn, donate, *avals)
    assert pool_copy_lines(compiled, _lfm2_cache(**cache)) == []
    text = compiled.as_text()
    assert 'input_output_alias={ {0}: (0, {}, may-alias)' in text
    assert ' scatter(' not in text and 'tpu_custom_call' not in text
    assert '/short_conv/' in text and '/conv_tail/' in text
    if chunk > 1:
        assert ' dynamic-update-slice(' in text


def test_the_conv_projections_read_their_matrices_in_scope(
        one_chip, compile_for_chip, monkeypatch):
    """A decode round of two conv layers of `lfm2-24b-a2b-l10` (its two
    dense layers, a small vocabulary), the backend steered to the
    chip's route: each of the four projections is an ops/
    pallas_streamed call in scope `short_conv` that takes its matrix
    as the program's parameter, and memory-space assignment copies no
    projection's matrix into VMEM ahead of it. Without the kernel's
    HBM constraint it does: the second layer's matrices arrive in
    slices during the first layer's MLP, and the waits for them
    belong to no scope (rooflines/short_conv_decode.py)."""
    import flax.linen as nn
    from skypilot_tpu.models import lfm2
    from skypilot_tpu.ops import pallas_streamed
    monkeypatch.setattr(pallas_streamed, 'available', lambda: True)
    cfg = lfm2.Lfm2Config.l10(layer_types=('conv', 'conv'), vocab_size=1024,
                              kv_total_pages=64)
    model = lfm2.Lfm2(cfg)
    slots, pages = LFM2_SLOTS, 4
    variables = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
        positions=jnp.zeros((slots, 1), jnp.int32), decode=True,
        page_indices=jnp.zeros((slots, pages), jnp.int32))))

    def aval(s, dtype=None):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                    sharding=one_chip)

    def step(params, cache, tokens, positions, table, live):
        logits, mutated = model.apply(
            {'params': params, 'cache': cache}, tokens,
            positions=positions, decode=True, page_indices=table,
            live=live, mutable=['cache'])
        return mutated['cache'], logits

    rows = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        jax.tree.map(lambda s: aval(s, jnp.bfloat16), variables['params']),
        jax.tree.map(aval, variables['cache']), rows, rows,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots, 1), jnp.bool_, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4
    for line in calls:
        assert re.search(r'/short_conv/(in|out)_proj/\S*/streamed_matmul/',
                         line), line[:300]
        # The matrix goes in as the entry parameter, in HBM.
        operand = re.search(r'custom-call\(%\S+, %(\S+)\)', line).group(1)
        assert re.search(rf'%{re.escape(operand)} = bf16\[2048,\d+\]'
                         rf'[^ ]* parameter\(', text), operand
    assert not re.search(r'(copy|slice)-start\(%\S*_proj', text)


# -- the other cells' programs, as they lowered before models/lfm2.py ------
#: sha256 of each cell's decode round and first prefill chunk lowered for
#: the chip (`_normalized` text), the cell's published widths at 4 slots
#: and a 64-page pool, recorded from the tree before models/lfm2.py came
#: in. The LFM2 change touches code these programs run (MoEByShare's
#: shared expert, ops/ssm.causal_conv's bias, the paged read's route);
#: they must lower to the same text. A change that means to alter one
#: records its new digest here and says so.
LOWERED_BEFORE_LFM2 = {
    ('mistral-7b-l16', 'decode'):
        'f62be756b1104e667cb9163adf0ad78717f8703febc166b5999bac6bd9df8861',
    ('mistral-7b-l16', 'first_chunk'):
        '0f4e28d2659fef804aeed575ca2b22ece110ddd87233c55e78d33079cfb213fe',
    ('deepseek-v32-l5-ep16', 'decode'):
        'adcfeb966906939be6707de67da8d206c9f63bdb5acbf8520bab1cdc2a62f8f2',
    ('deepseek-v32-l5-ep16', 'first_chunk'):
        '559036a551a6f3565f498a29ffcde343844a745cda98a9f8a499413929f3e098',
    ('nemotron3-super-l11-ep4', 'decode'):
        '97d5094a915c9a578ab21f81017572b9f1f9ad7005027d5f07dbb81f6b3824cb',
    ('nemotron3-super-l11-ep4', 'first_chunk'):
        '553e81a393acc7a86554bb8cd0bdcee811cc9c3b930d28e332eacb004a5b9eda',
}


def _normalized(text):
    """A lowered module's text with each Mosaic kernel's serialized body
    replaced by the digest of its text WITHOUT source locations: a
    kernel's bytecode carries the file and line of every operation, so
    a line added above a kernel moves it, and that is no change to the
    program."""
    import base64
    import hashlib
    from jax._src.lib.mlir import ir

    def kernel(match):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return 'KERNEL ' + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', kernel, text)


def _cell_model(name):
    import json
    import os
    from perfbench import shim
    from skypilot_tpu.models import deepseek, llama, nemotron_h
    if name == 'mistral-7b-l16':
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        with open(os.path.join(root, 'perfbench', 'configs',
                               'mistral-7b-l16.json'), encoding='utf-8') as f:
            cfg = json.load(f)
        return llama.Llama(llama.LlamaConfig(
            **shim.llama_kwargs(cfg), max_seq_len=4096, kv_total_pages=64))
    if name == 'deepseek-v32-l5-ep16':
        return deepseek.Deepseek(
            deepseek.DeepseekConfig.v32_l5_ep16(kv_total_pages=64))
    return nemotron_h.NemotronH(
        nemotron_h.NemotronHConfig.super_l11_ep4(kv_total_pages=64))


@pytest.mark.parametrize('name, program', sorted(LOWERED_BEFORE_LFM2))
def test_the_other_cells_programs_lower_as_before(one_chip, monkeypatch,
                                                  name, program):
    """Mistral's, DeepSeek's and Nemotron's decode round and first
    prefill chunk (the model's call as the engine makes it, the routes
    steered to the chip's) lower to the text they lowered to before
    LFM2 shared their code."""
    import hashlib
    import flax.linen as nn
    from skypilot_tpu.ops import pallas_experts, pallas_ssm
    from skypilot_tpu.ops import pallas_paged
    for module in (pallas_paged, pallas_experts, pallas_ssm):
        monkeypatch.setattr(module, 'available', lambda: True)
    slots, pages, chunk = 4, 8, 64
    model = _cell_model(name)
    var = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
        positions=jnp.zeros((slots, 1), jnp.int32), decode=True,
        page_indices=jnp.zeros((slots, pages), jnp.int32))))

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: aval(s.shape, (
        jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
        else s.dtype)), var['params'])
    cache = jax.tree.map(lambda s: aval(s.shape, s.dtype), var['cache'])
    takes_live = getattr(model, 'takes_live_mask', False)
    slot_state = bool(model.config.page_layout().slot_arrays)
    first = program == 'first_chunk'
    rows, seq = (1, chunk) if first else (slots, 1)

    def fn(params, cache, tokens, positions, table, live, slot):
        kw = {'live': live} if takes_live else {}
        if slot_state and first:
            kw['slots'] = slot[None]
        logits, mutated = model.apply(
            {'params': params, 'cache': cache}, tokens, positions=positions,
            decode=True, page_indices=table, prefill=first,
            page_aligned=first, mutable=['cache'], **kw)
        return mutated['cache'], logits

    lowered = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, aval((rows, seq), jnp.int32),
        aval((rows, seq), jnp.int32), aval((rows, pages), jnp.int32),
        aval((rows, seq), jnp.bool_), aval((), jnp.int32))
    assert hashlib.sha256(_normalized(lowered.as_text()).encode()
                          ).hexdigest() == LOWERED_BEFORE_LFM2[name, program]
