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
def test_state_by_slot_is_updated_where_it_lies(compile_for_chip, chunk):
    """The second kind of cache at the cell's shapes (128 slots of
    f32[128, 64, 128] state, 512 MiB a layer, and of bf16[30720]
    convolution tail): a decode round's live rows' loop and a prefill
    chunk's row, read at its slot and written back, compile for the
    chip with no copy of either array and, on one chip, no
    collective."""
    from skypilot_tpu.parallel.serving import pool_collective_lines
    fn, donate, avals, cache = _mamba_layer(chunk)
    compiled = compile_for_chip(fn, donate, *avals)
    assert {k: tuple(v.shape) for k, v in cache.items()
            if k.endswith('_state')} == {
        'ssm_state': (STATE_SLOTS, 128, 64, 128),
        'conv_state': (STATE_SLOTS, 30720)}
    assert pool_copy_lines(compiled, cache) == []
    mesh = jax.sharding.Mesh([jax.devices()[0]], ('tensor',))
    assert pool_collective_lines(compiled, cache, mesh) == []
    text = compiled.as_text()
    assert 'tpu_custom_call' not in text         # plain XLA, all of it
    assert ' dynamic-update-slice(' in text
    assert ' scatter(' not in text
    # Each array goes in and comes out as the same buffer.
    assert text.count('f32[128,128,64,128]') >= 2
