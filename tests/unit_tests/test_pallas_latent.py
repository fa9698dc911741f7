"""A prefill chunk's attention over latent pages, the kernel of
ops/pallas_latent.py in Pallas interpret mode on the CPU: against the
XLA walk of ops/sparse_latent.py on the same operands, the mask it is
handed against `topk_mask(...) & causal`, and the route's rules (what
`resolve_impl` chooses, what the refusal function refuses, what the
engine reports). Nothing here says what Mosaic accepts or how long a
call takes: tests/unit_tests/test_pool_write_aot.py compiles the kernel
for a described v5e, ops/kernel_check.py runs it on one.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import pallas_latent, pallas_paged
from skypilot_tpu.ops import sparse_latent as sl

PAGE, HEADS, WIDTH, VALUES, INDEX_HEADS, INDEX_DIM = 16, 4, 256, 128, 3, 8
SCALE = 0.3

#: name -> (chunk, offset of its first query, pages of the row's table,
#: pages a block, topk, what is special). A block is 8 pages = 128 keys.
CASES = {
    # Every causal key is selected: fewer than `topk` of them.
    'context_shorter_than_topk': dict(chunk=32, offset=40, pages=16,
                                      topk=512),
    # Integer index scores from a handful of values, six blocks walked:
    # the threshold is tied many times over, inside and across blocks.
    'blocks_with_ties_at_the_threshold': dict(chunk=32, offset=700,
                                              pages=48, topk=64,
                                              ties=True),
    # Queries 100..147: the chunk's own rows lie in blocks 0 and 1.
    'chunk_straddles_a_block_end': dict(chunk=48, offset=100, pages=16,
                                        topk=48),
    # MLA without an indexer: the mask is the causal one.
    'no_indexer': dict(chunk=32, offset=200, pages=16, topk=64,
                       indexed=False),
    # Block 0's index keys are zeros and score 0, below every later
    # key: the running maximum is still -inf after the first block.
    'nothing_selected_in_the_first_block': dict(chunk=32, offset=600,
                                                pages=40, topk=64,
                                                dead_first=True),
    # The row's pages lie in the pool in a shuffled order.
    'page_table_is_a_permutation': dict(chunk=32, offset=300, pages=24,
                                        topk=96, permuted=True),
}


def _operands(dtype, chunk, offset, pages, topk, indexed=True, ties=False,
              dead_first=False, permuted=False):
    keys = jax.random.split(jax.random.PRNGKey(offset), 7)
    total_pages = pages + 1                     # page 0: the trash page
    table = jnp.arange(1, total_pages, dtype=jnp.int32)
    if permuted:
        table = jax.random.permutation(keys[0], table)
    latent = jax.random.normal(keys[1], (1, total_pages, PAGE, WIDTH),
                               dtype)
    q = jax.random.normal(keys[2], (1, chunk, HEADS, WIDTH), dtype)
    positions = (offset + jnp.arange(chunk, dtype=jnp.int32))[None]
    if not indexed:
        return dict(q=q, q_idx=None, w_idx=None, latent=latent,
                    index_k=None, positions=positions, table=table[None],
                    topk=topk)
    shape_k = (1, total_pages, PAGE, INDEX_DIM)
    shape_q = (1, chunk, INDEX_HEADS, INDEX_DIM)
    if ties or dead_first:
        # Small non-negative integers: every score is an exact integer.
        index_k = jax.random.randint(keys[3], shape_k, 0, 3).astype(
            jnp.float32)
        q_idx = jax.random.randint(keys[4], shape_q, 0, 2).astype(
            jnp.float32)
        w_idx = jnp.ones((1, chunk, INDEX_HEADS), jnp.float32)
    else:
        index_k = jax.random.normal(keys[3], shape_k, jnp.float32)
        q_idx = jax.random.normal(keys[4], shape_q, jnp.float32)
        w_idx = jax.random.normal(keys[5], (1, chunk, INDEX_HEADS),
                                  jnp.float32)
    if dead_first:
        # The first block's keys (8 pages of the row) score 0; every
        # query and later key has a 1 in common, so scores 1 or more.
        index_k = index_k.at[0, table[:8]].set(0.0)
        index_k = index_k.at[0, table[8:], :, 0].set(1.0)
        q_idx = q_idx.at[..., 0].set(1.0)
    return dict(q=q, q_idx=q_idx, w_idx=w_idx, latent=latent,
                index_k=index_k, positions=positions, table=table[None],
                topk=topk)


def _chunk(ops, route, interpret=False):
    return jax.jit(lambda q, q_idx, w_idx, latent, index_k, positions,
                   table: sl.sparse_latent_chunk(
                       q, q_idx, w_idx, latent, index_k, positions, table,
                       topk=ops['topk'], scale=SCALE, value_dim=VALUES,
                       block_pages=8, route=route, interpret=interpret))(
        *(ops[k] for k in ('q', 'q_idx', 'w_idx', 'latent', 'index_k',
                           'positions', 'table')))


@pytest.mark.parametrize('dtype, tolerance', [
    (jnp.float32, 1e-5), (jnp.bfloat16, 5e-3)], ids=['f32', 'bf16'])
@pytest.mark.parametrize('case', list(CASES))
def test_the_kernel_equals_the_walk(case, dtype, tolerance):
    """The kernel through the Pallas interpreter against `_chunk_row`'s
    XLA walk, same operands, same mask: float32 operands to 1e-5, bf16
    within the walk's own rounding (both round `p` to bf16 before the
    second product)."""
    ops = _operands(dtype, **CASES[case])
    walk = _chunk(ops, 'sparse_latent_xla')
    kernel = _chunk(ops, 'sparse_latent_pallas', interpret=True)
    assert kernel.shape == walk.shape == (
        1, CASES[case]['chunk'], HEADS, VALUES)
    assert kernel.dtype == walk.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(kernel)))
    np.testing.assert_allclose(kernel, walk, rtol=tolerance,
                               atol=tolerance)


def test_rows_take_the_kernel_in_turn():
    """Two rows of one call (their own tables, positions and queries
    over one pool): a Pallas call is not batched, so each row is a
    call, and both equal the walk's vmapped rows."""
    ops = _operands(jnp.float32, **CASES['page_table_is_a_permutation'])
    two = {k: (v if k in ('topk', 'latent', 'index_k') else
               jnp.concatenate([v, jnp.flip(v, axis=1)
                                if k == 'table' else v + 1], axis=0))
           for k, v in ops.items()}
    assert two['q'].shape[0] == two['table'].shape[0] == 2
    walk = _chunk(two, 'sparse_latent_xla')
    kernel = _chunk(two, 'sparse_latent_pallas', interpret=True)
    assert kernel.shape == walk.shape and kernel.shape[0] == 2
    np.testing.assert_allclose(kernel, walk, rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(kernel[0] - kernel[1]))) > 1e-3


def _dense_scores(ops):
    """(causal, index scores -inf past a query's position) of the whole
    row at once: no blocks, no loop."""
    total = ops['table'].shape[1] * PAGE
    causal = jnp.arange(total)[None] <= ops['positions'][0][:, None]
    keys = ops['index_k'][0][ops['table'][0]].reshape(total, INDEX_DIM)
    s = jnp.einsum('shd,kd->shk', ops['q_idx'][0], keys,
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.sum(jax.nn.relu(s) * ops['w_idx'][0][..., None], axis=1)
    return causal, jnp.where(causal, s, -jnp.inf)


def _dense_keep(ops):
    """`topk_mask(index_scores, topk) & causal`."""
    if ops['index_k'] is None:
        total = ops['table'].shape[1] * PAGE
        return jnp.arange(total)[None] <= ops['positions'][0][:, None]
    causal, scores = _dense_scores(ops)
    return sl.topk_mask(scores, ops['topk']) & causal


@pytest.mark.parametrize('case', list(CASES))
def test_the_mask_handed_over_is_topk_mask_and_causal(case):
    """The same keys are kept as `lax.top_k` would, to the last tie:
    what `chunk_keep` makes (and `_chunk_row` hands the walk and the
    kernel alike) is `topk_mask(index_scores, topk) & causal`."""
    ops = _operands(jnp.float32, **CASES[case])
    keep = jax.jit(lambda q_idx, w_idx, positions, table, index_k:
                   sl.chunk_keep(q_idx, w_idx, positions, table, index_k,
                                 page=PAGE, topk=ops['topk'],
                                 block_pages=8))(
        *(None if ops[k] is None else ops[k][0] if k != 'index_k'
          else ops[k] for k in ('q_idx', 'w_idx', 'positions', 'table',
                                'index_k')))
    want = _dense_keep(ops)
    assert keep.dtype == jnp.bool_ and keep.shape == want.shape
    assert bool(jnp.all(keep == want))
    per_query = jnp.sum(keep, axis=1)
    context = ops['positions'][0] + 1
    assert per_query.tolist() == (
        context if ops['index_k'] is None
        else jnp.minimum(context, ops['topk'])).tolist()
    if CASES[case].get('ties'):
        # The case is what it says: every query's threshold is tied,
        # and some of the keys that score it are left out.
        _, scores = _dense_scores(ops)
        bar = sl.kth_largest(scores, ops['topk'])[:, None]
        left_out = jnp.sum((scores == bar) & ~keep, axis=1)
        assert bool(jnp.all(left_out > 0)), left_out
    if CASES[case].get('dead_first'):
        assert not bool(jnp.any(keep[:, :8 * PAGE]))


def test_the_kernel_is_not_a_copy_of_the_walk():
    """The pins bite: the kernel on a mask with one key less differs."""
    ops = _operands(jnp.float32, **CASES['context_shorter_than_topk'])
    q = jnp.swapaxes(ops['q'][0], 0, 1)
    rows = ops['latent'][0][ops['table'][0]].reshape(-1, WIDTH)
    keep = _dense_keep(ops)

    def run(keep):
        return pallas_latent.latent_chunk_attention(
            q, rows, keep, jnp.int32(1), block=128, value_dim=VALUES,
            scale=SCALE, interpret=True)

    full, less = run(keep), run(keep.at[:, 3].set(False))
    assert float(jnp.max(jnp.abs(full - less))) > 1e-3


V32_Q = jax.ShapeDtypeStruct((512, 128, 640), jnp.bfloat16)


@pytest.mark.parametrize('q, block, value_dim, why', [
    (V32_Q, 512, 512, None),
    (jax.ShapeDtypeStruct((16, 128, 640), jnp.bfloat16), 512, 512, None),
    (jax.ShapeDtypeStruct((8, 128, 640), jnp.bfloat16), 512, 512,
     'a chunk of 8 queries'),
    # deepseek-v32-tiny: a 128-wide row of which 112 values are summed,
    # 4 heads, 32-token chunks, 16 pages a row.
    (jax.ShapeDtypeStruct((32, 4, 128), jnp.bfloat16), 256, 112,
     'value_dim 112'),
    # deepseek-tiny (MLA without an indexer): a 48-wide row.
    (jax.ShapeDtypeStruct((32, 4, 48), jnp.float32), 256, 32,
     'row width 48'),
    (V32_Q, 96 * 16, 512, None),
    (V32_Q, 4 * 16, 512, 'a block of 64 keys'),
    (jax.ShapeDtypeStruct((512, 128, 640), jnp.int8), 512, 512,
     'row dtype int8'),
], ids=['v32', 'v32_tail_16', 'v32_tail_8', 'v32_tiny', 'tiny',
        'block_1536', 'block_64', 'int8'])
def test_what_the_kernel_takes_and_refuses(q, block, value_dim, why,
                                           monkeypatch):
    """Static shapes decide, by name: whole (sublane, lane) tiles or
    the walk. On a TPU `resolve_impl` follows the refusal; off one
    (this backend) every chunk keeps the walk."""
    refusal = pallas_latent.chunk_kernel_refusal(q, block, value_dim)
    if why is None:
        assert refusal is None
    else:
        assert why in refusal
    chunk = (q, block, value_dim)
    assert pallas_paged.resolve_impl(
        layout='latent', latent_chunk=chunk) == 'sparse_latent_xla'
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    assert pallas_paged.resolve_impl(layout='latent', latent_chunk=chunk) == (
        'sparse_latent_pallas' if why is None else 'sparse_latent_xla')
    # The decode round's reads pass no chunk: plain XLA on any backend.
    assert pallas_paged.resolve_impl(layout='latent') == 'sparse_latent_xla'
    if why is not None:
        with pytest.raises(ValueError, match='sparse_latent_pallas'):
            pallas_latent.latent_chunk_attention(
                jnp.zeros((q.shape[1], q.shape[0], q.shape[2]), q.dtype),
                jnp.zeros((block, q.shape[2]), q.dtype),
                jnp.ones((q.shape[0], block), bool), jnp.int32(1),
                block=block, value_dim=value_dim, scale=1.0,
                interpret=True)


def _engine_like(chunk, heads, width, rank, pages_per_seq, dtype):
    """What `chunk_attention_impl` reads of an engine, at a size no
    test builds one."""
    return types.SimpleNamespace(
        paged=True, prefill_chunk=chunk, page_size=16,
        pages_per_seq=pages_per_seq,
        page_layout=types.SimpleNamespace(kind='latent'),
        _pool_aval=jax.ShapeDtypeStruct((1, 2048, 16, width), dtype),
        model=types.SimpleNamespace(config=types.SimpleNamespace(
            num_heads=heads, kv_lora_rank=rank)))


@pytest.mark.parametrize('engine, on_a_tpu', [
    (_engine_like(512, 128, 640, 512, 1024, jnp.bfloat16),
     'sparse_latent_pallas'),
    (_engine_like(32, 4, 128, 112, 16, jnp.bfloat16), 'sparse_latent_xla'),
], ids=['v32_l5_ep16', 'v32_tiny'])
def test_stats_names_the_chunk_reads_route(engine, on_a_tpu, monkeypatch):
    """/stats `chunk_attention_impl` is `resolve_impl`'s answer for the
    engine's own chunk read: a refused shape reads the walk, on a TPU
    too, and so does every shape off one."""
    route = ContinuousBatchingEngine.chunk_attention_impl
    assert route(engine) == 'sparse_latent_xla'
    monkeypatch.setattr(pallas_paged, 'available', lambda: True)
    assert route(engine) == on_a_tpu


def test_under_a_tensor_mesh_each_chip_takes_its_own_heads():
    """Heads are independent, so under `--tensor N` the kernel is
    shard-mapped over them (rows, mask and the block count replicated)
    and the result is the unsharded call's to the last bit."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 host devices')
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    ops = _operands(jnp.float32, **CASES['chunk_straddles_a_block_end'])
    q = jnp.swapaxes(ops['q'][0], 0, 1)
    rows = ops['latent'][0][ops['table'][0]].reshape(-1, WIDTH)
    args = (q, rows, _dense_keep(ops), jnp.int32(2))
    kw = dict(block=128, value_dim=VALUES, scale=SCALE, interpret=True)
    alone = pallas_latent.latent_chunk_attention(*args, **kw)
    with mesh:
        jaxpr = jax.make_jaxpr(lambda *a: pallas_latent.
                               latent_chunk_attention(*a, **kw))(*args)
        sharded = pallas_latent.latent_chunk_attention(*args, **kw)
    assert 'shard_map' in str(jaxpr)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(alone))
