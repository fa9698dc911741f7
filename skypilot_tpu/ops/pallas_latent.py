"""A prefill chunk's attention over a row's latent rows, one Pallas TPU
kernel: a block of scores lives and dies in VMEM.

The XLA walk of ops/sparse_latent.py (`_chunk_row`'s second loop) is two
matrix products a block of keys with the online softmax's passes
between them, and every one of them carries the block of scores,
float32 [heads, chunk, keys] (134 MB at DeepSeek-V3.2's 128 heads, a
512-token chunk and 512 keys), through HBM: 53% of the device's busy
time in `deepseek-v32-l5-ep16.longctx-saturated` (PERF.md, PR 34).
`latent_chunk_attention` is the same arithmetic, term for term, with
the scores, the mask, the running maximum and sum and the accumulator
in VMEM: operands as stored, float32 scores and sums, `p` rounded to
the rows' dtype before the second product, a query with nothing
selected so far shifted by 0, `acc / l` written once.

What the kernel is handed is already plain: the query matrix a head
[heads, chunk, width], the row's latent rows as ONE [keys, width]
matrix (XLA gathers the row's pages once a layer; every head reads the
same rows, a block at a time, by `BlockSpec`), the selection as a mask
int8 [chunk, keys] (`sparse_latent.topk_mask`'s rule and the causal
bound: the selection itself stays exact XLA) and the number of key
blocks the chunk's last position reaches, a prefetched scalar: a block
past it is neither copied nor multiplied, so a chunk costs what its
context costs and no compiled shape depends on a context's length.

`pallas_paged.resolve_impl(layout='latent', latent_chunk=...)` chooses
it (`chunk_kernel_refusal` says which static shapes it takes). Kept in
a module of its own: a Mosaic body's source lines are in the compile
cache's key, so an edit to ops/pallas_paged.py costs the prefill
programs no cold compile, nor the reverse.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: VMEM the buffers that follow the head tile may take (a head's
#: queries and its output block, twice each for the pipeline, and its
#: running maximum and sum): 8 heads a step at 512 queries of 640 bf16
#: values and 512 float32 outputs. A larger tile only saves grid steps:
#: the rows a head tile re-reads are 0.66 MB a block against 0.6 GFLOP
#: a head, so the products bound every tile size (12 blocks on a v5e:
#: 6.59 ms with 1 head a step, 6.54 with 2, 6.18 with 4, 6.02 with 8,
#: 78% of the MXU's peak: my chip run, PR 34).
_HEAD_TILE_BUDGET = 32 << 20
#: The kernel's VMEM limit: the head tile, a block of rows and of the
#: mask twice, and a head's block of scores with its temporaries
#: (float32 [chunk, keys], 1 MB at 512 x 512, a handful live at once).
_VMEM_LIMIT = 64 << 20


def chunk_kernel_refusal(q: Any, block: int,
                         value_dim: int) -> Optional[str]:
    """Why `latent_chunk_attention` does not take this chunk read, or
    None when it does. `q`: the queries [.., chunk, heads, width], an
    array or its ShapeDtypeStruct (only the static shape and the dtype
    are read: the rows have the same width and dtype); `block`: keys a
    step; `value_dim`: the row's leading values that are summed.
    Every block the kernel cuts must be whole (sublane, 128-lane)
    tiles: a head's [chunk, width] queries and [chunk, value_dim]
    output, a [block, width] slab of rows, a [chunk, block] tile of
    the mask and of the scores."""
    seq, _, width = q.shape[-3:]
    dtype = jnp.dtype(q.dtype)
    if dtype.itemsize not in (2, 4) or not jnp.issubdtype(
            dtype, jnp.floating):
        return f'row dtype {dtype.name} is not bf16/f16/f32'
    sublanes = 32 // dtype.itemsize
    if width % 128 != 0:
        return f'row width {width} is not a multiple of 128 lanes'
    if value_dim % 128 != 0 or value_dim > width:
        return (f'value_dim {value_dim} is not a multiple of 128 lanes '
                f'within the row')
    if seq % sublanes != 0:
        return (f'a chunk of {seq} queries is not a multiple of the '
                f'{sublanes}-sublane tile of a {dtype.itemsize}-byte '
                f'dtype')
    if block % 128 != 0:
        return f'a block of {block} keys is not a multiple of 128 lanes'
    return None


def _heads_per_step(heads: int, seq: int, width: int, value_dim: int,
                    itemsize: int) -> int:
    """Heads a grid step holds, from the static shapes alone: the
    largest power of two that divides `heads` and keeps the tile's
    buffers inside `_HEAD_TILE_BUDGET`."""
    per_head = seq * (2 * width * itemsize + 2 * value_dim * 4
                      + 2 * 128 * 4)
    tile = 1
    while (heads % (tile * 2) == 0
           and tile * 2 * per_head <= _HEAD_TILE_BUDGET):
        tile *= 2
    return tile


def _chunk_kernel(scale, value_dim, n_blocks_ref, q_ref, rows_ref,
                  keep_ref, o_ref, m_ref, l_ref):
    """Grid (head tiles, key blocks): one block of the row's keys
    against a tile of heads a step; the output block is the
    accumulator (it stays in VMEM while the head tile does), the
    running maximum and sum are scratch.

    Refs (blocks): n_blocks i32[1] in SMEM; q [tile, chunk, width];
    rows [block, width]; keep i8[chunk, block]; o f32[tile, chunk,
    value_dim]; m, l f32[tile, chunk, 1]."""
    import jax.experimental.pallas as pl
    kb = pl.program_id(1)
    n_blocks = n_blocks_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        o_ref[...] = jnp.zeros(o_ref.shape, F32)

    @pl.when(kb < n_blocks)
    def _step():
        rows = rows_ref[...]                        # [block, width]
        values = rows[:, :value_dim]
        keep = keep_ref[...].astype(jnp.int32) != 0  # [chunk, block]

        def head(h, _):
            # q @ rows^T as a 2-D NT contraction, operands as stored.
            s = jax.lax.dot_general(
                q_ref[h], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * scale
            s = jnp.where(keep, s, -jnp.inf)
            m_prev = m_ref[h]                       # [chunk, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            # A query with nothing selected so far keeps -inf: shift
            # by 0 there, so that exp(-inf - m) stays 0 and never NaN.
            shift = jnp.where(m_new > -jnp.inf, m_new, 0.0)
            p = jnp.exp(s - shift)
            fade = jnp.exp(m_prev - shift)
            l_ref[h] = l_ref[h] * fade + jnp.sum(p, axis=-1,
                                                 keepdims=True)
            o_ref[h] = o_ref[h] * fade + jnp.dot(
                p.astype(rows.dtype), values, preferred_element_type=F32)
            m_ref[h] = m_new

        jax.lax.fori_loop(0, q_ref.shape[0], head, None)

    @pl.when(kb == n_blocks - 1)
    def _finish():
        o_ref[...] = o_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames=('block', 'value_dim',
                                             'scale', 'interpret'))
def _chunk_call(q, rows, keep, n_blocks, *, block, value_dim, scale,
                interpret):
    """Jitted on its own, as `pallas_paged._decode_call` is: a program
    of L layers traces and lowers the kernel once."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    heads, seq, width = q.shape
    tile = _heads_per_step(heads, seq, width, value_dim,
                           jnp.dtype(q.dtype).itemsize)

    def per_head(h, kb, n):
        return (h, 0, 0)

    def walked(kb, n):
        # A block past the walk's end names the last one walked: the
        # pipeline copies nothing for an index that does not change.
        return jnp.minimum(kb, n[0] - 1)

    running = pltpu.VMEM((tile, seq, 1), F32)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, scale, value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads // tile, rows.shape[0] // block),
            in_specs=[
                pl.BlockSpec((tile, seq, width), per_head),
                pl.BlockSpec((block, width),
                             lambda h, kb, n: (walked(kb, n), 0)),
                pl.BlockSpec((seq, block),
                             lambda h, kb, n: (0, walked(kb, n)))],
            out_specs=pl.BlockSpec((tile, seq, value_dim), per_head),
            scratch_shapes=[running, running]),
        out_shape=jax.ShapeDtypeStruct((heads, seq, value_dim), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='latent_chunk_attention',
    )(n_blocks.astype(jnp.int32).reshape(1), q, rows, keep)


def latent_chunk_attention(q: jax.Array, rows: jax.Array,
                           keep: jax.Array, n_blocks: jax.Array, *,
                           block: int, value_dim: int, scale: float,
                           interpret: bool = False) -> jax.Array:
    """Attention of one row's chunk over the row's latent rows under
    the selection's mask (route 'sparse_latent_pallas').

    q [heads, chunk, width]: a head's queries in the cached row's own
    terms; rows [keys, width]: the row's context in order, `keys` a
    whole number of blocks; keep bool[chunk, keys]: the keys a query
    attends (at least one in its first `n_blocks` blocks); n_blocks
    i32[]: the blocks of `block` keys to walk, at least 1. Returns
    f32[heads, chunk, value_dim]: the softmax-weighted sum of each
    query's kept rows' first `value_dim` values, as
    `sparse_latent._chunk_row`'s walk gives it.
    Under a tensor mesh each chip runs it on its own heads
    (`pallas_paged.shard_over_kv_heads`: heads are independent)."""
    refusal = chunk_kernel_refusal(
        jax.ShapeDtypeStruct((q.shape[1], q.shape[0], q.shape[2]),
                             q.dtype), block, value_dim)
    if refusal is not None:
        raise ValueError(f"route 'sparse_latent_pallas': {refusal}")
    assert rows.shape[0] % block == 0, (rows.shape, block)
    from jax.sharding import PartitionSpec as P
    from skypilot_tpu.ops import pallas_paged
    by_head, whole = P('tensor', None, None), P(None, None)
    call = functools.partial(_chunk_call, block=block,
                             value_dim=value_dim, scale=float(scale),
                             interpret=interpret)
    return pallas_paged.shard_over_kv_heads(
        call, q.shape[0], in_specs=(by_head, whole, whole, P()),
        out_specs=by_head)(q, rows, keep.astype(jnp.int8), n_blocks)
