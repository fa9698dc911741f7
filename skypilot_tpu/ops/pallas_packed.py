"""The one-token decode read of a PACKED K/V pool in one Pallas TPU pass
(route 'packed_decode'): the live pages of the live rows, each read
once.

A packed pool (ops/paged_attention.packed_kv_layout, kind 'kv_packed':
LFM2's 8 K/V heads of 64 lanes side by side in one 512-lane row) is
[1, pages, page, Hkv * D]. The XLA route (`_packed_attention`) gathers
every slot's WHOLE page table out of it and multiplies the gathered
rows: at 128 slots of 160 pages that is [20480, 16, 512] a pool and
layer, written and read back, for some 32 live rows of about 510
tokens (PERF.md). This kernel walks what is live instead:

  - The lengths and the flat page table are scalar-prefetched into
    SMEM; a row of length 0 is stepped over with no copy and no
    product, and a table entry past ceil(length / page) is never read,
    so neither is the trash page behind it.
  - A page of a pool is ONE 16 KB copy (`pool.at[0, page]`: [page, Hkv
    * D], whole (sublane, 128-lane) tiles) into a block of
    `decode_block_pages` pages, sized from the static shapes against
    `pallas_paged._DECODE_VMEM_BUDGET` as the 128-wide decode kernel's
    is. While a block is multiplied the next one (of this row, or the
    first of the next live row) is in flight.
  - The rows stay packed, with `_packed_attention`'s semantics: each
    query head is set into its K/V head's D lanes of a zero row
    (block-diagonal), so QK^T contracts all Hkv * D lanes in one [Hq,
    Hkv * D] x [Hkv * D, block] product, and PV gives every lane, of
    which each head keeps its own. Scores, the softmax state and the
    accumulator are float32; the weights meet the values in the pool's
    dtype, as in the XLA route.
  - The scale is 1 / sqrt(D), the head's own width (the query's last
    axis), passed in: never 1 / sqrt(Hkv * D), which the pool's last
    axis would give.

VMEM. q and o are held whole in VMEM at the heads' OWN D lanes ([B, Hq,
D]; the spread into a packed row and the collapse back are made in
VMEM a row at a time, each a product with a 0/1 [D, Hkv * D] matrix,
exact), not as [B, Hq, Hkv * D] rows, which at 128 slots would be 4
MiB each and overflow the 16 MiB scoped default once doubled and the
page buffers added. At `lfm2-24b-a2b-l10`'s 128 slots of 32 heads q and
o take 1 MiB each (64 lanes padded to a tile of 128), 4 MiB with the
pipeline's two buffers; the page buffers (K and V, two slots each) 4
MiB; the call asks for what these need plus 8 MiB for the products'
temporaries and the compiler (`vmem_limit_bytes`).

The call is jitted on its own (`_decode_call`), so a program of several
attention layers lowers the kernel once per operand shape.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import pallas_paged

F32 = jnp.float32
#: Room the call asks for beyond its buffers, q and o: the block's
#: scores and weights, the packed query and accumulator, the compiler's.
_VMEM_SLACK = 8 << 20
_SUBLANES = {4: 8, 2: 16}


def kernel_refusal(pool: Any) -> Optional[str]:
    """Why the kernel does not take the packed pool `pool` ([1, pages,
    page, W], an array or its ShapeDtypeStruct: only the static shape
    is read), or None when it does: a float dtype of 2 or 4 bytes, one
    row a token, a row of whole 128-lane tiles and a page of whole
    sublane tiles, so a page is one copy of whole tiles and a block's
    pages view as one [tokens, W] matrix."""
    heads, _, page_size, width = pool.shape
    dtype = jnp.dtype(pool.dtype)
    if dtype.itemsize not in _SUBLANES or not jnp.issubdtype(
            dtype, jnp.floating):
        return f'pool dtype {dtype.name} is not bf16/f16/f32'
    if heads != 1:
        return f'a packed pool keeps one row a token, not {heads}'
    if width % 128 != 0:
        return f'a packed row of {width} lanes is not whole 128-lane tiles'
    if page_size % _SUBLANES[dtype.itemsize] != 0:
        return (f'page_size {page_size} is not a multiple of the '
                f'{_SUBLANES[dtype.itemsize]}-sublane tile of a '
                f'{dtype.itemsize}-byte dtype')
    return None


def _kernel(sm_scale, group, block_pages, pages_per_seq, perturb,
            lengths_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
            sems):
    """One invocation walks every (row, block) with block * tokens <
    length, as `pallas_paged._decode_kernel` does.

    Refs: lengths i32[B] and the flat table i32[B * pages_per_seq] in
    SMEM; q/o [B, Hq, D] whole in VMEM; the pools [1, P, page, W] where
    they lie (HBM); k/vbuf [2, block_pages, page, W]; DMA semaphores
    [slot, pool]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, num_q_heads, head_dim = q_ref.shape
    _, _, page_size, width = k_hbm.shape
    block = block_pages * page_size
    dtype = kbuf.dtype

    def each_copy(b, i, slot, act):
        """act(descriptor) for the live pages of block i of row b."""
        first = i * block_pages
        live = jnp.minimum(
            (lengths_ref[b] + page_size - 1) // page_size - first,
            block_pages)

        def page_copies(j, _):
            page = tbl_ref[b * pages_per_seq + first + j]
            act(pltpu.make_async_copy(
                k_hbm.at[0, page], kbuf.at[slot, j], sems.at[slot, 0]))
            act(pltpu.make_async_copy(
                v_hbm.at[0, page], vbuf.at[slot, j], sems.at[slot, 1]))

        jax.lax.fori_loop(0, live, page_copies, None)

    def start(b, i, slot):
        each_copy(b, i, slot, lambda copy: copy.start())

    def wait(b, i, slot):
        each_copy(b, i, slot, lambda copy: copy.wait())

    def next_live(b):
        """The first row >= b with length > 0, else `batch`."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < batch,
                lengths_ref[jnp.minimum(r, batch - 1)] == 0),
            lambda r: r + 1, b)

    # A dead row's output is zeros; a page slot no copy has filled
    # must not hold a NaN pattern for a zero weight to multiply.
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
    # tile[d, w] = 1 where lane w of a packed row is lane d of its head;
    # own[h, w]: lane w belongs to query head h's K/V head.
    lane = jax.lax.broadcasted_iota(jnp.int32, (head_dim, width), 1)
    tile = (lane - lane // head_dim * head_dim == jax.lax.broadcasted_iota(
        jnp.int32, (head_dim, width), 0)).astype(q_ref.dtype)
    own = (jax.lax.broadcasted_iota(jnp.int32, (num_q_heads, width), 0)
           // group == jax.lax.broadcasted_iota(
               jnp.int32, (num_q_heads, width), 1) // head_dim)
    # A product with the 0/1 `tile` is exact: one term a result. A
    # float32 operand needs the full-precision passes for that; Mosaic
    # refuses them on a 16-bit one, which one pass already keeps whole.
    exact = (jax.lax.Precision.HIGHEST if jnp.dtype(q_ref.dtype).itemsize
             == 4 else None)

    def row(carry):
        b, slot0 = carry
        length = lengths_ref[b]
        blocks = (length + block - 1) // block
        after = next_live(b + 1)
        # The row's heads, each in its K/V head's lanes of a zero row.
        spread = jnp.where(own, jnp.dot(
            q_ref[b], tile, precision=exact, preferred_element_type=F32),
            0.0).astype(dtype)                      # [Hq, W]

        def step(i, state):
            m_prev, l_prev, acc = state
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < blocks)
            def _():
                start(b, i + 1, 1 - slot)

            @pl.when(jnp.logical_and(i + 1 == blocks, after < batch))
            def _():
                start(after, 0, 1 - slot)

            wait(b, i, slot)
            s = jax.lax.dot_general(
                spread, kbuf[slot].reshape(block, width),
                (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * sm_scale  # [Hq, block]
            if perturb:
                # Non-vacuity hook, as `_decode_kernel`'s.
                s = s * (1.0 + perturb)
            t_idx = (i * block +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(t_idx < length, s, -jnp.inf)
            # A walked block holds at least one live token: m_new is
            # finite and every exp() argument finite or -inf.
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            w = jnp.exp(s - m_new)                  # [Hq, block] f32
            l_new = l_prev * alpha + jnp.sum(w, axis=-1, keepdims=True)
            pv = jnp.dot(w.astype(dtype), vbuf[slot].reshape(block, width),
                         preferred_element_type=F32)
            return m_new, l_new, acc * alpha + pv

        _, l, acc = jax.lax.fori_loop(0, blocks, step, (
            jnp.full((num_q_heads, 1), -jnp.inf, F32),
            jnp.zeros((num_q_heads, 1), F32),
            jnp.zeros((num_q_heads, width), F32)))
        # Each head keeps its own K/V head's D lanes of the row.
        out = jnp.where(own, acc / l, 0.0)
        o_ref[b] = jax.lax.dot_general(
            out, tile.astype(F32), (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=F32).astype(o_ref.dtype)
        return after, (slot0 + blocks) % 2

    first = next_live(jnp.int32(0))

    @pl.when(first < batch)
    def _():
        start(first, 0, 0)

    jax.lax.while_loop(lambda carry: carry[0] < batch, row,
                       (first, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=('interpret', 'perturb'))
def _decode_call(q, k_pages, v_pages, lengths, page_indices, *, interpret,
                 perturb):
    """Jitted on its own, as `pallas_paged._decode_call` is: a program of
    L attention layers traces and lowers the kernel once, not L times."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, num_q_heads, head_dim = q.shape
    _, _, page_size, width = k_pages.shape
    pages_per_seq = page_indices.shape[1]
    block_pages = pallas_paged.decode_block_pages(k_pages, pages_per_seq)
    kernel = functools.partial(
        _kernel, head_dim ** -0.5, num_q_heads // (width // head_dim),
        block_pages, pages_per_seq, perturb)
    whole = pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0))
    buf = pltpu.VMEM((2, block_pages, page_size, width), k_pages.dtype)
    itemsize = jnp.dtype(q.dtype).itemsize
    rows = _SUBLANES.get(itemsize, 8)
    # q and o as VMEM tiles them (two buffers each), the page buffers.
    q_bytes = (batch * -(-num_q_heads // rows) * rows
               * -(-head_dim // 128) * 128 * itemsize)
    vmem = (4 * q_bytes + 4 * block_pages * page_size * width
            * jnp.dtype(k_pages.dtype).itemsize + _VMEM_SLACK)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=int(vmem)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='packed_decode_attention',
    )(lengths.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1), q, k_pages, v_pages)


def packed_decode_kernel(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, lengths: jax.Array,
                         page_indices: jax.Array, *,
                         interpret: bool = False,
                         perturb: float = 0.0) -> jax.Array:
    """The decode read of a packed pool (route 'packed_decode'): one
    query token a row over its paged history.

    q: [B, Hq, D]; k/v_pages: [1, total_pages, page_size, Hkv * D] of a
    shape `kernel_refusal` takes (interpret mode takes any); lengths
    i32[B] (0: the row is skipped and returns zeros); page_indices
    i32[B, pages_per_seq]. Returns [B, Hq, D] in q.dtype:
    ops/paged_attention.packed_decode_attention's semantics."""
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    kv_heads, rem = divmod(k_pages.shape[-1], q.shape[-1])
    assert rem == 0 and q.shape[1] % kv_heads == 0, (q.shape,
                                                     k_pages.shape)
    refusal = kernel_refusal(k_pages)
    if refusal is not None and not interpret:
        raise ValueError(f"paged-attention impl 'packed_decode': {refusal}")
    return _decode_call(q, k_pages, v_pages, lengths, page_indices,
                        interpret=interpret, perturb=perturb)
