"""Learned sparse attention over a paged latent cache: plain XLA, but
for the attention of a prefill chunk where the kernel takes it.

DeepSeek-V3.2 keeps two rows a cached token and layer in the page pool
(ops/paged_attention.PageLayout, kind 'latent'): MLA's compressed row
`[c_kv | k_rope]` and the lightning indexer's key. A query reads its
context in three steps, and these are the three device paths here:

  1. the INDEX SCORES over the row's paged indexer keys,
     I(t, s) = sum_j w_j ReLU(q_j . k_s), float32 queries and keys at
     full precision (scope `indexer`);
  2. the SELECTION of the `index_topk` largest (scope `topk_select`,
     inside `indexer`);
  3. ATTENTION in the absorbed form over the selected latent rows only
     (scope `latent_attention`): the query already multiplied by W_uk,
     so keys and values are the cached row itself.

One decode token a row (`*_decode`) gathers its selected rows by index.
A prefill chunk (`sparse_latent_chunk`) has hundreds of queries with a
selection each, so it takes the context in blocks of keys instead: a
first pass leaves every query's index scores, their `index_topk`-th
largest is found exactly by bisection on the float's bits
(`kth_largest`), and the selection becomes a mask, bool [chunk, keys]
(`chunk_keep`): the scores above it and, of those equal to it, the
first few (`topk_mask`'s rule, which is `lax.top_k`'s order). Attention
under that mask with an online softmax is either the kernel of
ops/pallas_latent.py, in which a block of scores never leaves VMEM, or
the blocked XLA walk here, which carries it through HBM between its
two products. Both passes end at the chunk's last position, so a chunk
costs what its context costs, and no compiled shape depends on how
long a context is or on whether it exceeds `index_topk`. Without
indexer keys (`index_pages=None`: MLA as DeepSeek-V2 has it) every
causal position is selected.

The route is `pallas_paged.resolve_impl(layout='latent')`'s answer:
'sparse_latent_xla' for the decode round's reads on every backend and
for a chunk whose static shapes the kernel refuses (and off a TPU),
'sparse_latent_pallas' for a chunk it takes (`chunk_route`);
ops/kernel_check.py compares the three paths with float32 on the chip,
the kernel with the walk.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import paged_attention as paged_ops

F32 = jnp.float32
#: Index scores are products of float32 queries and keys at full
#: precision: a TPU's default float32 product rounds both to bf16, and
#: a rounded score moves positions across the selection's boundary.
EXACT = jax.lax.Precision.HIGHEST
#: Pages of keys a prefill chunk's two loops take a step (512 keys at
#: 16-token pages: a [heads, chunk, 512] block of float32 scores).
BLOCK_PAGES = 32
#: A step of the decode round's index read: this many (row, block of
#: keys) pairs, each block this many pages (2,048 keys at 16-token
#: pages: 4 x 1 MiB of float32 keys a step; 647 us a layer at 16 live
#: rows of 48, where 8 x 64 pages took 812 and 8 x 32 1,198: my chip
#: run, PR 33).
DECODE_BLOCK_PAGES = 128
DECODE_ITEMS = 4
#: Rows a step of the decode round's attention takes (each gathers its
#: `index_topk` selected rows; 858 us a layer at 16 live rows of 48,
#: 970 with 4 and 929 with 16).
DECODE_ROWS = 8


@jax.named_scope('latent_write')
def write_rows(latent_pages: jax.Array, index_pages: Optional[jax.Array],
               latent_new: jax.Array, index_new: Optional[jax.Array],
               positions: jax.Array, page_indices: jax.Array, *,
               page_aligned: bool = False
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The chunk's rows ([B, S, width] each) into their page slots, in
    place: the K/V write's own `_write_pool`, one head wide."""
    latent_pages = paged_ops._write_pool(  # pylint: disable=protected-access
        latent_pages, latent_new[:, :, None], positions, page_indices,
        page_aligned=page_aligned)
    if index_pages is not None:
        index_pages = paged_ops._write_pool(  # pylint: disable=protected-access
            index_pages, index_new[:, :, None], positions, page_indices,
            page_aligned=page_aligned)
    return latent_pages, index_pages


def _weighted_relu(scores: jax.Array, weights: jax.Array) -> jax.Array:
    """sum_j w_j ReLU(s_j) over the head axis (1 of [.., heads, keys]),
    elementwise in float32: a float32 contraction on the MXU would
    round its operands."""
    return jnp.sum(jax.nn.relu(scores) * weights[..., None], axis=-2)


@jax.named_scope('indexer')
def index_scores_decode(q_idx: jax.Array, w_idx: jax.Array,
                        index_pages: jax.Array, page_indices: jax.Array,
                        lengths: jax.Array) -> jax.Array:
    """One query a row against the row's paged indexer keys.

    q_idx [B, Hi, Di]; w_idx f32[B, Hi]; index_pages [1, P, page, Di];
    page_indices i32[B, pages]; lengths i32[B] (the query's own
    position included; 0 for a row that holds no request). Returns
    f32[B, pages * page], -inf from `lengths` on.

    The read follows the LIVE contexts: the rows' blocks of
    `DECODE_BLOCK_PAGES` pages up to each row's length are laid end to
    end in one work list, and a loop whose trip count is that list's
    length takes `DECODE_ITEMS` of them a step. A row of length 0 and
    the pages past a row's length are never read (gathering every
    row's whole table was 4.8 ms of a 23.5 ms round at 9 live rows of
    24: my chip run, PR 33)."""
    batch, n_pages = page_indices.shape
    page = index_pages.shape[2]
    block_pages = min(DECODE_BLOCK_PAGES, n_pages)
    table = jnp.pad(page_indices, ((0, 0), (0, -n_pages % block_pages)))
    n_blocks = table.shape[1] // block_pages
    table = table.reshape(batch, n_blocks, block_pages)
    block = block_pages * page
    items = min(DECODE_ITEMS, batch * n_blocks)
    # The work list: row 0's blocks, then row 1's, ...; padded by one
    # step's items so that no slice of it is clamped.
    need = (lengths + block - 1) // block
    ends = jnp.cumsum(need)
    item = jnp.arange(batch * n_blocks + items)
    row_of = jnp.minimum(
        jnp.sum(ends[None, :] <= item[:, None], axis=1), batch - 1)
    block_of = jnp.clip(item - (ends - need)[row_of], 0, n_blocks - 1)
    # What lies past the list's end is written to a spare row.
    out_of = jnp.where(item < ends[-1], row_of, batch)

    def step(i, buf):
        take = lambda x: jax.lax.dynamic_slice(  # noqa: E731
            x, (i * items,), (items,))
        rows, blocks, outs = take(row_of), take(block_of), take(out_of)
        keys = index_pages[0][table[rows, blocks]].reshape(
            items, block, -1)
        s = jnp.einsum('ghd,gtd->ght', q_idx[rows], keys, precision=EXACT,
                       preferred_element_type=F32)
        s = _weighted_relu(s, w_idx[rows])
        for g in range(items):
            buf = jax.lax.dynamic_update_slice(
                buf, s[g][None], (outs[g], blocks[g] * block))
        return buf

    buf = jax.lax.fori_loop(
        0, (ends[-1] + items - 1) // items, step,
        jnp.full((batch + 1, n_blocks * block), -jnp.inf, F32))
    live = jnp.arange(n_pages * page)[None, :] < lengths[:, None]
    return jnp.where(live, buf[:batch, :n_pages * page], -jnp.inf)


def select_topk(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """The min(k, T) largest of each row of f32[B, T]: (positions
    i32[B, k], whether each is a real one: a row with fewer than k
    finite scores selects them all). `lax.top_k`: a sort of every row
    on the TPU, 0.53 ms for 48 rows of 16,384; `topk_mask` and a binary
    search for the j-th selected position in its running count took
    15.3 ms on the same rows (my chip run, PR 33)."""
    with jax.named_scope('indexer'), jax.named_scope('topk_select'):
        values, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
        return idx, values > -jnp.inf


def kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of f32[..., T], exactly: 32 counting
    passes that fix the answer's bits from the top, on the floats mapped
    to unsigned integers of the same order. k <= T. 0.2 ms for 48 rows
    of 16,384 (my chip run, PR 33); a prefill chunk's 512 queries need
    the k-th value only (their selection is a mask), not a sort with
    its indices."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(0x80000000)
    keys = jnp.where(bits >= top, ~bits, bits | top)

    def fix_bit(i, found):
        trial = found | (top >> i.astype(jnp.uint32))
        count = jnp.sum(keys >= trial[..., None], axis=-1)
        return jnp.where(count >= k, trial, found)

    found = jax.lax.fori_loop(0, 32, fix_bit,
                              jnp.zeros(x.shape[:-1], jnp.uint32))
    bits = jnp.where(found >= top, found & ~top, ~found)
    return jax.lax.bitcast_convert_type(bits, F32)


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """bool[..., T]: the k largest of each row, equal scores to the
    lower position first, as `lax.top_k` orders them (a row with fewer
    than k finite scores: its finite ones)."""
    bar = kth_largest(scores, min(k, scores.shape[-1]))[..., None]
    above = scores > bar
    tie = (scores == bar) & jnp.isfinite(scores)
    quota = k - jnp.sum(above, axis=-1, keepdims=True)
    rank = jnp.cumsum(tie, axis=-1) - tie
    return above | (tie & (rank < quota))


@jax.named_scope('latent_attention')
def sparse_latent_decode(q: jax.Array, latent_pages: jax.Array,
                         page_indices: jax.Array, idx: jax.Array,
                         valid: jax.Array, *, scale: float,
                         value_dim: int) -> jax.Array:
    """Absorbed attention of one query a row over its selected rows.

    q [B, H, W]: a head's query already in the cached row's terms,
    `[q_nope W_uk | q_rope]`; latent_pages [1, P, page, W]; idx i32[B, k]
    positions in the row's context, valid bool[B, k], the best first
    (`select_topk`'s order). Returns f32[B, H, value_dim]: the
    softmax-weighted sum of the rows' first `value_dim` values (c_kv;
    the caller multiplies by W_uv); zeros for a row with nothing
    selected.

    Only rows with a selection are gathered: they are taken first,
    `DECODE_ROWS` a step, by a loop whose trip count is their number."""
    batch, heads, _ = q.shape
    _, total_pages, page, width = latent_pages.shape
    flat = latent_pages[0].reshape(total_pages * page, width)
    group = min(DECODE_ROWS, batch)
    alive = valid[:, 0]
    order = jnp.argsort(~alive, stable=True)

    def step(i, out):
        # The last step's slice is clamped to the batch: it takes some
        # rows again, to the same result.
        rows = jax.lax.dynamic_slice(order, (i * group,), (group,))
        pick, ok = idx[rows], valid[rows]
        physical = jnp.take_along_axis(page_indices[rows], pick // page,
                                       axis=1)
        sel = flat.at[physical * page + pick % page].get(
            mode='promise_in_bounds')                      # [G, k, W]
        s = jnp.einsum('ghw,gkw->ghk', q[rows], sel,
                       preferred_element_type=F32) * scale
        s = jnp.where(ok[:, None, :], s, -jnp.inf)
        # A softmax that leaves a row with nothing selected at 0.
        top = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
        total = jnp.sum(p, axis=-1, keepdims=True)
        probs = p / jnp.where(total > 0, total, 1.0)
        ctx = jnp.einsum('ghk,gkc->ghc', probs.astype(sel.dtype),
                         sel[..., :value_dim], preferred_element_type=F32)
        for g in range(group):
            out = jax.lax.dynamic_update_slice(out, ctx[g][None],
                                               (rows[g], 0, 0))
        return out

    return jax.lax.fori_loop(
        0, (jnp.sum(alive) + group - 1) // group, step,
        jnp.zeros((batch, heads, value_dim), F32))


def chunk_route(q, latent_pages, pages_per_seq: int, value_dim: int,
                block_pages: int = BLOCK_PAGES) -> str:
    """The route a chunk's attention takes, `pallas_paged.resolve_impl`'s
    answer for what the read hands it: `q` [.., S, H, W] and
    `latent_pages` [1, P, page, W], arrays or their ShapeDtypeStructs
    (static shapes and the dtype only), the pages of a row's table and
    the row's summed values. The engine asks with the same four for
    /stats (`chunk_attention_impl`), so the name reported is the
    program compiled."""
    from skypilot_tpu.ops import pallas_paged
    block = min(block_pages, pages_per_seq) * latent_pages.shape[2]
    return pallas_paged.resolve_impl(
        layout='latent', latent_chunk=(q, block, value_dim))


def _whole_blocks(page_row, positions, page, block_pages):
    """(the row's table padded to whole blocks, the pages of a block,
    the blocks up to the chunk's last position)."""
    block_pages = min(block_pages, page_row.shape[0])
    page_row = jnp.pad(page_row, (0, -page_row.shape[0] % block_pages))
    return (page_row, block_pages,
            jnp.max(positions) // (block_pages * page) + 1)


def chunk_keep(q_idx, w_idx, positions, page_row, index_pages, *,
               page: int, topk: int,
               block_pages: int = BLOCK_PAGES) -> jax.Array:
    """bool[S, keys]: the keys each query of one row's chunk attends,
    over the row's table in whole blocks: the causal ones and, with an
    indexer, of those the `topk` best by index score (`topk_mask`'s
    rule, to the last tie). It is H times smaller than a block of
    scores, made once a layer, and is all of the selection that the
    attention, walk or kernel, is handed."""
    page_row, block_pages, n_blocks = _whole_blocks(
        page_row, positions, page, block_pages)
    seq, block = positions.shape[0], block_pages * page
    total = page_row.shape[0] * page
    causal = jnp.arange(total)[None, :] <= positions[:, None]
    if index_pages is None:
        return causal
    with jax.named_scope('indexer'):
        def score_block(i, buf):
            pages = jax.lax.dynamic_slice(page_row, (i * block_pages,),
                                          (block_pages,))
            keys = index_pages[0][pages].reshape(block, -1)
            s = jnp.einsum('shd,kd->shk', q_idx, keys, precision=EXACT,
                           preferred_element_type=F32)
            at = jax.lax.dynamic_slice(causal, (0, i * block),
                                       (seq, block))
            s = jnp.where(at, _weighted_relu(s, w_idx), -jnp.inf)
            return jax.lax.dynamic_update_slice(buf, s, (0, i * block))

        index_scores = jax.lax.fori_loop(
            0, n_blocks, score_block,
            jnp.full((seq, total), -jnp.inf, F32))
        with jax.named_scope('topk_select'):
            # A score past a query's position is -inf, and `topk_mask`
            # keeps finite scores only.
            return topk_mask(index_scores, topk)


def _chunk_row(q, q_idx, w_idx, positions, page_row, latent_pages,
               index_pages, *, topk, scale, value_dim, block_pages,
               kernel, interpret):
    """One row of `sparse_latent_chunk`."""
    seq, heads, _ = q.shape
    page = latent_pages.shape[2]
    page_row, block_pages, n_blocks = _whole_blocks(
        page_row, positions, page, block_pages)
    block = block_pages * page
    keep = chunk_keep(q_idx, w_idx, positions, page_row, index_pages,
                      page=page, topk=topk, block_pages=block_pages)

    with jax.named_scope('latent_attention'):
        if kernel:
            from skypilot_tpu.ops import pallas_latent
            # The row's pages once a layer, as one [keys, W] matrix
            # every head's walk reads (21 MB at 16,384 positions).
            rows = latent_pages[0][page_row].reshape(keep.shape[1], -1)
            out = pallas_latent.latent_chunk_attention(
                jnp.swapaxes(q, 0, 1), rows, keep, n_blocks, block=block,
                value_dim=value_dim, scale=scale, interpret=interpret)
            return jnp.swapaxes(out, 0, 1)

        def attend_block(i, carry):
            m, l, acc = carry
            pages = jax.lax.dynamic_slice(page_row, (i * block_pages,),
                                          (block_pages,))
            rows = latent_pages[0][pages].reshape(block, -1)
            s = jnp.einsum('shw,kw->hsk', q, rows,
                           preferred_element_type=F32) * scale
            at = jax.lax.dynamic_slice(keep, (0, i * block), (seq, block))
            s = jnp.where(at[None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # A query with nothing selected so far keeps -inf: shift by
            # 0 there, so that exp(-inf - m) stays 0 and never NaN.
            shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - shift[..., None])
            fade = jnp.exp(m - shift)
            l = l * fade + jnp.sum(p, axis=-1)
            acc = acc * fade[..., None] + jnp.einsum(
                'hsk,kc->hsc', p.astype(rows.dtype), rows[:, :value_dim],
                preferred_element_type=F32)
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, attend_block,
            (jnp.full((heads, seq), -jnp.inf, F32),
             jnp.zeros((heads, seq), F32),
             jnp.zeros((heads, seq, value_dim), F32)))
        return jnp.swapaxes(acc / l[..., None], 0, 1)


def sparse_latent_chunk(q: jax.Array, q_idx: Optional[jax.Array],
                        w_idx: Optional[jax.Array],
                        latent_pages: jax.Array,
                        index_pages: Optional[jax.Array],
                        positions: jax.Array, page_indices: jax.Array, *,
                        topk: int, scale: float, value_dim: int,
                        block_pages: int = BLOCK_PAGES,
                        route: Optional[str] = None,
                        interpret: bool = False) -> jax.Array:
    """S queries a row over the row's paged history, each over its own
    selection (the chunk's rows are already written).

    q [B, S, H, W] absorbed queries; q_idx [B, S, Hi, Di] and w_idx
    f32[B, S, Hi] (None with `index_pages` None: no selection);
    positions i32[B, S], rising within a row; page_indices
    i32[B, pages]. Returns f32[B, S, H, value_dim] as
    `sparse_latent_decode` does.

    The attention takes `chunk_route`'s route. ops/kernel_check.py and
    the tests name one (`route`) to hold the kernel to the walk, and
    run the kernel through the Pallas interpreter (`interpret`)."""
    if route is None:
        route = chunk_route(q, latent_pages, page_indices.shape[1],
                            value_dim, block_pages)
    kernel = route == 'sparse_latent_pallas'

    def row(*args):
        return _chunk_row(*args, latent_pages, index_pages, topk=topk,
                          scale=scale, value_dim=value_dim,
                          block_pages=block_pages, kernel=kernel,
                          interpret=interpret)

    args = (q, q_idx, w_idx, positions, page_indices)
    if not kernel:
        return jax.vmap(row)(*args)
    # A Pallas call is not batched: the rows take the kernel in turn
    # (the engine's chunks are one row each, and stacking one is free).
    return jnp.stack([row(*(None if a is None else a[b] for a in args))
                      for b in range(q.shape[0])])
