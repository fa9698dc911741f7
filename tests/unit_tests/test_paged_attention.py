"""Paged KV-cache attention: reference semantics vs dense attention.

The pallas kernel is TPU-only; on CPU the XLA reference defines the
semantics. These tests prove the paged layout (scattered pages, page
tables, per-row lengths) computes EXACTLY what dense causal decode
attention computes, including GQA and non-contiguous page assignment.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import paged_attention as pa

PAGE = 8
PAGES_PER_SEQ = 4
TOTAL_PAGES = 32
HKV, HQ, D = 2, 4, 16


def _dense_reference(q, k_hist, v_hist, lengths):
    """q: [B,H,D]; k/v_hist: [B,T,Hkv,D] (valid up to lengths[b])."""
    rep = q.shape[1] // k_hist.shape[2]
    k = jnp.repeat(k_hist, rep, axis=2)
    v = jnp.repeat(v_hist, rep, axis=2)
    s = jnp.einsum('bhd,bkhd->bhk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    mask = (jnp.arange(k.shape[1])[None, :] < lengths[:, None])[:, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhk,bkhd->bhd', p, v.astype(jnp.float32))


def _build_paged(k_hist, v_hist, lengths, rng):
    """Scatter dense history into RANDOMLY-ordered physical pages."""
    batch, max_len = k_hist.shape[0], k_hist.shape[1]
    assert max_len == PAGES_PER_SEQ * PAGE
    perm = np.asarray(rng.permutation(TOTAL_PAGES))
    page_indices = perm[:batch * PAGES_PER_SEQ].reshape(
        batch, PAGES_PER_SEQ)
    k_pages = np.zeros((HKV, TOTAL_PAGES, PAGE, D), np.float32)
    v_pages = np.zeros((HKV, TOTAL_PAGES, PAGE, D), np.float32)
    for b in range(batch):
        for t in range(int(lengths[b])):
            phys = page_indices[b, t // PAGE]
            k_pages[:, phys, t % PAGE] = np.asarray(k_hist[b, t])
            v_pages[:, phys, t % PAGE] = np.asarray(v_hist[b, t])
    return (jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(page_indices, jnp.int32))


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_paged_matches_dense_varied_lengths():
    batch, max_len = 4, PAGES_PER_SEQ * PAGE
    q = _rand((batch, HQ, D), 0)
    k_hist = _rand((batch, max_len, HKV, D), 1)
    v_hist = _rand((batch, max_len, HKV, D), 2)
    lengths = jnp.asarray([1, 7, 20, 32], jnp.int32)  # cross-page mix
    rng = np.random.default_rng(0)
    k_pages, v_pages, page_indices = _build_paged(k_hist, v_hist,
                                                  lengths, rng)
    out = pa.paged_decode_attention(q, k_pages, v_pages, lengths,
                                    page_indices)
    ref = _dense_reference(q, k_hist, v_hist, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_write_kv_then_attend_matches_dense_decode():
    """Simulate real decode: write_kv each step, attend, compare with
    the dense cached_decode path at every step."""
    batch = 3
    k_pages, v_pages = pa.init_pages(HKV, TOTAL_PAGES, PAGE, D,
                                     jnp.float32)
    alloc = pa.PageAllocator(TOTAL_PAGES, PAGES_PER_SEQ)
    page_indices = np.zeros((batch, PAGES_PER_SEQ), np.int32)
    owned = []
    for b in range(batch):
        pages = alloc.allocate(PAGES_PER_SEQ)
        owned.append(pages)
        page_indices[b] = pages
    page_indices = jnp.asarray(page_indices)

    steps = 2 * PAGE + 3  # crosses two page boundaries
    max_len = PAGES_PER_SEQ * PAGE
    k_hist = np.zeros((batch, max_len, HKV, D), np.float32)
    v_hist = np.zeros((batch, max_len, HKV, D), np.float32)
    for t in range(steps):
        q = _rand((batch, HQ, D), 100 + t)
        k_new = _rand((batch, HKV, D), 200 + t)
        v_new = _rand((batch, HKV, D), 300 + t)
        positions = jnp.full((batch,), t, jnp.int32)
        k_pages, v_pages = pa.write_kv(k_pages, v_pages, k_new, v_new,
                                       positions, page_indices)
        k_hist[:, t] = np.asarray(k_new)
        v_hist[:, t] = np.asarray(v_new)
        lengths = jnp.full((batch,), t + 1, jnp.int32)
        out = pa.paged_decode_attention(q, k_pages, v_pages, lengths,
                                        page_indices)
        ref = _dense_reference(q, jnp.asarray(k_hist),
                               jnp.asarray(v_hist), lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, err_msg=f'step {t}')


def test_rows_at_different_depths():
    """Continuous batching: rows write at DIFFERENT positions in one
    step (the per-row positions contract)."""
    batch = 2
    k_pages, v_pages = pa.init_pages(HKV, TOTAL_PAGES, PAGE, D,
                                     jnp.float32)
    page_indices = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    positions = jnp.asarray([2, PAGE + 1], jnp.int32)  # different pages
    k_new = _rand((batch, HKV, D), 1)
    v_new = _rand((batch, HKV, D), 2)
    k_pages, v_pages = pa.write_kv(k_pages, v_pages, k_new, v_new,
                                   positions, page_indices)
    # Row 0's token landed in physical page 0 slot 2:
    np.testing.assert_allclose(np.asarray(k_pages[:, 0, 2]),
                               np.asarray(k_new[0]), atol=0)
    # Row 1's token landed in physical page 5 slot 1:
    np.testing.assert_allclose(np.asarray(k_pages[:, 5, 1]),
                               np.asarray(k_new[1]), atol=0)


def _scatter_reference(pool, new, positions, table):
    """The old scatter's semantics in plain NumPy: token (b, s) lands
    at pool[:, table[b, pos // page], pos % page, :], applied in
    (row, position) order (last write wins where junk collides on
    the trash page)."""
    pool = np.array(pool)
    page = pool.shape[2]
    for b in range(positions.shape[0]):
        for s in range(positions.shape[1]):
            pos = int(positions[b, s])
            pool[:, table[b, pos // page], pos % page, :] = new[b, s]
    return pool


#: name -> (positions [B, S], page table [B, PAGES_PER_SEQ],
#: page_aligned). Page 0 is the trash page (unallocated entries).
_WRITE_CASES = {
    # One token a row: first slot, last slot of a page, first slot of
    # the next page, deep in the last page.
    'decode_rows_mixed_depths': (
        np.asarray([[0], [PAGE - 1], [PAGE], [4 * PAGE - 3]]),
        np.asarray([[3, 9, 1, 7], [12, 4, 30, 2], [5, 6, 8, 10],
                    [20, 21, 22, 23]]), False),
    # A prefill chunk of three pages starting at page 1 of the row:
    # one real page, then a padded tail through two unallocated
    # table entries, i.e. twice into the trash page.
    'aligned_chunk_padded_tail': (
        PAGE + np.arange(3 * PAGE)[None, :],
        np.asarray([[11, 17, 0, 0]]), True),
    # Aligned start, but shorter than a page: token by token.
    'aligned_chunk_shorter_than_page': (
        2 * PAGE + np.arange(PAGE // 2)[None, :],
        np.asarray([[11, 17, 13, 0]]), True),
    # Aligned start, a page and a half: one page, then four tokens.
    'aligned_chunk_page_and_remainder': (
        np.arange(PAGE + PAGE // 2)[None, :],
        np.asarray([[25, 26, 0, 0]]), True),
    # Speculative-verify shape [B, k+1]: rows start anywhere and
    # cross page boundaries mid-chunk.
    'unaligned_verify_chunk': (
        np.asarray([[5], [PAGE - 2], [2 * PAGE - 1]])
        + np.arange(5)[None, :],
        np.asarray([[3, 9, 1, 7], [12, 4, 30, 2], [5, 6, 8, 10]]),
        False),
}


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('case', sorted(_WRITE_CASES))
def test_write_is_bit_identical_to_scatter_reference(case, dtype):
    """The in-place write (dynamic-update-slices, by page where the
    caller promises alignment) leaves the pool holding exactly the
    bytes the scatter form put there, everywhere in the pool."""
    positions, table, aligned = _WRITE_CASES[case]
    batch, chunk = positions.shape
    k_pool = _rand((HKV, TOTAL_PAGES, PAGE, D), 10).astype(dtype)
    v_pool = _rand((HKV, TOTAL_PAGES, PAGE, D), 11).astype(dtype)
    k_new = _rand((batch, chunk, HKV, D), 12).astype(dtype)
    v_new = _rand((batch, chunk, HKV, D), 13).astype(dtype)
    pos, tbl = jnp.asarray(positions, jnp.int32), jnp.asarray(
        table, jnp.int32)
    if chunk == 1:
        write = jax.jit(pa.write_kv)
        k_out, v_out = write(k_pool, v_pool, k_new[:, 0], v_new[:, 0],
                             pos[:, 0], tbl)
    else:
        write = jax.jit(functools.partial(pa.write_kv_chunk,
                                          page_aligned=aligned))
        k_out, v_out = write(k_pool, v_pool, k_new, v_new, pos, tbl)
    for out, pool, new in ((k_out, k_pool, k_new),
                           (v_out, v_pool, v_new)):
        assert out.dtype == dtype
        want = _scatter_reference(np.asarray(pool), np.asarray(new),
                                  positions, table)
        np.testing.assert_array_equal(np.asarray(out), want)


def test_unaligned_chunk_must_not_claim_alignment():
    """What `page_aligned` promises: the same unaligned chunk written
    page-wise lands on the wrong slots, so the flag is the caller's
    statement and never a default."""
    positions = 3 + np.arange(PAGE)[None, :]
    table = np.asarray([[4, 5, 6, 7]])
    pool = np.zeros((HKV, TOTAL_PAGES, PAGE, D), np.float32)
    new = np.asarray(_rand((1, PAGE, HKV, D), 14))
    want = _scatter_reference(pool, new, positions, table)
    args = (jnp.asarray(pool), jnp.asarray(pool), jnp.asarray(new),
            jnp.asarray(new), jnp.asarray(positions, jnp.int32),
            jnp.asarray(table, jnp.int32))
    honest, _ = pa.write_kv_chunk(*args)
    np.testing.assert_array_equal(np.asarray(honest), want)
    wrong, _ = pa.write_kv_chunk(*args, page_aligned=True)
    assert not np.array_equal(np.asarray(wrong), want)


def test_allocator_lifecycle():
    alloc = pa.PageAllocator(total_pages=8, pages_per_seq=4)
    a = alloc.allocate(3)
    b = alloc.allocate(5)
    assert sorted(a + b) == list(range(8))
    assert not alloc.can_allocate(1)
    try:
        alloc.allocate(1)
        raise AssertionError('expected MemoryError')
    except MemoryError:
        pass
    alloc.release(a)
    assert alloc.free_pages == 3
    assert alloc.pages_needed(17, PAGE) == 3
    assert alloc.pages_needed(16, PAGE) == 2
    assert alloc.pages_needed(1, PAGE) == 1
