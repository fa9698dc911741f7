"""Paged KV-cache attention for LM serving.

The vLLM idea, TPU-native: instead of one dense [B, max_len] KV cache
per slot (allocated for the worst case), K/V live in fixed-size pages
shared by all slots; each sequence owns a page list. Total page count
is sized for the *aggregate* live tokens, so many short sequences fit
where the dense layout would exhaust HBM — more decode slots, higher
serving throughput.

On a TPU the decode read of an unquantized pool whose pages are
whole tiles a head (128-wide heads, 16-token bf16 pages) is
ops/pallas_paged.paged_decode_kernel, which fetches a page for every
KV head of the chip in one copy, multiplies all of the row's head
groups in one step and walks live rows only; every read of an int8
pool is ops/pallas_paged.fused_paged_attention. Every other read (an
unquantized pool of another shape, 64-wide heads among them; an S>1
chunk of an unquantized pool; anything on the CPU test backend) is
the pure-XLA gather + masked attention below, which compiles
everywhere. That reference also defines the semantics the kernels are
checked against on the chip (ops/kernel_check.py). The route is
chosen once, at trace time, by `pallas_paged.resolve_impl` from the
backend and the static shapes and from nothing else; nothing switches
routes at run time, and /stats names the one compiled.

A pool of 64-wide heads that a model declares PACKED (`packed_kv_layout`,
kind 'kv_packed': models/lfm2.py) keeps a token's K/V heads side by
side in one row, [1, pages, page, Hkv * D]: a page is then whole lane
tiles, so a read by page takes the pool where it lies (a [Hkv, pages,
page, 64] pool is given a page-major layout of its own by XLA:TPU, a
copy of the whole pool in and out, each layer and round). On a TPU
`packed_decode_attention` is the kernel of ops/pallas_packed.py (route
'packed_decode'), one 16 KB copy a live page of a live row, the rows
kept packed; `packed_chunk_attention`, and the decode read off a TPU
or under a mesh of several devices, gather every row's pages whole and
keep the rows packed through the products (`_packed_attention`, route
'packed_heads_xla'). Neither is ever the 128-wide decode kernel,
which would take the packed row for one head.

Layouts (matching the pallas kernel):
  q            [B, num_q_heads, head_dim]      one decode token per row
  k/v_pages    [num_kv_heads, total_pages, page_size, head_dim]
  lengths      i32[B]   tokens already in the cache (incl. current)
  page_indices i32[B, pages_per_seq]  physical page ids per sequence

Page allocation is host-side (`PageAllocator`): XLA needs static
shapes, so the device arrays are fixed-size and the allocator only
decides which physical pages a sequence uses.

TENSOR-PARALLEL POOLS (parallel/serving.py, PR 15): under a mesh the
pool's LEADING kv-heads axis is sharded over `tensor`, so each chip
holds a head-slice of every page. These ops are sharding-transparent
— the page gather indexes the pages axis (axis 1) and every
per-token compute is elementwise over heads — so GSPMD partitions
them without inserting pool-shaped collectives (asserted by the
pool_collective_lines guard). Page ids, lengths, and page tables are
replicated host-side values; the scale arrays (below) have no heads
axis and replicate.

INT8 KV PAGES (kv_dtype='int8' on the model config): the page pool
stores int8 with one f32 scale per page SLOT (i.e. per cached token,
shared across KV heads) living in a parallel scale-page array
  k/v_scales   f32[total_pages, page_size]
Quantization is symmetric absmax over that token's (Hkv, head_dim)
values, applied on every cache write (`write_kv_quant` /
`write_kv_chunk_quant`); the attention reads dequantize right after
the page gather so every matmul stays bf16/f32. Scales travel with
their physical page, so allocation, free-lists, prefix sharing and
chain keys are untouched — a shared prefix page is one int8 copy
plus its scales, refcounted exactly like a bf16 page. Per-slot
scales (rather than one scale per whole page) keep single-token
decode writes requantization-free: a write never touches another
token's already-quantized values.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization of per-token KV rows.

    x: [..., num_kv_heads, head_dim] (one leading index per cached
    token). Returns (q int8 same shape, scale f32[...]) with the
    scale taken over each token's (Hkv, D) values. An all-zero token
    quantizes to scale 0 / values 0 (dequant is exactly zero)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=(-2, -1))
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x32 / safe[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of `quantize_kv_rows`: q [..., Hkv-or-Hq, D] int8,
    scale [...] f32 broadcast over the trailing two dims."""
    return q.astype(jnp.float32) * scale[..., None, None]


@jax.named_scope('paged_attention')
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           page_indices: jax.Array,
                           *, k_scales: Optional[jax.Array] = None,
                           v_scales: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Attention of one query token per row over its paged KV history.

    Returns [B, num_q_heads, head_dim] (q.dtype). GQA: num_q_heads may
    be a multiple of num_kv_heads. `k_scales`/`v_scales`
    (f32[total_pages, page_size]) mark int8 pages.

    The route is `pallas_paged.resolve_impl`'s, at trace time and
    from the backend and the static shapes: on a TPU 'decode' (the
    in-repo kernel, ops/pallas_paged.paged_decode_kernel) for an
    unquantized pool where a page of one head is whole tiles, 'fused'
    (the in-repo kernel that dequantizes int8 pages in-register) for
    an int8 pool, and 'xla', the gather reference, for the other
    unquantized shapes (64-wide heads among them) and off a TPU. The
    reference dequantizes an int8 pool in HBM, the traffic the fused
    path deletes. Under a tensor mesh context each kernel runs per
    chip on that chip's kv-head slice of the pool
    (`shard_over_kv_heads`).
    """
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    from skypilot_tpu.ops import pallas_paged
    impl = pallas_paged.resolve_impl(quantized=k_scales is not None,
                                     decode_pool=k_pages)
    if impl == 'decode':
        return pallas_paged.paged_decode_kernel(
            q, k_pages, v_pages, lengths, page_indices)
    if impl in ('fused', 'fused_interpret'):
        out = pallas_paged.fused_paged_attention(
            q[:, None], k_pages, v_pages, (lengths - 1)[:, None],
            page_indices, k_scales=k_scales, v_scales=v_scales,
            interpret=impl == 'fused_interpret')
        return out[:, 0]
    return _reference_paged_attention(q, k_pages, v_pages, lengths,
                                      page_indices,
                                      k_scales=k_scales,
                                      v_scales=v_scales)


def _gather_kv(q_heads: int, k_pages: jax.Array, v_pages: jax.Array,
               page_indices: jax.Array,
               k_scales: Optional[jax.Array] = None,
               v_scales: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Per-row page gather + GQA head expansion: the shared read side
    of every XLA paged-attention path. Returns k/v as [B, T, Hq, D]
    where T = pages_per_seq * page_size. With scale pages the gather
    DEQUANTIZES (int8 * per-slot f32 scale) before head expansion —
    the one place quantized storage meets the math."""
    num_kv_heads, _, page_size, head_dim = k_pages.shape
    max_len = page_indices.shape[1] * page_size

    # [Hkv, pages, page, D] -> [T, Hkv, D], per row.
    def gather_row(pages, idx):
        g = pages[:, idx]                       # [Hkv, pages, page, D]
        g = jnp.swapaxes(g, 0, 1)               # [pages, Hkv, page, D]
        g = jnp.swapaxes(g, 1, 2)               # [pages, page, Hkv, D]
        return g.reshape(max_len, num_kv_heads, head_dim)

    def gather_scale_row(scales, idx):
        return scales[idx].reshape(max_len)     # [pages, page] -> [T]

    k_all = jax.vmap(gather_row, in_axes=(None, 0))(k_pages, page_indices)
    v_all = jax.vmap(gather_row, in_axes=(None, 0))(v_pages, page_indices)
    if k_scales is not None:
        k_s = jax.vmap(gather_scale_row,
                       in_axes=(None, 0))(k_scales, page_indices)
        v_s = jax.vmap(gather_scale_row,
                       in_axes=(None, 0))(v_scales, page_indices)
        k_all = k_all.astype(jnp.float32) * k_s[:, :, None, None]
        v_all = v_all.astype(jnp.float32) * v_s[:, :, None, None]
    if q_heads != num_kv_heads:
        rep = q_heads // num_kv_heads
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    return k_all, v_all


def _reference_paged_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, lengths: jax.Array,
                               page_indices: jax.Array,
                               k_scales: Optional[jax.Array] = None,
                               v_scales: Optional[jax.Array] = None
                               ) -> jax.Array:
    """Pure-XLA semantics: gather each row's pages, masked softmax."""
    head_dim = k_pages.shape[-1]
    max_len = page_indices.shape[1] * k_pages.shape[2]
    k_all, v_all = _gather_kv(q.shape[1], k_pages, v_pages,
                              page_indices, k_scales, v_scales)

    scale = 1.0 / (head_dim ** 0.5)
    s = jnp.einsum('bhd,bkhd->bhk', q.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_len)[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhk,bkhd->bhd', p, v_all.astype(jnp.float32))
    return out.astype(q.dtype)


def _placed_like_pool(x: jax.Array) -> jax.Array:
    """Under a tensor mesh, pin `x` ([Hkv, ...]: the pool, or the
    rows to write into it) to the pool's own placement: kv heads over
    `tensor` when they divide it, else replicated (parallel/serving's
    GQA remainder rule). `_write_pool` pins both ends of its update
    chain: the projections leave the new rows sharded over `tensor`
    however the heads divide, and left alone GSPMD carries THAT
    through every update and all-gathers a replicated pool back
    whole, each layer, each round. Pinned, it gathers the few rows."""
    from skypilot_tpu.ops.attention import _active_mesh
    mesh = _active_mesh()
    tensor = mesh.shape.get('tensor', 1) if mesh is not None else 1
    if tensor <= 1:
        return x
    spec = P('tensor') if x.shape[0] % tensor == 0 else P()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@functools.partial(jax.jit, static_argnames=('page_aligned',))
def _write_pool(pages: jax.Array, new: jax.Array, positions: jax.Array,
                page_indices: jax.Array, *, page_aligned: bool
                ) -> jax.Array:
    """Put new[b, s] ([B, S, Hkv, D]) at positions[b, s]'s page slot
    of `pages` ([Hkv, P, page, D]) IN PLACE: one unrolled
    `lax.dynamic_update_slice` per written page or token.

    Not `pages.at[:, physical, slot, :].set(...)`: XLA:TPU gives a
    scatter a page-major layout ({3,0,2,1}) while the program's
    parameters, results and the paged-attention custom call hold the
    pool in the default one, so every scatter is wrapped in TWO
    whole-pool layout copies (168 MB each at a 5 GiB pool of 16
    layers), donated or not. A dynamic-update-slice keeps the pool's
    own layout and aliases through: the donated pool is updated where
    it lies.

    `page_aligned` (static) is the caller's promise that every row's
    positions are consecutive and start on a page boundary (prefill
    chunks): whole pages then go in as one [Hkv, 1, page, D] update
    each, and only the sub-page remainder token by token. Without it
    (decode rows at mixed depths, speculative-verify chunks) every
    token is its own [Hkv, 1, 1, D] update. Updates apply in (row,
    position) order; rows own distinct pages and only junk ever
    collides (on the trash page), so the order is unobservable.

    Jitted on its own so that a program traces and lowers the
    unrolled updates once, not once per layer and pool (the K and V
    pools of every layer share the shapes): XLA inlines the calls.
    """
    batch, chunk = positions.shape
    page_size = pages.shape[2]
    whole = chunk // page_size if page_aligned else 0
    sizes = (page_size,) * whole + (1,) * (chunk - whole * page_size)
    new = _placed_like_pool(jnp.moveaxis(new, 2, 0))   # [Hkv, B, S, D]
    physical = jnp.take_along_axis(page_indices, positions // page_size,
                                   axis=1)
    slot = positions % page_size
    for b in range(batch):
        s = 0
        for tokens in sizes:
            # A whole page starts at its slot 0. Table pages and slots
            # are never negative, so no wrap-around ops are traced.
            at_slot = slot[b, s] if tokens == 1 else 0
            pages = jax.lax.dynamic_update_slice(
                pages, new[:, b:b + 1, s:s + tokens],
                (0, physical[b, s], at_slot, 0),
                allow_negative_indices=False)
            s += tokens
    return _placed_like_pool(pages)


def _write_scales(scales: jax.Array, new: jax.Array,
                  positions: jax.Array, page_indices: jax.Array
                  ) -> jax.Array:
    """Per-token scales ([B, S] f32) into the [P, page] scale pages.
    A scatter: the array is small (4 bytes a cached token), so
    whatever layout it is given costs nothing pool-sized."""
    page_size = scales.shape[1]
    physical = jnp.take_along_axis(page_indices, positions // page_size,
                                   axis=1)
    return scales.at[physical.reshape(-1),
                     (positions % page_size).reshape(-1)].set(
                         new.reshape(-1))


def write_kv(k_pages: jax.Array, v_pages: jax.Array, k_new: jax.Array,
             v_new: jax.Array, positions: jax.Array,
             page_indices: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """Write one token's K/V per row at its position's page slot.

    k_new/v_new: [B, num_kv_heads, head_dim]; positions: i32[B] (the
    index the token lands at, i.e. lengths - 1 after admission);
    returns updated (k_pages, v_pages). Rows write distinct physical
    pages (the allocator guarantees no sharing). In place, in the
    pool's own layout (`_write_pool`): B small updates, no pool copy.
    """
    return write_kv_chunk(k_pages, v_pages, k_new[:, None],
                          v_new[:, None], positions[:, None],
                          page_indices)


@jax.named_scope('kv_write')
def write_kv_chunk(k_pages: jax.Array, v_pages: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   positions: jax.Array, page_indices: jax.Array,
                   *, page_aligned: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Chunk write: S tokens per row, in place (`_write_pool`).

    k_new/v_new: [B, S, num_kv_heads, head_dim]; positions: i32[B, S].
    Within a row positions are distinct; padded-tail positions map to
    unallocated table entries, i.e. the trash page (duplicate writes
    there are benign). `page_aligned` (static): every row's positions
    are consecutive from a page boundary, so whole pages are written
    as pages (prefill chunks); speculative-verify chunks start
    anywhere and leave it False.
    """
    return (_write_pool(k_pages, k_new, positions, page_indices,
                        page_aligned=page_aligned),
            _write_pool(v_pages, v_new, positions, page_indices,
                        page_aligned=page_aligned))


def write_kv_quant(k_pages: jax.Array, v_pages: jax.Array,
                   k_scales: jax.Array, v_scales: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   positions: jax.Array, page_indices: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array,
                              jax.Array]:
    """`write_kv` for an int8 pool: quantize the token's K/V rows and
    write values (in place, `_write_pool`) + per-slot scales. Same
    race-freedom argument (rows own distinct physical pages;
    trash-page collisions write junk over junk)."""
    return write_kv_chunk_quant(
        k_pages, v_pages, k_scales, v_scales, k_new[:, None],
        v_new[:, None], positions[:, None], page_indices)


@jax.named_scope('kv_write')
def write_kv_chunk_quant(k_pages: jax.Array, v_pages: jax.Array,
                         k_scales: jax.Array, v_scales: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         positions: jax.Array,
                         page_indices: jax.Array,
                         *, page_aligned: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """`write_kv_chunk` for an int8 pool: S tokens per row quantized
    (one scale per (row, position) token) and written with their
    scales. Padded-tail positions land in the trash page exactly as
    the bf16 write does."""
    qk, sk = quantize_kv_rows(k_new)                       # sk: [B, S]
    qv, sv = quantize_kv_rows(v_new)
    return (_write_pool(k_pages, qk, positions, page_indices,
                        page_aligned=page_aligned),
            _write_pool(v_pages, qv, positions, page_indices,
                        page_aligned=page_aligned),
            _write_scales(k_scales, sk, positions, page_indices),
            _write_scales(v_scales, sv, positions, page_indices))


def gather_page_rows(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather whole physical pages out of a pool-shaped cache leaf
    (the export side of KV-page handoff / spill).

    Page arrays [Hkv, total_pages, page_size, D] gather along axis 1
    and come back page-major ([n, Hkv, page_size, D] — one leading
    row per page, the wire/spill layout); scale arrays
    [total_pages, page_size] gather along axis 0 ([n, page_size]).
    Pure indexing: int8 pages stay int8, bf16 stays bf16 — the
    gathered bytes ARE the pool's bytes (bit-identical round trip).
    """
    if arr.ndim == 4:
        return jnp.swapaxes(arr[:, idx], 0, 1)
    assert arr.ndim == 2, arr.shape
    return arr[idx]


def scatter_page_rows(arr: jax.Array, idx: jax.Array,
                      rows: jax.Array) -> jax.Array:
    """Inverse of `gather_page_rows`: write page-major rows back into
    a pool-shaped leaf at physical pages `idx` (the import/restore
    side). Same dtype-preserving contract."""
    if arr.ndim == 4:
        return arr.at[:, idx].set(jnp.swapaxes(rows, 0, 1))
    assert arr.ndim == 2, arr.shape
    return arr.at[idx].set(rows)


class PageAllocator:
    """Host-side free-list over the fixed physical page pool.

    Not traced: the engine calls it between steps to grow a sequence's
    page list or release a finished sequence's pages.
    """

    def __init__(self, total_pages: int, pages_per_seq: int) -> None:
        self.total_pages = total_pages
        self.pages_per_seq = pages_per_seq
        self._free: List[int] = list(range(total_pages - 1, -1, -1))
        # page 0 may be handed out like any other; rows' unused table
        # entries point at whatever page — masked out by `lengths`.

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_allocate(self, num_pages: int) -> bool:
        return len(self._free) >= num_pages

    def allocate(self, num_pages: int) -> List[int]:
        if not self.can_allocate(num_pages):
            raise MemoryError(
                f'paged KV cache exhausted: need {num_pages} pages, '
                f'{len(self._free)} free of {self.total_pages}')
        return [self._free.pop() for _ in range(num_pages)]

    def release(self, pages: List[int]) -> None:
        self._free.extend(pages)

    def pages_needed(self, num_tokens: int, page_size: int) -> int:
        return -(-num_tokens // page_size)  # ceil div


@dataclasses.dataclass(frozen=True)
class PoolArray:
    """One array of a layer's page pool: its leaf name in the model's
    `cache` collection and a cached token's row in it, `heads` rows of
    `width` values ([heads, total_pages, page_size, width]), kept in
    `dtype` (None: the model's compute dtype, or int8 with scales)."""
    name: str
    heads: int
    width: int
    dtype: Any = None


@dataclasses.dataclass(frozen=True)
class SlotArray:
    """One array of a layer's state BY SLOT: its leaf name in the
    model's `cache` collection, the shape of one slot's row and its
    dtype (None: the model's compute dtype). The array is [num_slots,
    *shape]; a sequence keeps one row of it however long it is (a
    state-space layer's recurrent state, its convolution's tail)."""
    name: str
    shape: Tuple[int, ...]
    dtype: Any = None

    def row_bytes(self, itemsize: int) -> int:
        return math.prod(self.shape) * (
            jnp.dtype(self.dtype).itemsize if self.dtype else itemsize)


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """What a model keeps in the page pool for a cached token (D3a):
    the MODEL supplies it (`config.page_layout()`), and the engine, the
    pool's sizing (`--kv-pool-bytes`), its placement and its guards
    read the arrays from here instead of spelling K and V out.

    `kind` 'kv': keys and values, [Hkv, pages, page, D] each (Llama,
    GPT-2, Mixtral); `kind` 'kv_packed': the same with a token's heads
    side by side in one row, [1, pages, page, Hkv * D] each
    (`packed_kv_layout`, LFM2's 64-wide heads). `kind` 'latent': MLA's
    compressed row, one
    [1, pages, page, kv_lora_rank + rope_head_dim (in whole lane tiles:
    models/deepseek.py `latent_width`)] array a layer, and
    beside it, where the model has a lightning indexer, that token's
    indexer key, [1, pages, page, index_head_dim]. Every array has the
    same four axes, so allocation, the page table, `_write_pool`, the
    prefix keys and `pool_copy_lines` are the same code for both.

    `layers`: how many of the model's layers keep such a row (None:
    every one). `slot_arrays` / `slot_layers`: what a SEQUENCE keeps
    beside its pages, one row a slot in each of `slot_layers` layers
    (a model with state-space layers; none for the others). The engine
    allocates them with its slots, tells the model which slot a
    prefill row belongs to and which decode lanes are live, and turns
    off what assumes that a sequence is its pages (models/batching.py
    `_refuse_slot_state`)."""
    kind: str
    arrays: Tuple[PoolArray, ...]
    page_size: int
    total_pages: int
    layers: Optional[int] = None
    slot_arrays: Tuple[SlotArray, ...] = ()
    slot_layers: int = 0

    def shape(self, array: PoolArray) -> Tuple[int, int, int, int]:
        return (array.heads, self.total_pages, self.page_size,
                array.width)

    @property
    def row_values(self) -> int:
        """Values a cached token takes in one layer."""
        return sum(a.heads * a.width for a in self.arrays)

    @staticmethod
    def array_bytes(a: PoolArray, itemsize: int) -> int:
        """Bytes a cached token takes in array `a` of one layer,
        `itemsize` being that of an array that states no dtype."""
        return a.heads * a.width * (jnp.dtype(a.dtype).itemsize
                                    if a.dtype else itemsize)

    def row_bytes(self, itemsize: int) -> int:
        """Bytes a cached token takes in one layer."""
        return sum(self.array_bytes(a, itemsize) for a in self.arrays)

    def slot_bytes(self, itemsize: int) -> int:
        """Bytes a slot's state takes over all `slot_layers` layers."""
        return self.slot_layers * sum(a.row_bytes(itemsize)
                                      for a in self.slot_arrays)

    def describe_slots(self, num_slots: int, itemsize: int
                       ) -> Dict[str, Any]:
        """The state by slot as /stats `state_pool` reports it."""
        return {'arrays': {a.name: list(a.shape)
                           for a in self.slot_arrays},
                'layers': self.slot_layers,
                'bytes_per_slot': self.slot_bytes(itemsize),
                'slots': num_slots,
                'bytes': num_slots * self.slot_bytes(itemsize)}

    def describe(self, num_layers: int, itemsize: int) -> Dict[str, Any]:
        """The row as /stats `page_pool.row_layout` reports it, over
        the `layers` that keep one (of the model's `num_layers`)."""
        num_layers = self.layers or num_layers
        return {'kind': self.kind,
                'arrays': {a.name: [a.heads, a.width]
                           for a in self.arrays},
                # A token's bytes in each array, one layer.
                'array_bytes': {a.name: self.array_bytes(a, itemsize)
                                for a in self.arrays},
                'bytes_per_token': self.row_bytes(itemsize) * num_layers}


def kv_layout(num_kv_heads: int, head_dim: int, page_size: int,
              total_pages: int) -> PageLayout:
    """The K/V layout of models/{llama,gpt,mixtral}.py."""
    return PageLayout('kv', (PoolArray('k_pages', num_kv_heads, head_dim),
                             PoolArray('v_pages', num_kv_heads, head_dim)),
                      page_size, total_pages)


def packed_kv_layout(num_kv_heads: int, head_dim: int, page_size: int,
                     total_pages: int) -> PageLayout:
    """K and V with a token's `num_kv_heads` heads side by side in one
    row of `num_kv_heads * head_dim` values (kind 'kv_packed',
    models/lfm2.py): read by `packed_decode_attention` and
    `packed_chunk_attention`, written by `write_kv_chunk` as a pool of
    one head."""
    width = num_kv_heads * head_dim
    return PageLayout('kv_packed', (PoolArray('k_pages', 1, width),
                                    PoolArray('v_pages', 1, width)),
                      page_size, total_pages)


#: Every leaf name a pool array may have: parallel/serving.py finds
#: the pool's arrays in a cache tree by these.
POOL_LEAF_NAMES = ('k_pages', 'v_pages', 'latent_pages', 'index_k_pages')
#: Every leaf name an array of state by slot may have.
SLOT_LEAF_NAMES = ('ssm_state', 'conv_state')


def _gather_packed(pages: jax.Array, page_indices: jax.Array) -> jax.Array:
    """A packed pool [1, pages, page, W] -> each row's history [B, T, W]
    (T = pages_per_seq * page): a gather of whole pages, kept packed."""
    _, _, page_size, width = pages.shape
    batch, per_row = page_indices.shape
    return pages[0][page_indices].reshape(batch, per_row * page_size,
                                          width)


def _packed_attention(q: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, bound: jax.Array,
                      page_indices: jax.Array) -> jax.Array:
    """q [B, S, Hq, D] over a packed pool: query s of row b attends every
    cache index <= bound[b, s]. The rows stay packed, [B, T, Hkv * D]:
    a query head is set into its K/V head's D lanes of a row of zeros
    (block-diagonal), so QK^T contracts all Hkv * D lanes and PV gives
    every lane, of which the head keeps its own. No pool-sized array is
    relaid into heads of D lanes (a v5e spent 6.5 of a 29 ms round on
    that for LFM2's 8 heads of 64); the products are Hkv times the
    heads' own, which a decode round's read, bound by its bytes, does
    not feel. Products of the pool's dtype accumulated in float32, the
    softmax float32. Returns [B, S, Hq, D] (q.dtype)."""
    batch, seq, q_heads, head_dim = q.shape
    k = _gather_packed(k_pages, page_indices)
    v = _gather_packed(v_pages, page_indices)
    kv_heads = k.shape[-1] // head_dim
    # own[h, w]: lane w of a packed row is query head h's K/V head's.
    own = (jnp.arange(q_heads)[:, None] // (q_heads // kv_heads)
           == jnp.arange(kv_heads * head_dim)[None, :] // head_dim)
    spread = jnp.where(own, jnp.tile(q, (1, 1, 1, kv_heads)), 0)
    s = jnp.einsum('bshw,btw->bhst', spread.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * head_dim ** -0.5
    mask = jnp.arange(k.shape[1])[None, None, :] <= bound[:, :, None]
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhst,btw->bhsw', p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = jnp.where(own[:, None], out, 0).reshape(
        batch, q_heads, seq, kv_heads, head_dim).sum(axis=3)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@jax.named_scope('paged_attention')
def packed_decode_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, lengths: jax.Array,
                            page_indices: jax.Array) -> jax.Array:
    """`paged_decode_attention` over a packed pool (`packed_kv_layout`):
    q [B, Hq, D], k/v_pages [1, pages, page, Hkv * D], lengths i32[B]
    (tokens in the cache, the current one included; 0: a dead row,
    which returns zeros). Returns [B, Hq, D] (q.dtype).

    The route is `pallas_paged.resolve_impl`'s for the layout: on a TPU
    'packed_decode', ops/pallas_packed.packed_decode_kernel, which
    copies the live pages of the live rows only; else
    'packed_heads_xla', `_packed_attention` over every row's whole
    table."""
    from skypilot_tpu.ops import pallas_paged
    impl = pallas_paged.resolve_impl(layout='kv_packed',
                                     decode_pool=k_pages)
    if impl in ('packed_decode', 'packed_decode_interpret'):
        from skypilot_tpu.ops import pallas_packed
        return pallas_packed.packed_decode_kernel(
            q, k_pages, v_pages, lengths, page_indices,
            interpret=impl == 'packed_decode_interpret')
    return packed_decode_xla(q, k_pages, v_pages, lengths, page_indices)


def packed_decode_xla(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      lengths: jax.Array, page_indices: jax.Array
                      ) -> jax.Array:
    """`packed_decode_attention`'s route 'packed_heads_xla': every row's
    whole table gathered, a row of length 0 zeros."""
    out = _packed_attention(q[:, None], k_pages, v_pages,
                            (jnp.maximum(lengths, 1) - 1)[:, None],
                            page_indices)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(q.dtype)


@jax.named_scope('chunk_attention')
def packed_chunk_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, positions: jax.Array,
                           page_indices: jax.Array) -> jax.Array:
    """`paged_chunk_attention` over a packed pool: q [B, S, Hq, D],
    query s of row b attends every cache index <= positions[b, s] (the
    chunk's own K/V already written). Returns [B, S, Hq, D] (q.dtype)."""
    return _packed_attention(q, k_pages, v_pages, positions, page_indices)


def init_pages(num_kv_heads: int, total_pages: int, page_size: int,
               head_dim: int, dtype=jnp.bfloat16
               ) -> Tuple[jax.Array, jax.Array]:
    shape = (num_kv_heads, total_pages, page_size, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


@jax.named_scope('chunk_attention')
def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, positions: jax.Array,
                          page_indices: jax.Array,
                          k_scales: Optional[jax.Array] = None,
                          v_scales: Optional[jax.Array] = None
                          ) -> jax.Array:
    """S queries per row over the row's FULL paged history.

    The paged analog of ops.attention.chunked_cache_attention's read
    side: query s of row b attends every cache index <= positions[b, s]
    — what speculative-decoding verification chunks need (the chunk's
    K/V must already be written via `write_kv_chunk`). Chunk sizes are
    small (draft_k + 1), so the gather-based XLA path (route 'xla') is
    a fine shape for an unquantized pool; on a TPU an int8 pool takes
    the fused kernel (ops/pallas_paged.py), which handles S>1 blocks
    natively and skips the HBM dequantize-materialize step.

    q: [B, S, num_q_heads, head_dim]; positions: i32[B, S].
    Returns [B, S, num_q_heads, head_dim] (q.dtype).
    """
    from skypilot_tpu.ops import pallas_paged
    impl = pallas_paged.resolve_impl(quantized=k_scales is not None)
    if impl in ('fused', 'fused_interpret'):
        return pallas_paged.fused_paged_attention(
            q, k_pages, v_pages, positions, page_indices,
            k_scales=k_scales, v_scales=v_scales,
            interpret=impl == 'fused_interpret')
    return _reference_chunk_attention(q, k_pages, v_pages, positions,
                                      page_indices, k_scales=k_scales,
                                      v_scales=v_scales)


def _reference_chunk_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, positions: jax.Array,
                               page_indices: jax.Array,
                               k_scales: Optional[jax.Array] = None,
                               v_scales: Optional[jax.Array] = None
                               ) -> jax.Array:
    """Pure-XLA semantics of the chunk read: gather each row's pages,
    softmax under each query's own causal bound."""
    head_dim = k_pages.shape[-1]
    max_len = page_indices.shape[1] * k_pages.shape[2]
    k_all, v_all = _gather_kv(q.shape[2], k_pages, v_pages,
                              page_indices, k_scales, v_scales)

    scale = 1.0 / (head_dim ** 0.5)
    s = jnp.einsum('bshd,bthd->bhst', q.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_len)[None, None, :]
            <= positions[:, :, None])[:, None]              # [B,1,S,T]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhst,bthd->bshd', p, v_all.astype(jnp.float32))
    return out.astype(q.dtype)
