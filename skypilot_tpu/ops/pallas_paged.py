"""In-repo Pallas paged-attention + LoRA kernels and the one function
that says which of them a paged read takes.

WHY. Decode is memory-bound: per-chip tokens/s is HBM bytes/token or
nothing. The XLA gather route DEQUANTIZES an int8 KV pool IN HBM: it
materializes f32 copies of every gathered page (then GQA-expands
them) each step; per-slot LoRA likewise paid one batched
gather+matmul chain per projection. JAX's own pallas paged-attention
call is not used for the unquantized pool: it is bound by the count
of its copies, not by their bytes, a 4 KB copy and a wait a page AND
head (940 us against `paged_decode_kernel`'s 231 us on the same
inputs on a v5e; PERF.md, PR 30). The kernels here close these gaps:

  paged_decode_kernel     the S=1 decode read of an unquantized pool
                          (route 'decode'). One invocation walks the
                          (row, block) pairs with block * tokens <
                          length, so a row of length 0 costs no copy
                          and no product; a block's pages arrive as
                          ONE strided copy a page covering every KV
                          head the chip holds (`pool.at[:, page]`),
                          started only for the row's live pages and
                          double-buffered across blocks and rows; a
                          step multiplies all of the row's head groups
                          against the block, operands as stored,
                          scores / softmax state / accumulator in f32.
                          The pool stays [Hkv, pages, page, D] in HBM:
                          no layout change, no pool-shaped copy. The
                          block is sized from the static shapes
                          against `_DECODE_VMEM_BUDGET`.
  fused_paged_attention   reads int8 k/v pages plus their parallel
                          f32 scale rows straight from the pool and
                          dequantizes IN-REGISTER inside the kernel
                          body — HBM sees only the int8 bytes and the
                          scales, never a dequantized page. One grid
                          (batch, kv_heads, pages_per_seq) walks each
                          row's page table via scalar prefetch; online
                          softmax accumulates across the page walk in
                          VMEM scratch. Handles bf16 pools too, and
                          both block shapes the engine issues: S=1
                          decode and S>1 chunked prefill / speculative
                          verification chunks (`positions[b, s]` is the
                          per-query causal bound, exactly the XLA
                          reference's mask).
  fused_qkv_lora_delta    ONE pallas dispatch for the wq/wk/wv LoRA
                          deltas of a multi-tenant batch: adapter ids
                          ride scalar prefetch, each row's a/b factors
                          are gathered by BlockSpec index_maps, and the
                          three (x @ a) @ b chains run in one kernel
                          body instead of three separate gather+matmul
                          dispatches per layer.

DISPATCH. `resolve_impl(quantized=..., decode_pool=...)` names the
route of a read from what the code observes, the backend and the
pool's static shape, and from nothing else: on a TPU an int8 pool ->
'fused'; the one-token decode read of an unquantized pool that
`decode_kernel_refusal` takes -> 'decode'; every other unquantized
read (a refused shape such as 64-wide heads, every S>1 chunk) ->
'xla', the gather; off a TPU (the CPU test backend) -> 'xla'. A
latent pool (`layout='latent'`, ops/sparse_latent.py) -> plain XLA,
'sparse_latent_xla', but for a prefill chunk's attention on a TPU
whose static shapes `pallas_latent.chunk_kernel_refusal` takes ->
'sparse_latent_pallas', the kernel of ops/pallas_latent.py. A pool
of packed heads (`layout='kv_packed'`) -> 'packed_decode', the
kernel of ops/pallas_packed.py, for a one-token decode read on a TPU
whose pool `pallas_packed.kernel_refusal` takes, outside a mesh of
several devices; else 'packed_heads_xla', the gather kept packed. No
argument, setter or environment variable selects a route; whoever
wants one kernel by name calls that kernel. The one override is
`impl_scope`, the tests' way to run the engine through the
interpreter on a CPU; a route forced through it that cannot run here
is a ValueError, never a silent switch to another route.
`unavailable_reason()` says WHY the compiled kernel path is off so
/stats and test skip messages can say so.

INTERPRET-MODE CONTRACT. Every pallas_call here takes
`interpret=<kwarg>` (enforced repo-wide by `stpu check` rule SKY006),
so the kernels run on CPU (`fused_paged_attention(...,
interpret=True)`, or a whole engine under
`impl_scope('fused_interpret')`; `paged_decode_kernel(...,
interpret=True)`, whose DMAs take the TPU interpreter) —
bit-tolerance pinned against the XLA reference in
tests/unit_tests/test_pallas_paged.py, with a deliberately perturbed
kernel (the `perturb` hooks below) proving the pins are non-vacuous.

SHARDING. Under an active `with mesh:` context the attention wrappers
shard_map over the PR 15 pool layout: kv-heads (and the grouped q
heads) ride `tensor` when divisible, everything else replicates; the
GQA-remainder rule (kv-heads not divisible by tensor -> replicated
pool) falls out as the unsharded call. Without a mesh context (the
GSPMD-propagation serving path) the call runs as a single program —
correct everywhere, though GSPMD treats it as an opaque replicated
region, so sharded-pool TPU deployments trace their forwards under
the mesh context.

ROOFLINE. `bytes_per_token_model()` is the analytic HBM-traffic model
(pool reads + scale rows + XLA dequant materialization + amortized
weight reads + LoRA factor rows) that benchmarks/serve_bench.py emits
next to achieved tokens/s, scoring runs as a fraction of the modeled
HBM limit rather than vs yesterday's number.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.ops import pallas_latent

#: The routes a paged read can take: 'xla' is the gather reference
#: (compiles everywhere); 'decode' this module's decode read of an
#: unquantized pool (`paged_decode_kernel`); 'fused' this module's
#: int8-capable kernel; 'fused_interpret' the same kernel in pallas
#: interpret mode (runs anywhere, CPU included; `impl_scope` only);
#: 'packed_decode' the decode read of a packed pool (ops/pallas_packed
#: .packed_decode_kernel) and 'packed_decode_interpret' the same in
#: interpret mode (`impl_scope` only).
IMPLS: Tuple[str, ...] = ('xla', 'decode', 'fused', 'fused_interpret',
                          'packed_decode', 'packed_decode_interpret')

#: The routes Mosaic compiles: a TPU backend only.
_COMPILED = ('decode', 'fused', 'packed_decode')
#: The routes that read a packed pool, and nothing else.
_PACKED = ('packed_decode', 'packed_decode_interpret')

# -- availability -----------------------------------------------------------
def available() -> bool:
    """True when the COMPILED kernel routes ('decode', 'fused') can
    run here: Mosaic compiles for a TPU backend only."""
    return jax.default_backend() == 'tpu'


def unavailable_reason() -> Optional[str]:
    """None when `available()`; otherwise why the compiled kernel path
    is off — surfaced in /stats' storage section and test skips."""
    backend = jax.default_backend()
    if backend != 'tpu':
        return (f"backend is {backend!r}: the fused kernel compiles on "
                f"TPU only (`impl_scope('fused_interpret')` still runs "
                f"here)")
    return None


# -- impl selection ---------------------------------------------------------
_scoped_impl: Optional[str] = None


@contextlib.contextmanager
def impl_scope(impl: str):
    """The tests' one override: every paged read traced inside the
    scope takes route `impl`, whatever the backend and the pool. It is
    how the CPU tests run the engine through the Pallas interpreter
    ('fused_interpret'). Nothing outside tests/ enters it. Dispatch
    resolves at trace time, so enter it BEFORE the first traced
    forward pass: warm jit caches do not retrace."""
    if impl not in IMPLS:
        raise ValueError(
            f'unknown paged-attention impl {impl!r} (choices: '
            f'{", ".join(IMPLS)})')
    global _scoped_impl
    prev, _scoped_impl = _scoped_impl, impl
    try:
        yield
    finally:
        _scoped_impl = prev


def resolve_impl(*, quantized: bool = False,
                 decode_pool: Any = None, layout: str = 'kv',
                 latent_chunk: Any = None) -> str:
    """The route of one paged read: 'xla' | 'decode' | 'fused' |
    'sparse_latent_xla' | 'sparse_latent_pallas' | 'packed_decode' |
    'packed_heads_xla' (or, inside an `impl_scope`, the route the
    scope names).

    A pool whose model's page layout is 'latent' (MLA's compressed
    rows, ops/paged_attention.PageLayout) is read by
    ops/sparse_latent.py: the index-score read, the selection and
    attention over the selected rows, as plain XLA on every backend,
    'sparse_latent_xla'. One of its reads has a kernel: on a TPU a
    prefill chunk's attention (`latent_chunk`: the chunk's queries,
    the keys a step and the row's summed values, which only that read
    passes) whose static shapes `pallas_latent.chunk_kernel_refusal`
    takes is 'sparse_latent_pallas'; a refused shape (a row or a
    `value_dim` that is no whole number of lane tiles, a chunk of
    fewer queries than a sublane tile) keeps the walk. The rest is
    about K/V pools.

    Decided by what the code can observe and by nothing else: off a
    TPU (the CPU test backend) the XLA gather; on a TPU the fused
    kernel for an int8 pool and, for an unquantized one, this module's
    decode kernel where `decode_pool` (the K pool, an array or its
    ShapeDtypeStruct: only the one-token decode read passes it) has a
    static shape the kernel takes (`decode_kernel_refusal`), else the
    XLA gather: a refused shape (64-wide heads) and every S>1 chunk.
    A route forced through `impl_scope` that cannot run here raises —
    it never degrades to another route, so what /stats reports is what
    was compiled.

    A pool whose layout is 'kv_packed' (a page keeps a token's K/V
    heads side by side in one row: 64-wide heads, models/lfm2.py) never
    reaches this module's decode kernel, which would take the packed
    row for one head. Its one-token decode read (`decode_pool` given)
    is, on a TPU, outside a mesh of more than one device (the packed
    row is one 'head' and cannot be split over `tensor`; a Pallas call
    is opaque to GSPMD) and for a pool `pallas_packed.kernel_refusal`
    takes, the kernel of ops/pallas_packed.py, 'packed_decode', which
    walks the live pages of the live rows; else, and for every S>1
    chunk, ops/paged_attention._packed_attention, a gather of whole
    pages kept packed: 'packed_heads_xla'. A scope naming a packed
    route applies to a packed pool's decode read only, and is an
    error on any other read."""
    if layout == 'kv_packed':
        if quantized:
            raise ValueError('a packed K/V page pool has no int8 form')
        return _packed_route(decode_pool)
    if layout == 'latent':
        if quantized:
            raise ValueError('a latent page pool has no int8 form')
        if (latent_chunk is not None and available()
                and pallas_latent.chunk_kernel_refusal(
                    *latent_chunk) is None):
            return 'sparse_latent_pallas'
        return 'sparse_latent_xla'
    impl = _scoped_impl
    if impl is not None:
        if impl in _PACKED:
            raise ValueError(
                f'paged-attention impl {impl!r} reads packed pools only')
        if impl == 'decode' and quantized:
            raise ValueError(
                "paged-attention impl 'decode' reads unquantized pools "
                "only; an int8 pool needs 'fused'")
        if impl in _COMPILED and not available():
            raise ValueError(
                f'paged-attention impl {impl!r} was selected but '
                f'cannot run: {unavailable_reason()}')
        return impl
    if not available():
        return 'xla'
    if quantized:
        return 'fused'
    if (decode_pool is not None
            and decode_kernel_refusal(decode_pool) is None):
        return 'decode'
    return 'xla'


def _packed_route(decode_pool: Any) -> str:
    """`resolve_impl` for a packed pool (see there)."""
    from skypilot_tpu.ops import pallas_packed
    from skypilot_tpu.ops.attention import _active_mesh
    if decode_pool is None:
        return 'packed_heads_xla'
    impl = _scoped_impl
    if impl in _PACKED:
        if impl in _COMPILED and not available():
            raise ValueError(
                f'paged-attention impl {impl!r} was selected but '
                f'cannot run: {unavailable_reason()}')
        return impl
    mesh = _active_mesh()
    if (available() and (mesh is None or mesh.size <= 1)
            and pallas_packed.kernel_refusal(decode_pool) is None):
        return 'packed_decode'
    return 'packed_heads_xla'


def lora_fusion_impl(quantized: bool = False) -> Optional[str]:
    """'fused' / 'fused_interpret' when the QKV LoRA fusion should
    engage beside the attention route of such a pool, else None
    (models call this at trace time next to the attention dispatch)."""
    impl = resolve_impl(quantized=quantized)
    return impl if impl in ('fused', 'fused_interpret') else None


# -- fused paged attention --------------------------------------------------
#: Query rows per kv head are padded to a multiple of this before the
#: kernel: 16 is the bf16 sublane tile (8 for f32), so the q/out
#: blocks and the (rows, 1) softmax scratch are whole tiles whatever
#: the GQA group (Llama-3: 4) or chunk length.
_ROW_ALIGN = 16


def _attention_kernel(quantized, sm_scale, page_size, pages_per_seq,
                      perturb, tbl_ref, q_ref, pos_ref, k_ref, v_ref,
                      *rest):
    """Grid (batch, kv_heads, pages_per_seq): one physical page of one
    kv head per step, online-softmax state in VMEM scratch.

    Refs (blocks; squeezed dims dropped): q/o [rows, D] — the kv
    head's grouped queries, row = s * group + g; pos i32[rows, 1] —
    each row's causal bound; k/v [page, D]; int8 pools add k/v scale
    rows [1, page]."""
    import jax.experimental.pallas as pl
    del tbl_ref  # consumed by the index maps
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32)          # [rows, D]
    k = k_ref[...].astype(jnp.float32)          # [page, D]
    v = v_ref[...].astype(jnp.float32)
    # q @ k^T as a 2-D NT contraction (Mosaic has no 3-D einsum).
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale  # [rows, page]
    if quantized:
        # In-register dequant, folded into the scores: the page's f32
        # per-slot scale row [1, page] broadcasts over the query rows
        # (q . (ks_t * k_t) == ks_t * (q . k_t)). No dequantized page
        # ever exists, in HBM or in VMEM.
        s = s * ks_ref[...]
    if perturb:
        # Non-vacuity hook: a deliberately wrong kernel for tests to
        # prove the parity pins actually bite. Scores are SCALED (a
        # temperature error) — an additive constant would be invisible
        # under softmax's shift invariance.
        s = s * (1.0 + perturb)
    t_idx = (p * page_size +
             jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    s = jnp.where(t_idx <= pos_ref[...], s, -jnp.inf)

    m_prev = m_ref[...]                         # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # All-masked rows keep m == -inf; shifting by 0 there keeps every
    # exp() argument finite-or--inf (exp(-inf) == 0, never a nan).
    m_safe = jnp.where(m_new > -jnp.inf, m_new, 0.0)
    alpha = jnp.exp(m_prev - m_safe)
    w = jnp.exp(s - m_safe)                     # [rows, page]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=-1, keepdims=True)
    if quantized:
        w = w * vs_ref[...]                     # same fold, v side
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        w, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == pages_per_seq - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l > 0, l, 1.0)            # fully-masked rows -> 0
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _fused_call(q, k_pages, v_pages, positions, page_indices,
                k_scales, v_scales, *, interpret, perturb):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, chunk, num_q_heads, head_dim = q.shape
    num_kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = page_indices.shape[1]
    group = num_q_heads // num_kv_heads
    quantized = k_scales is not None
    sm_scale = 1.0 / (head_dim ** 0.5)
    kernel = functools.partial(_attention_kernel, quantized, sm_scale,
                               page_size, pages_per_seq, perturb)

    # One kv head's grouped queries as contiguous rows: [B, S, Hq, D]
    # -> [B, Hkv, S*G (padded), D], so the block's last two dims are
    # the array's own (Mosaic refuses a (group, D) block cut out of a
    # 32-head axis). Padded rows carry position -1: fully masked, they
    # produce zeros that the unpad drops.
    rows = chunk * group
    rows_pad = -(-rows // _ROW_ALIGN) * _ROW_ALIGN
    q_rows = jnp.transpose(
        q.reshape(batch, chunk, num_kv_heads, group, head_dim),
        (0, 2, 1, 3, 4)).reshape(batch, num_kv_heads, rows, head_dim)
    pos_rows = jnp.repeat(positions.astype(jnp.int32), group, axis=1)
    if rows_pad != rows:
        q_rows = jnp.pad(
            q_rows, ((0, 0), (0, 0), (0, rows_pad - rows), (0, 0)))
        pos_rows = jnp.pad(pos_rows, ((0, 0), (0, rows_pad - rows)),
                           constant_values=-1)
    pos_rows = pos_rows[:, :, None]             # [B, rows, 1]

    # Index maps see the scalar-prefetch page table: the page walk
    # gathers SCATTERED physical pages into VMEM blocks.
    def q_map(b, h, p, tbl):
        return (b, h, 0, 0)

    def pos_map(b, h, p, tbl):
        return (b, 0, 0)

    def kv_map(b, h, p, tbl):
        return (h, tbl[b, p], 0, 0)

    def scale_map(b, h, p, tbl):
        return (tbl[b, p], 0, 0)

    q_spec = pl.BlockSpec((None, None, rows_pad, head_dim), q_map)
    in_specs = [
        q_spec,
        pl.BlockSpec((None, rows_pad, 1), pos_map),
        pl.BlockSpec((None, None, page_size, head_dim), kv_map),
        pl.BlockSpec((None, None, page_size, head_dim), kv_map),
    ]
    operands = [q_rows, pos_rows, k_pages, v_pages]
    if quantized:
        # Scale rows as [total_pages, 1, page_size] (a free reshape):
        # the (1, page) block is then the array's own last two dims.
        in_specs += [pl.BlockSpec((None, 1, page_size), scale_map),
                     pl.BlockSpec((None, 1, page_size), scale_map)]
        operands += [k_scales[:, None, :], v_scales[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, num_kv_heads, pages_per_seq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, head_dim), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
        name='fused_paged_attention',
    )(page_indices, *operands)
    out = out[:, :, :rows].reshape(batch, num_kv_heads, chunk, group,
                                   head_dim)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(q.shape)


def fused_paged_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, positions: jax.Array,
                          page_indices: jax.Array, *,
                          k_scales: Optional[jax.Array] = None,
                          v_scales: Optional[jax.Array] = None,
                          interpret: bool = False,
                          perturb: float = 0.0) -> jax.Array:
    """Fused paged attention over int8 or bf16 pools.

    q: [B, S, Hq, D]; positions: i32[B, S] — query s of row b attends
    every cache index <= positions[b, s] (decode is S=1 with
    positions = lengths - 1; chunks pass their absolute positions).
    k/v_pages: [Hkv, total_pages, page_size, D]; k/v_scales
    (f32[total_pages, page_size]) mark an int8 pool and are
    dequantized in-register. Returns [B, S, Hq, D] in q.dtype,
    matching `_reference_paged_attention` semantics.

    Under an active mesh context with a divisible kv-heads axis the
    call shard_maps over `tensor` (pool sharded, tables/scales
    replicated); otherwise — including the PR 15 GQA-remainder
    replicated-pool layout — it runs unsharded.
    """
    assert q.ndim == 4 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    num_kv_heads = k_pages.shape[0]
    assert q.shape[2] % num_kv_heads == 0, (q.shape, k_pages.shape)
    call = functools.partial(_fused_call, interpret=interpret,
                             perturb=perturb)
    from jax.sharding import PartitionSpec as P
    qspec = P(None, None, 'tensor', None)       # grouped q heads
    pool = P('tensor', None, None, None)        # kv-heads axis
    rep = P(None, None)
    if k_scales is None:
        fn = lambda q_, kp, vp, pos, tbl: call(q_, kp, vp, pos, tbl,
                                               None, None)
        in_specs = (qspec, pool, pool, rep, rep)
        args = (q, k_pages, v_pages, positions, page_indices)
    else:
        fn = call
        in_specs = (qspec, pool, pool, rep, rep, rep, rep)
        args = (q, k_pages, v_pages, positions, page_indices,
                k_scales, v_scales)
    return shard_over_kv_heads(fn, num_kv_heads, in_specs=in_specs,
                               out_specs=qspec)(*args)


# -- bf16 decode read: one copy a page, all heads ---------------------------
#: VMEM the decode kernel's page buffers may take: two pools (K, V)
#: times two slots (the block being multiplied and the one in flight)
#: times the block; a quarter of a v5e's 16 MiB default scoped limit.
#: At 8 bf16 heads of 128 it gives 32 pages (512 tokens) a step. A
#: step multiplies its whole block whatever part of it is live, so a
#: larger block pays in a row's tail and a smaller one in steps: on a
#: v5e, 32 rows of chat-length contexts, a half of this budget ran 7%
#: slower, a quarter 22% and twice 25% (my chip run, PR 30).
_DECODE_VMEM_BUDGET = 4 << 20
#: Sublane tile by itemsize: a page must be whole tiles for the
#: [pages, page, D] -> [tokens, D] view of a block to be free.
_SUBLANES = {4: 8, 2: 16}


def decode_kernel_refusal(pool: Any) -> Optional[str]:
    """Why `paged_decode_kernel` does not take `pool` (a K or V pool
    [Hkv, pages, page, D], an array or its ShapeDtypeStruct: only the
    static shape is read; that shape takes the XLA gather), or None
    when it does. A page of one head must be whole (sublane, 128-lane)
    tiles: the kernel views a block's pages as one [tokens, D]
    matrix."""
    _, _, page_size, head_dim = pool.shape
    dtype = jnp.dtype(pool.dtype)
    if dtype.itemsize not in _SUBLANES or not jnp.issubdtype(
            dtype, jnp.floating):
        return f'pool dtype {dtype.name} is not bf16/f16/f32'
    if head_dim % 128 != 0:
        return f'head_dim {head_dim} is not a multiple of 128 lanes'
    if page_size % _SUBLANES[dtype.itemsize] != 0:
        return (f'page_size {page_size} is not a multiple of the '
                f'{_SUBLANES[dtype.itemsize]}-sublane tile of a '
                f'{dtype.itemsize}-byte dtype')
    return None


def decode_block_pages(pool: Any, pages_per_seq: int) -> int:
    """Pages a step of the decode kernel multiplies, from the static
    shapes alone (`pool`: the K pool as the chip holds it): the
    largest power of two whose four buffers fit `_DECODE_VMEM_BUDGET`,
    at most the row's table."""
    num_kv_heads, _, page_size, head_dim = pool.shape
    page_bytes = (num_kv_heads * page_size * head_dim
                  * jnp.dtype(pool.dtype).itemsize)
    fit = max(1, _DECODE_VMEM_BUDGET // (4 * page_bytes))
    pages = 1
    while pages * 2 <= min(fit, pages_per_seq):
        pages *= 2
    return pages


def _decode_kernel(sm_scale, block_pages, pages_per_seq, perturb,
                   lengths_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems):
    """One invocation walks every (row, block) with block * tokens <
    length; a row of length 0 is stepped over by the scalar scan and
    costs no copy and no product.

    Refs: lengths i32[B] and the flat table i32[B * pages_per_seq] in
    SMEM; q/o [B, Hq, D] whole in VMEM; the pools [Hkv, P, page, D]
    where they lie (HBM); k/vbuf [2, Hkv, block_pages, page, D]; DMA
    semaphores [slot, pool]. A block is fetched as ONE strided copy a
    page and pool covering every KV head (`pool.at[:, page]`), and
    only for the row's live pages: a table entry past ceil(length /
    page) is never read, so the trash page behind it is not either.
    While a block is multiplied the next one (of this row, or the
    first of the next row with length > 0) is in flight."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, num_q_heads, head_dim = q_ref.shape
    num_kv_heads, _, page_size, _ = k_hbm.shape
    group = num_q_heads // num_kv_heads
    block = block_pages * page_size

    def each_copy(b, i, slot, act):
        """act(descriptor) for the live pages of block i of row b."""
        first = i * block_pages
        live = jnp.minimum(
            (lengths_ref[b] + page_size - 1) // page_size - first,
            block_pages)

        def page_copies(j, _):
            page = tbl_ref[b * pages_per_seq + first + j]
            act(pltpu.make_async_copy(
                k_hbm.at[:, page], kbuf.at[slot, :, j], sems.at[slot, 0]))
            act(pltpu.make_async_copy(
                v_hbm.at[:, page], vbuf.at[slot, :, j], sems.at[slot, 1]))

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
    head_of_row = jax.lax.broadcasted_iota(
        jnp.int32, (num_q_heads, 1), 0) // group

    def by_head(parts):
        """[Hq, n] whose row r is parts[r // group][r]: every head's
        product is taken for all query rows (the matrix unit is paid
        by the keys it loads, not by 4 or 32 query rows) and each row
        keeps its own head's."""
        out = parts[0]
        for h in range(1, num_kv_heads):
            out = jnp.where(head_of_row >= h, parts[h], out)
        return out

    def row(carry):
        b, slot0 = carry
        length = lengths_ref[b]
        blocks = (length + block - 1) // block
        after = next_live(b + 1)
        q = q_ref[b]                                # [Hq, D] as stored

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
            s = by_head([
                jax.lax.dot_general(
                    q, kbuf[slot, h].reshape(block, head_dim),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                for h in range(num_kv_heads)]) * sm_scale
            if perturb:
                # Non-vacuity hook, as `_attention_kernel`'s.
                s = s * (1.0 + perturb)
            t_idx = (i * block +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(t_idx < length, s, -jnp.inf)
            # A walked block holds at least one live token: m_new is
            # finite and every exp() argument finite or -inf.
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            w = jnp.exp(s - m_new)                  # [Hq, block] f32
            l_new = l_prev * alpha + jnp.sum(w, axis=-1, keepdims=True)
            pv = by_head([
                jnp.dot(w, vbuf[slot, h].reshape(block, head_dim)
                        .astype(jnp.float32),
                        preferred_element_type=jnp.float32)
                for h in range(num_kv_heads)])
            return m_new, l_new, acc * alpha + pv

        _, l, acc = jax.lax.fori_loop(0, blocks, step, (
            jnp.full((num_q_heads, 1), -jnp.inf, jnp.float32),
            jnp.zeros((num_q_heads, 1), jnp.float32),
            jnp.zeros((num_q_heads, head_dim), jnp.float32)))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return after, (slot0 + blocks) % 2

    first = next_live(jnp.int32(0))

    @pl.when(first < batch)
    def _():
        start(first, 0, 0)

    jax.lax.while_loop(lambda carry: carry[0] < batch, row,
                       (first, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=('block_pages', 'interpret',
                                             'perturb'))
def _decode_call(q, k_pages, v_pages, lengths, page_indices, *,
                 block_pages, interpret, perturb):
    """Jitted on its own, as `paged_attention._write_pool` is: a
    program of L layers traces and lowers the kernel once, not L
    times (16 lowerings were 33 s of the server's warm-up on the
    chip, compile cache or not: the cache is keyed on what lowering
    returns)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    num_kv_heads, _, page_size, head_dim = k_pages.shape
    pages_per_seq = page_indices.shape[1]
    kernel = functools.partial(
        _decode_kernel, 1.0 / (head_dim ** 0.5), block_pages,
        pages_per_seq, perturb)
    whole = pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0))
    buf = pltpu.VMEM((2, num_kv_heads, block_pages, page_size, head_dim),
                     k_pages.dtype)
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
            dimension_semantics=('arbitrary',)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='paged_decode_attention',
    )(lengths.astype(jnp.int32),
      page_indices.astype(jnp.int32).reshape(-1), q, k_pages, v_pages)


def paged_decode_kernel(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, lengths: jax.Array,
                        page_indices: jax.Array, *,
                        interpret: bool = False,
                        perturb: float = 0.0) -> jax.Array:
    """The decode read of an unquantized pool (route 'decode'): one
    query token a row over its paged history.

    q: [B, Hq, D]; k/v_pages: [Hkv, total_pages, page_size, D] of a
    shape `decode_kernel_refusal` takes; lengths i32[B] (0: the row is
    skipped and returns zeros); page_indices i32[B, pages_per_seq].
    Returns [B, Hq, D] in q.dtype, `_reference_paged_attention`'s
    semantics. Keys, values and the query reach the matrix unit as
    stored; scores, softmax state and the accumulator are float32.
    Under a tensor mesh each chip runs it on its own kv-head slice
    (`shard_over_kv_heads`)."""
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    refusal = decode_kernel_refusal(k_pages)
    if refusal is not None:
        raise ValueError(f"paged-attention impl 'decode': {refusal}")
    from jax.sharding import PartitionSpec as P
    heads = P(None, 'tensor', None)
    pool = P('tensor', None, None, None)

    def per_chip(q_, k_, v_, lengths_, tbl):
        # The block follows the heads THIS chip holds.
        return _decode_call(
            q_, k_, v_, lengths_, tbl, interpret=interpret,
            perturb=perturb,
            block_pages=decode_block_pages(k_, tbl.shape[1]))

    return shard_over_kv_heads(
        per_chip, k_pages.shape[0],
        in_specs=(heads, pool, pool, P(None), P(None, None)),
        out_specs=heads)(q, k_pages, v_pages, lengths, page_indices)


def shard_over_kv_heads(fn, num_kv_heads: int, *, in_specs, out_specs):
    """`fn` shard_mapped over the active mesh's `tensor` axis when the
    kv-heads axis divides it, else `fn` unchanged (no mesh context, a
    single device, or the GQA-remainder replicated pool).

    GSPMD treats a Pallas call as opaque: left alone under a sharded
    jit it gathers the head-sharded pool onto every chip each layer,
    each step. Both paged-attention kernels of this module are
    per-kv-head independent, so each chip runs the kernel on its own
    head slice of the pool."""
    from skypilot_tpu.ops.attention import _active_mesh
    mesh = _active_mesh()
    tensor = mesh.shape.get('tensor', 1) if mesh is not None else 1
    if tensor <= 1 or num_kv_heads % tensor != 0:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# -- fused QKV LoRA ---------------------------------------------------------
def _qkv_lora_kernel(ids_ref, x_ref, aq_ref, bq_ref, ak_ref, bk_ref,
                     av_ref, bv_ref, dq_ref, dk_ref, dv_ref):
    x = x_ref[0].astype(jnp.float32)            # [S, d_model]
    for a_ref, b_ref, o_ref in ((aq_ref, bq_ref, dq_ref),
                                (ak_ref, bk_ref, dk_ref),
                                (av_ref, bv_ref, dv_ref)):
        h = x @ a_ref[0].astype(jnp.float32)    # [S, r]
        o_ref[0] = h @ b_ref[0].astype(jnp.float32)


def fused_qkv_lora_delta(x: jax.Array, wq_factors: Dict,
                         wk_factors: Dict, wv_factors: Dict,
                         adapter_ids: jax.Array, *,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """UNSCALED f32 LoRA deltas for wq/wk/wv in ONE pallas dispatch.

    x: [B, S, d_model]; each factors dict holds stacked
    a [N, d_in, r] / b [N, r, d_out]; adapter_ids i32[B] selects each
    row's adapter via scalar-prefetch index_maps (no gathered factor
    copies in HBM). Returns (dq, dk, dv) as f32 [B, S, d_out]; the
    caller applies `y + (scale * d).astype(y.dtype)` so numerics match
    `lora.apply_delta` — same (x @ a) @ b contraction order in f32.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, chunk, d_model = x.shape

    def x_map(b, ids):
        return (b, 0, 0)

    def factor_map(b, ids):
        return (ids[b], 0, 0)

    in_specs = [pl.BlockSpec((1, chunk, d_model), x_map)]
    operands = [x]
    out_shapes = []
    out_specs = []
    for f in (wq_factors, wk_factors, wv_factors):
        a, b_fac = f['a'], f['b']
        _, d_in, rank = a.shape
        d_out = b_fac.shape[-1]
        in_specs += [pl.BlockSpec((1, d_in, rank), factor_map),
                     pl.BlockSpec((1, rank, d_out), factor_map)]
        operands += [a, b_fac]
        out_shapes.append(
            jax.ShapeDtypeStruct((batch, chunk, d_out), jnp.float32))
        out_specs.append(pl.BlockSpec((1, chunk, d_out), x_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(batch,),
        in_specs=in_specs, out_specs=out_specs)
    return pl.pallas_call(
        _qkv_lora_kernel, grid_spec=grid_spec, out_shape=out_shapes,
        interpret=interpret, name='fused_qkv_lora',
    )(adapter_ids.astype(jnp.int32), *operands)


# -- analytic HBM roofline --------------------------------------------------
def bytes_per_token_model(*, num_layers: int, num_kv_heads: int,
                          num_q_heads: int, head_dim: int,
                          page_size: int, pages_per_seq: int,
                          kv_elem_bytes: int, quantized: bool,
                          impl: str, weight_bytes: int = 0,
                          batch: int = 1,
                          lora_bytes_per_row: int = 0
                          ) -> Dict[str, float]:
    """Modeled HBM bytes one decode step moves PER SEQUENCE (= per
    generated token), from the engine's actual page geometry.

    The model charges every row its FULL page table (the XLA gather
    does read it whole; the kernels stop at the row's length), so
    context traffic is static per config and an upper bound. Per
    layer:

      pool reads    2 * pages_per_seq * page_size * Hkv * D * elem
      scale rows    2 * pages_per_seq * page_size * 4        (int8)
      xla dequant   the gather route additionally materializes
                    dequantized + GQA-expanded [T, Hq, D] copies of k
                    and v in HBM — one write + one read each. This is
                    the term the fused kernel deletes.

    Whole-model terms: weight reads amortize over the decode batch
    (weights stream once per step); each row re-reads its adapter's
    LoRA factor rows (`lora_bytes_per_row` — identical bytes fused or
    not, the fusion saves dispatches, not factor traffic).
    """
    tokens_walked = pages_per_seq * page_size
    pool = (2 * tokens_walked * num_kv_heads * head_dim
            * kv_elem_bytes * num_layers)
    scales = (2 * tokens_walked * 4 * num_layers) if quantized else 0
    dequant = 0
    if impl == 'xla':
        elem = 4 if quantized else kv_elem_bytes
        dequant = (2 * 2 * tokens_walked * num_q_heads * head_dim
                   * elem * num_layers)
    weights = weight_bytes / max(batch, 1)
    total = pool + scales + dequant + weights + lora_bytes_per_row
    return {
        'impl': impl,
        'context_tokens_walked': tokens_walked,
        'kv_pool_bytes': pool,
        'kv_scale_bytes': scales,
        'dequant_materialize_bytes': dequant,
        'weight_bytes_amortized': round(weights, 1),
        'lora_bytes': lora_bytes_per_row,
        'total_bytes_per_token': round(total + 0.0, 1),
    }
