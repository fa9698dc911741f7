"""Attention dispatch: pallas TPU flash attention when profitable.

MXU-friendly attention for the recipe models. On TPU with long enough
sequences, uses the pallas flash-attention kernel (blockwise softmax,
O(S) memory, no S×S materialization in HBM); otherwise
`jax.nn.dot_product_attention` (XLA fuses the mask+softmax chain).

Layout convention: q/k/v are [batch, seq, heads, head_dim] (BSHD).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Sequence length from which 'auto' takes the Pallas flash kernel on
# TPU: its O(S) memory pays once the S x S scores stop fitting
# VMEM-friendly XLA fusions. Where the crossover sits on a v5e is NOT
# MEASURED: ROADMAP S7 sets this from `kernel_check` on the chip.
_FLASH_MIN_SEQ = 2048


@jax.named_scope('attention')
def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          *, causal: bool = True,
                          impl: str = 'auto') -> jax.Array:
    """q: [B,S,H,D]; k/v: [B,S,Hkv,D] (GQA allowed). Returns [B,S,H,D]."""
    assert q.ndim == 4 and k.ndim == 4 and v.ndim == 4, (q.shape, k.shape)
    if v.shape[-1] != q.shape[-1]:
        # Mismatched value dim (MLA: qk_head_dim != v_head_dim). Must
        # be decided BEFORE the ring/flash dispatch: both kernels
        # require equal q/k/v dims. einsum + f32 softmax fuses fine
        # under XLA — but on a seq-sharded mesh this forfeits the ring
        # path's O(S/shards) memory guarantee, so say so (trace-time).
        from skypilot_tpu.parallel import context as cp_context
        if cp_context.active_seq_mesh() is not None:
            import warnings
            warnings.warn(
                'context parallelism requested (seq-sharded mesh) but '
                f'v_head_dim={v.shape[-1]} != qk_head_dim={q.shape[-1]} '
                '(MLA): ring attention does not support unequal dims, '
                'falling back to materialized S x S scores under GSPMD '
                '— results are correct but per-shard attention memory '
                'is O(S), not O(S/shards).', stacklevel=2)
        return _unequal_dims_attention(q, k, v, causal=causal)
    # Context parallelism: a seq-sharded mesh switches to ring attention.
    from skypilot_tpu.parallel import context as cp_context
    seq_mesh = cp_context.active_seq_mesh()
    if seq_mesh is not None and impl in ('auto', 'ring'):
        from skypilot_tpu.ops import ring_attention as ra
        num_q_heads, num_kv_heads = q.shape[2], k.shape[2]
        if num_kv_heads != num_q_heads:
            rep = num_q_heads // num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        heads_axis = 'tensor' if seq_mesh.shape.get('tensor', 1) > 1 else None
        return ra.ring_attention(q, k, v, mesh=seq_mesh, causal=causal,
                                 heads_axis=heads_axis)
    seq_len = q.shape[1]
    use_flash = (impl == 'flash' or
                 (impl == 'auto' and jax.default_backend() == 'tpu' and
                  seq_len >= _FLASH_MIN_SEQ and _flash_shardable(q)))
    if use_flash:
        return _flash(q, k, v, causal=causal)
    # GQA: expand kv heads to q heads for the XLA path.
    num_q_heads, num_kv_heads = q.shape[2], k.shape[2]
    if num_kv_heads != num_q_heads:
        rep = num_q_heads // num_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return jax.nn.dot_product_attention(q, k, v, is_causal=causal)


def _unequal_dims_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            *, causal: bool) -> jax.Array:
    """Generic attention for v_head_dim != qk_head_dim (MLA)."""
    num_q_heads, num_kv_heads = q.shape[2], k.shape[2]
    if num_kv_heads != num_q_heads:
        rep = num_q_heads // num_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        seq_q, seq_k = q.shape[1], k.shape[1]
        mask = (jnp.arange(seq_k)[None, :]
                <= jnp.arange(seq_q)[:, None])
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhqk,bkhv->bqhv', p, v.astype(jnp.float32))
    return out.astype(q.dtype)


@jax.named_scope('flash_attention')
def _pallas_flash_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool) -> jax.Array:
    """Single-shard pallas flash attention ([B,S,H,D] in/out)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    # pallas kernel wants [B,H,S,D]
    q_, k_, v_ = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    out = fa.flash_attention(q_, k_, v_, causal=causal, sm_scale=sm_scale)
    return jnp.swapaxes(out, 1, 2)


def _active_mesh():
    """The `with mesh:` context's mesh, or None. The trainer and the
    serving engine enter the legacy context manager, which JAX 0.9
    keeps in `jax._src.mesh.thread_resources` (the public
    `jax.sharding.get_abstract_mesh` only sees `jax.set_mesh`)."""
    from jax._src import mesh as mesh_mod
    mesh = mesh_mod.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _batch_shards(mesh) -> tuple:
    """(axes, product) of the mesh axes the batch dim is sharded on."""
    axes = [a for a in ('data', 'fsdp') if mesh.shape.get(a, 1) > 1]
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    return axes, shards


def _flash_shardable(q: jax.Array) -> bool:
    """Whether the flash kernel can be shard_mapped over the active
    mesh: the batch must divide the data x fsdp shards. When it does
    not, 'auto' takes the GSPMD-native XLA attention — said once, at
    trace time, because the O(S) memory guarantee goes with it."""
    mesh = _active_mesh()
    if mesh is None or mesh.size == 1:
        return True
    _, shards = _batch_shards(mesh)
    if q.shape[0] % shards == 0:
        return True
    import warnings
    warnings.warn(
        f'flash attention not used at seq={q.shape[1]}: batch '
        f'{q.shape[0]} does not divide the mesh\'s {shards} data x '
        f'fsdp shards, so the Pallas call cannot be shard_mapped; XLA '
        f'attention (S x S scores materialized) runs instead.',
        stacklevel=3)
    return False


def _flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool, kernel=_pallas_flash_kernel) -> jax.Array:
    """Sharding-safe flash attention. Raises when the batch cannot be
    cleanly shard_mapped ('auto' checks `_flash_shardable` first;
    impl='flash' asked for this kernel by name)."""
    num_q_heads, num_kv_heads = q.shape[2], k.shape[2]
    mesh = _active_mesh()
    batch_axes = []
    if mesh is not None and mesh.size > 1:
        batch_axes, batch_shards = _batch_shards(mesh)
        if q.shape[0] % batch_shards != 0:
            raise ValueError(
                f'flash attention: batch {q.shape[0]} does not divide '
                f'the mesh\'s {batch_shards} data x fsdp shards')
    if num_kv_heads != num_q_heads:
        rep = num_q_heads // num_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, causal)
    # A pallas call is opaque to GSPMD: under a sharded jit it would be
    # REPLICATED onto every chip. shard_map it over the mesh instead —
    # batch rides the data/fsdp axes, heads ride tensor; causal masking
    # is per (batch, head) so shards are independent.
    heads_axis = ('tensor' if mesh.shape.get('tensor', 1) > 1 and
                  num_q_heads % mesh.shape['tensor'] == 0 else None)
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(batch_axes) if batch_axes else None, None, heads_axis,
             None)
    return jax.shard_map(
        functools.partial(kernel, causal=causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


@jax.named_scope('attention')
def chunked_cache_attention(q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, cached_k: jax.Array,
                            cached_v: jax.Array, positions: jax.Array,
                            *, chunk_only: bool = False):
    """Multi-token cache attention at arbitrary PER-ROW offsets.

    Generalizes `cached_decode_attention` to S>=1 query chunks: writes
    this chunk's K/V at `positions[b, s]` (contiguous per row, starting
    at positions[:, 0]) and attends each query over every cache entry
    with index <= its absolute position. One op drives both chunked
    prefill (offset 0 — the old empty-cache special case) and
    speculative-decoding verification chunks (offset = current length),
    because the chunk is written BEFORE attending: any stale cache
    entries from a previous step's rejected drafts are overwritten
    before the mask can expose them. `chunk_only=True` is the prefill
    fast path: the caller guarantees the cache holds nothing below the
    offset, so attention stays chunk-local (S x S, flash-eligible)
    instead of scanning all T cache slots.

    q/k_new/v_new: [B, S, H|Hkv, D]; cached_k/v: [B, T, Hkv, D];
    positions: [B, S]. Returns (out [B,S,H,D], cached_k, cached_v).
    """
    dtype = cached_k.dtype
    max_len = cached_k.shape[1]
    start = positions[:, 0]

    def write_rows(cache_row, kv_rows, p):
        return jax.lax.dynamic_update_slice(cache_row, kv_rows, (p, 0, 0))

    cached_k = jax.vmap(write_rows)(cached_k, k_new.astype(dtype), start)
    cached_v = jax.vmap(write_rows)(cached_v, v_new.astype(dtype), start)
    if chunk_only:
        # PREFILL fast path (contract: nothing live in the cache below
        # the offset): attend only within the chunk — S x S, flash-
        # dispatchable — instead of S x T over the whole cache.
        out = dot_product_attention(q, k_new, v_new, causal=True)
        return out, cached_k, cached_v
    num_q_heads, num_kv_heads = q.shape[2], cached_k.shape[2]
    k_all, v_all = cached_k, cached_v
    if num_kv_heads != num_q_heads:
        rep = num_q_heads // num_kv_heads
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum('bshd,bthd->bhst', q.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_len)[None, None, :]
            <= positions[:, :, None])[:, None]          # [B,1,S,T]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhst,bthd->bshd', p, v_all.astype(jnp.float32))
    return out.astype(q.dtype), cached_k, cached_v


@jax.named_scope('attention')
def cached_decode_attention(q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, cached_k: jax.Array,
                            cached_v: jax.Array, pos: jax.Array):
    """One-token KV-cache attention with PER-ROW write positions.

    The single serving-cache contract shared by every model family
    (llama/mixtral/gpt): write this step's k/v at `pos[b]` in row b's
    cache, attend q over the cache masked to `k_idx <= pos[b]`
    (f32 softmax), with GQA expansion when q has more heads than the
    cache. Rows at different depths decode in one step — what the
    continuous-batching engine (models/batching.py) relies on.

    q/k_new/v_new: [B, 1, H|Hkv, D]; cached_k/v: [B, T, Hkv, D];
    pos: [B]. Returns (out [B, 1, H, D], cached_k, cached_v).
    """
    dtype = cached_k.dtype
    max_len = cached_k.shape[1]

    def write_row(cache_row, kv_row, p):
        return jax.lax.dynamic_update_slice(cache_row, kv_row, (p, 0, 0))

    cached_k = jax.vmap(write_row)(cached_k, k_new.astype(dtype), pos)
    cached_v = jax.vmap(write_row)(cached_v, v_new.astype(dtype), pos)
    num_q_heads, num_kv_heads = q.shape[2], cached_k.shape[2]
    k_all, v_all = cached_k, cached_v
    if num_kv_heads != num_q_heads:
        rep = num_q_heads // num_kv_heads
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k_all.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_len)[None, :] <= pos[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhqk,bkhd->bqhd', p, v_all.astype(jnp.float32))
    return out.astype(q.dtype), cached_k, cached_v
