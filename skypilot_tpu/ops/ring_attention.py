"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context recipe op (task mandate; the reference launches user
ring-attention code — llm/ examples — but implements none; here it is
a framework op). Sequence (context) parallelism: q/k/v are sharded
along the mesh's `seq` axis; each step every device computes blockwise
attention of its local queries against the resident k/v block, then
rotates k/v one hop around the ring with `lax.ppermute` — ICI
neighbor-to-neighbor traffic, overlapping compute with the rotation,
O(S_local) memory per device. Online-softmax (flash-style) accumulation
in f32 keeps it exact.

Causality is by *global block position*: a k/v block that originated
downstream of the query shard is fully masked; the diagonal block uses
the triangular mask.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _online_block_update(o, m, l, s, v):
    """One flash-attention accumulation step.

    o: [B,Sq,H,D] f32 accumulator; m,l: [B,Sq,H] running max / denom;
    s: [B,Sq,H,Sk] scores; v: [B,Sk,H,D].
    """
    block_max = jnp.max(s, axis=-1)                       # [B,Sq,H]
    new_m = jnp.maximum(m, block_max)
    # Renormalize previous accumulator.
    correction = jnp.exp(m - new_m)                       # [B,Sq,H]
    p = jnp.exp(s - new_m[..., None])                     # [B,Sq,H,Sk]
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum('bqhk,bkhd->bqhd', p, v.astype(jnp.float32))
    new_o = o * correction[..., None] + pv
    return new_o, new_m, new_l


def _ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                            axis_name: str, causal: bool,
                            vary_axes: Tuple[str, ...] = ()) -> jax.Array:
    """Runs on each shard: q,k,v are the LOCAL [B,Sl,H,D] blocks."""
    vary_axes = tuple(vary_axes) or (axis_name,)
    num_shards = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    batch, s_local, num_heads, head_dim = q.shape
    scale = 1.0 / (head_dim ** 0.5)
    q32 = q.astype(jnp.float32) * scale

    # Mark accumulators device-varying over every axis the inputs vary
    # on, so the fori_loop carry type stays stable once they mix with
    # per-shard data.
    def _vary(x):
        return lax.pcast(x, vary_axes, to='varying')

    o = _vary(jnp.zeros((batch, s_local, num_heads, head_dim), jnp.float32))
    m = _vary(jnp.full((batch, s_local, num_heads), -jnp.inf, jnp.float32))
    l = _vary(jnp.zeros((batch, s_local, num_heads), jnp.float32))

    if causal:
        tri = jnp.tril(jnp.ones((s_local, s_local), bool))  # [Sq,Sk]

    def body(step, carry):
        o, m, l, k_blk, v_blk = carry
        src = (my_idx - step) % num_shards  # which block k_blk came from
        s = jnp.einsum('bqhd,bkhd->bqhk', q32, k_blk.astype(jnp.float32))
        if causal:
            # Block-level causality + diagonal triangular mask.
            fully_visible = src < my_idx
            diagonal = src == my_idx
            mask = jnp.where(
                diagonal,
                tri[None, :, None, :],
                jnp.full((1, s_local, 1, s_local), fully_visible))
            s = jnp.where(mask, s, -jnp.inf)
        o, m, l = _online_block_update(o, m, l, s, v_blk)
        perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk

    o, m, l, _, _ = lax.fori_loop(0, num_shards, body, (o, m, l, k, v))
    # Fully-masked rows (none under causal with left-to-right layout,
    # but guard anyway): l == 0 → output 0.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (o / safe_l[..., None]).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, seq_axis: str = 'seq',
                   batch_axes: Tuple[str, ...] = ('data', 'fsdp'),
                   heads_axis: Optional[str] = 'tensor',
                   causal: bool = True) -> jax.Array:
    """Exact attention with q/k/v sharded along `seq_axis`.

    q/k/v: [B, S, H, D] global shapes; S must divide evenly by the seq
    axis size. GQA callers must pre-expand kv heads.
    """
    assert q.shape == k.shape == v.shape, (q.shape, k.shape)
    spec = P(batch_axes, seq_axis, heads_axis, None)
    vary_axes = tuple(batch_axes) + (seq_axis,)
    if heads_axis is not None:
        vary_axes += (heads_axis,)
    fn = jax.shard_map(
        functools.partial(_ring_attention_sharded, axis_name=seq_axis,
                          causal=causal, vary_axes=vary_axes),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Plain full attention (for numerical comparison in tests)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum('bqhd,bkhd->bqhk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        seq = q.shape[1]
        mask = jnp.tril(jnp.ones((seq, seq), bool))
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bqhk,bkhd->bqhd', p,
                      v.astype(jnp.float32)).astype(q.dtype)
