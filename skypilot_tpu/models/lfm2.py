"""LFM2-family hybrid decoder (`lfm2_moe`): gated short convolutions,
grouped-query attention with normed queries and keys, and routed
experts.

`layer_types` spells the model a layer at a time, `conv` or
`full_attention`. A layer is `x + mixer(RMSNorm(x))`, then `x +
ffn(RMSNorm(x))`: a dense SwiGLU of `mlp_dim` in the first
`num_dense_layers` layers and routed experts in the others. After the
last a final RMSNorm and the head, tied to the embedding.

  - `conv` (the short convolution): `[B; C; x] = W_in h` (3 x hidden,
    no bias), `u_t = B_t * x_t`, `v_t = sum_j w_j * u_{t-K+1+j}` over
    the `conv_L_cache` = K taps (depthwise, causal, zeros before the
    sequence, no bias and no activation), `y_t = W_out (C_t * v_t)`.
    The products and the convolution's sums in float32; `u` is rounded
    to the compute dtype, which is what the tail keeps. A decode
    round's two projections read their matrices inside
    ops/pallas_streamed calls on a TPU, so that the mixer's scope holds
    its weights' reads.
  - `full_attention`: q, k, v in heads of `hidden / heads` (64) lanes,
    q and k each RMS-normed over a head's lanes with a learned scale,
    then rotated (rotate-half RoPE over the whole head); causal softmax
    at 1 / sqrt(head_dim), `num_heads / num_kv_heads` query heads to a
    K/V head, and `wo`.
  - routed experts (models/deepseek.MoEByShare): a sigmoid router over
    all `n_routed_experts` in float32 at full precision; the top
    `num_experts_per_tok` of score + expert bias are chosen, weighted
    by their plain scores normalised over the chosen; SwiGLU experts
    of `moe_dim`; no shared expert. This chip holds `experts_held`
    from `expert_offset` (0 held = all).

Serving keeps two kinds of cache. K and V of the attention layers live
in the page pool with a token's heads side by side in one row
(ops/paged_attention.packed_kv_layout: 64-wide heads read by the
page with no copy of the pool; a decode round's read is on a TPU the
kernel of ops/pallas_packed.py, route 'packed_decode', which copies the
live pages of the live lanes only, and a chunk's the gather,
'packed_heads_xla'). A conv
layer's only state is its convolution's tail, the last K - 1 inputs
`u` of a sequence: one row a SLOT (`page_layout().slot_arrays`,
`conv_state` [(K - 1) x hidden], the inputs one after the other). A
prompt's first chunk (`prefill=True`) starts from zeros and never
reads the slot's old row, so slot reuse
and preemption-by-recompute need no reset; a later chunk starts from
the tail the one before left; a decode round shifts the tails of the
live lanes and leaves a dead lane's as it was.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.deepseek import MoEByShare, expert_route
from skypilot_tpu.models.llama import (FeedForward as SwiGLU, RMSNorm,
                                       _proj, apply_rope)
from skypilot_tpu.models.nemotron_h import _rows
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import paged_attention as paged_ops
from skypilot_tpu.ops import pallas_streamed
from skypilot_tpu.ops import ssm

Dtype = Any
F32 = jnp.float32

#: The published `layer_types` of LFM2-24B-A2B (40 layers: attention
#: at 2, 6, ..., 38).
LFM2_24B_LAYERS = tuple(
    'full_attention' if i % 4 == 2 else 'conv' for i in range(40))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """HF `lfm2_moe` config.json; the defaults are LFM2-24B-A2B's
    published sizes."""
    vocab_size: int = 65536
    max_seq_len: int = 128000
    layer_types: Tuple[str, ...] = LFM2_24B_LAYERS
    embed_dim: int = 2048
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    num_heads: int = 32
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    # The dense layers' SwiGLU (models/llama.FeedForward reads mlp_dim).
    num_dense_layers: int = 2
    mlp_dim: int = 11776
    # Routed experts (models/deepseek.MoEByShare reads these names): the
    # ROUTER's width, the published count; this chip holds
    # `experts_held` of them from `expert_offset` on (0 held = all).
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_dim: int = 1536
    n_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: int = 0
    expert_offset: int = 0
    dtype: Dtype = jnp.bfloat16
    logits_dtype: Optional[Dtype] = None
    remat: bool = False
    # Page pool of the attention layers (see llama.LlamaConfig).
    kv_page_size: int = 16
    kv_total_pages: int = 0
    kv_dtype: str = 'bf16'

    @classmethod
    def l10(cls, **kw) -> 'Lfm2Config':
        """The published layers 0-9 at every published width: the two
        dense layers and two periods [full_attention, conv, conv, conv]
        of expert layers, all 64 experts held, the whole vocabulary
        (stage 1 of a 4-stage pipeline: perfbench/configs/
        lfm2-24b-a2b-l10.json has the cut)."""
        base = dict(layer_types=LFM2_24B_LAYERS[:10], max_seq_len=4096,
                    kv_total_pages=2048)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw) -> 'Lfm2Config':
        """Every kind of layer at a size a CPU test holds: a dense conv
        layer, then routed attention and conv layers, 16 experts of
        which 4 a token, heads of 32 lanes packed four to a row."""
        base = dict(
            vocab_size=512, max_seq_len=256,
            layer_types=('conv', 'full_attention', 'conv', 'conv',
                         'full_attention'),
            embed_dim=128, num_heads=4, num_kv_heads=2,
            num_dense_layers=1, mlp_dim=192, n_routed_experts=16,
            num_experts_per_tok=4, moe_dim=64, experts_held=16,
            kv_total_pages=128)
        base.update(kw)
        return cls(**base)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def page_layout(self) -> paged_ops.PageLayout:
        """K and V of the attention layers by page, a token's heads side
        by side in one row; beside them, by SLOT, each conv layer's
        tail."""
        kv = paged_ops.packed_kv_layout(self.num_kv_heads, self.head_dim,
                                        self.kv_page_size,
                                        self.kv_total_pages)
        return dataclasses.replace(
            kv, layers=self.layer_types.count('full_attention'),
            # The tail's K - 1 inputs one after the other in one row:
            # as [K - 1, hidden] XLA:TPU tiles the array (2, 128) and
            # copies all of it around a decode round's update.
            slot_arrays=(paged_ops.SlotArray(
                'conv_state', ((self.conv_L_cache - 1) * self.embed_dim,)),),
            slot_layers=self.layer_types.count('conv'))


class _Proj(nn.Module):
    """A bias-free projection seeded normal with a standard deviation of
    1 / sqrt(its inputs), as `nn.Dense` with that init (the same
    parameter, the same product). `streamed`: a decode round's rows on
    a TPU, through ops/pallas_streamed, which reads the matrix from HBM
    inside the call, and so inside the caller's scope."""
    features: int
    axes: Tuple[str, str]
    dtype: Dtype

    @nn.compact
    def __call__(self, h: jax.Array, streamed: bool = False) -> jax.Array:
        kernel = self.param(
            'kernel', nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, 'fan_in', 'normal'),
                self.axes), (h.shape[-1], self.features), F32)
        h, kernel = h.astype(self.dtype), kernel.astype(self.dtype)
        if streamed:
            rows = h.reshape(-1, h.shape[-1])
            return pallas_streamed.streamed_matmul(rows, kernel).reshape(
                *h.shape[:-1], self.features)
        return jax.lax.dot_general(h, kernel,
                                   (((h.ndim - 1,), (0,)), ((), ())))


def _streams(cfg: 'Lfm2Config', rows: int) -> bool:
    """A decode round's conv projections take ops/pallas_streamed where
    Mosaic compiles it and both shapes pass its refusal."""
    dim = cfg.embed_dim
    return pallas_streamed.available() and not any(
        pallas_streamed.refusal(rows, k, n, cfg.dtype)
        for k, n in ((dim, 3 * dim), (dim, dim)))


def conv_step(tail: jax.Array, u: jax.Array, weight: jax.Array,
              live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A decode round's convolution, a lane a slot: tail [slots, (K - 1)
    C] (a slot's last K - 1 inputs one after the other in one row,
    oldest first), u [slots, C] the round's inputs, weight [K, C] (tap
    K - 1 multiplies u), live bool[slots]. Returns (float32 [slots, C],
    the taps summed in the order ops/ssm.causal_conv sums them; the
    tails, a live lane's shifted by its input and a dead lane's as it
    was, in one pass over the array where it lies)."""
    taps, width = weight.shape
    full = jnp.concatenate([tail, u.astype(tail.dtype)], axis=1)

    def tap(k):
        return (full[:, k * width:(k + 1) * width].astype(F32)
                * weight[k].astype(F32))

    conv = tap(0)
    for k in range(1, taps):
        conv = conv + tap(k)
    # The taps read the old tails before the shift overwrites them where
    # they lie; left to itself XLA:TPU may order the shift first and
    # keep a copy of the old array for the taps.
    conv, tail = jax.lax.optimization_barrier((conv, tail))
    with jax.named_scope('conv_tail'):
        shifted = jnp.concatenate([tail[:, width:], u.astype(tail.dtype)],
                                  axis=1)
        return conv, jnp.where(live[:, None], shifted, tail)


class ShortConv(nn.Module):
    """The gated short convolution, all of it in the scope
    `short_conv`; its tail by slot when serving, written in the scope
    `conv_tail`."""
    config: Lfm2Config

    @nn.compact
    @jax.named_scope('short_conv')
    def __call__(self, h: jax.Array, decode: bool = False,
                 prefill: bool = False,
                 live: Optional[jax.Array] = None,
                 slots: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq, dim = h.shape
        taps = cfg.conv_L_cache
        # Seeded so that the mixer carries signal at any width: the
        # projections at 1 / sqrt(fan-in) (at 2,048 inputs that is the
        # published 0.02), the taps as PyTorch's Conv1d has them.
        weight = self.param(
            'conv_weight', nn.with_logical_partitioning(
                lambda k, s, d: jax.random.uniform(
                    k, s, d, -taps ** -0.5, taps ** -0.5), (None, None)),
            (taps, dim), F32)
        streamed = decode and seq == 1 and _streams(cfg, batch)
        bcx = _Proj(3 * dim, ('embed', 'mlp'), cfg.dtype,
                    name='in_proj')(h, streamed)
        b, c, x = (bcx[..., i * dim:(i + 1) * dim] for i in range(3))
        u = (b.astype(F32) * x.astype(F32)).astype(cfg.dtype)
        mask = _rows(live, batch, seq)
        tail = None
        if decode:
            # One row a SLOT (the engine's cache has its `num_slots`
            # rows: it is made by a call of one token a slot), the last
            # K - 1 inputs one after the other (`page_layout`).
            tail = self.variable('cache', 'conv_state', jnp.zeros,
                                 (batch, (taps - 1) * dim), cfg.dtype)
        if decode and seq == 1:
            # A decode round: a lane is its slot.
            conv, tail.value = conv_step(tail.value, u[:, 0], weight,
                                         mask[:, 0])
            conv = conv[:, None]
        else:
            lengths = jnp.sum(mask, axis=1, dtype=jnp.int32)
            if decode and slots is None:
                raise ValueError(
                    'a prefill chunk of a model with state by slot '
                    'needs `slots`, the slot of each of its rows')
            if decode and not prefill:
                # Row by row, as slices: a gather may be given a layout
                # of its own, and with it a copy of the whole array.
                tail0 = jnp.stack([
                    jax.lax.dynamic_index_in_dim(tail.value, slots[row], 0,
                                                 keepdims=False)
                    for row in range(batch)]).reshape(batch, taps - 1, dim)
            else:
                # A sequence's start: zeros, whatever the slot's row
                # held (a finished or preempted request's).
                tail0 = jnp.zeros((batch, taps - 1, dim), cfg.dtype)
            conv, tail1 = ssm.causal_conv(u, tail0, weight, None, lengths)
            if decode:
                with jax.named_scope('conv_tail'):
                    tail1 = tail1.reshape(batch, (taps - 1) * dim)
                    for row in range(batch):
                        tail.value = jax.lax.dynamic_update_index_in_dim(
                            tail.value, tail1[row], slots[row], 0)
        y = (c.astype(F32) * conv).astype(cfg.dtype)
        return _Proj(dim, ('mlp', 'embed'), cfg.dtype,
                     name='out_proj')(y, streamed)


class Attention(nn.Module):
    """Grouped-query attention with q and k RMS-normed a head before
    RoPE, over the packed K/V page pool when serving; a decode round's
    dead lanes (`live` False) read none of it."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq, _ = x.shape
        hd, kv_heads = cfg.head_dim, cfg.num_kv_heads

        def heads(name, n):
            return _proj(n * hd, ('embed', 'heads'), cfg.dtype,
                         name)(x).reshape(batch, seq, n, hd)

        def normed(name, t):
            # The norm and the rotation in float32, rounded once.
            t = RMSNorm(cfg.norm_eps, F32, name=name)(t)
            return apply_rope(t, positions, cfg.rope_theta).astype(cfg.dtype)

        q = normed('q_layernorm', heads('wq', cfg.num_heads))
        k = normed('k_layernorm', heads('wk', kv_heads))
        v = heads('wv', kv_heads)
        if not decode:
            out = attention_ops.dot_product_attention(q, k, v, causal=True)
        else:
            layout = cfg.page_layout()
            k_pages, v_pages = (
                self.variable('cache', a.name, jnp.zeros, layout.shape(a),
                              cfg.dtype) for a in layout.arrays)
            # A token's heads side by side: one row of the packed pool.
            k_pages.value, v_pages.value = paged_ops.write_kv_chunk(
                k_pages.value, v_pages.value,
                k.reshape(batch, seq, 1, kv_heads * hd),
                v.reshape(batch, seq, 1, kv_heads * hd), positions,
                page_indices, page_aligned=page_aligned and seq > 1)
            if seq == 1:
                # A dead lane reads nothing: length 0, and zeros out.
                out = paged_ops.packed_decode_attention(
                    q[:, 0], k_pages.value, v_pages.value,
                    lengths=jnp.where(_rows(live, batch, 1)[:, 0],
                                      positions[:, 0] + 1, 0),
                    page_indices=page_indices)[:, None]
            elif prefill:
                # The sequence starts with this chunk: chunk-local.
                out = attention_ops.dot_product_attention(q, k, v,
                                                          causal=True)
            else:
                out = paged_ops.packed_chunk_attention(
                    q, k_pages.value, v_pages.value, positions,
                    page_indices)
        out = out.astype(cfg.dtype).reshape(batch, seq,
                                            cfg.num_heads * hd)
        return _proj(cfg.embed_dim, ('heads', 'embed'), cfg.dtype,
                     'wo')(out)


class Block(nn.Module):
    config: Lfm2Config
    kind: str                           # the layer's `layer_types` entry
    routed: bool = False                # routed experts, else dense

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False, page_aligned: bool = False,
                 live: Optional[jax.Array] = None,
                 slots: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name='operator_norm')(x)
        if self.kind == 'conv':
            x = x + ShortConv(cfg, name='conv')(h, decode, prefill, live,
                                                slots)
        elif self.kind == 'full_attention':
            x = x + Attention(cfg, name='self_attn')(
                h, positions, decode, page_indices, prefill, page_aligned,
                live)
        else:
            raise ValueError(
                f'layer_types entry {self.kind!r}: this model builds '
                f'conv and full_attention layers')
        if self.routed:
            h32 = RMSNorm(cfg.norm_eps, F32, name='ffn_norm')(x)
            x = x + MoEByShare(cfg, stacked_init=True,
                               name='feed_forward')(
                h32.astype(cfg.dtype), live, count=decode, x_router=h32)
        else:
            h = RMSNorm(cfg.norm_eps, cfg.dtype, name='ffn_norm')(x)
            x = x + SwiGLU(cfg, name='feed_forward')(h)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))


class Lfm2(nn.Module):
    """LFM2 decoder; __call__ returns logits [B, S, vocab].

    `decode=False`: the whole sequence from empty state, no cache.
    `decode=True`: through the engine's cache (models/batching.py):
    `page_indices` the rows' page tables, `slots` the slot of each row
    of a prefill chunk (a decode round's lane is its slot), `live` the
    tokens that are a request's own, `prefill` a sequence's first
    chunk."""
    config: Lfm2Config
    #: The serving engine hands `live` to a model that declares this,
    #: and /stats fetches the cache leaves named here.
    takes_live_mask = True
    counter_leaves = ('expert_tokens', 'expert_calls_touched')

    def expert_impl(self) -> str:
        """The route of the expert layers' grouped pass
        (models/deepseek.expert_route; /stats `expert_impl`)."""
        return expert_route(self.config, 'swiglu', self.config.embed_dim)

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 return_hidden: bool = False,
                 page_aligned: bool = False,
                 live: Optional[jax.Array] = None,
                 slots: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq = tokens.shape
        if decode and page_indices is None:
            raise ValueError(
                'Lfm2 serves through the page pool only: give the config '
                'kv_total_pages (serve_lm --continuous-batching '
                '--kv-pool-bytes)')
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        embed = self.param(
            'tok_embed',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                ('vocab', 'table_embed')),
            (cfg.vocab_size, cfg.embed_dim), F32)
        x = embed.astype(cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))
        for i, kind in enumerate(cfg.layer_types):
            x = Block(cfg, kind, routed=i >= cfg.num_dense_layers,
                      name=f'layer_{i}')(
                x, positions, decode, page_indices, prefill, page_aligned,
                live, slots)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        if return_hidden:
            return nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
        # The head is the embedding (tied).
        logits = jnp.einsum('bse,ve->bsv', x, embed.astype(cfg.dtype),
                            preferred_element_type=(cfg.logits_dtype or
                                                    F32))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))
