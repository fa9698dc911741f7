"""Llama-3-family decoder in flax.linen with logical sharding axes.

Recipe model #2 (BASELINE.md configs 2/4): RMSNorm, rotary position
embeddings, grouped-query attention, SwiGLU MLP, untied LM head.
Same logical-axis scheme as models/gpt.py so one rules table drives
DP×FSDP×TP for both.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models import lora as lora_lib
from skypilot_tpu.ops import attention as attention_ops

Dtype = Any


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency rescaling (HF config.json `rope_scaling`).

    `llama3` is the Llama 3.1/3.2 long-context rule: frequencies whose
    wavelength exceeds the original context are divided by `factor`,
    high frequencies are kept, and a smooth ramp interpolates between
    `low_freq_factor` and `high_freq_factor` (reference recipes:
    `llm/llama-3_1-finetuning/` serve these checkpoints). `linear` is
    classic position-interpolation (all frequencies / factor).
    """
    rope_type: str = 'llama3'
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    max_seq_len: int = 8192
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    embed_dim: int = 4096
    mlp_dim: int = 14336
    rope_theta: float = 500_000.0
    rope_scaling: Optional[RopeScaling] = None
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # LM-head logits precision. None = f32 (the safe default for this
    # family; GPT defaults to bf16 — see GPTConfig.logits_dtype for the
    # HBM-traffic rationale). Set jnp.bfloat16 to halve logits traffic.
    logits_dtype: Optional[Dtype] = None
    remat: bool = False
    # Paged KV cache (serving): page size in tokens and the physical
    # page-pool size. Used only when decode calls pass `page_indices`;
    # page 0 is the engine's trash page for unallocated table entries.
    kv_page_size: int = 16
    kv_total_pages: int = 128
    # KV page storage format: 'bf16' stores pages in `dtype`; 'int8'
    # stores int8 pages plus parallel f32 per-page-slot scale arrays
    # (quantize on write, dequantize inside the attention gather —
    # ops/paged_attention.py). Roughly halves pool bytes per token,
    # i.e. ~2x slots / prefix-cache residency at the same HBM.
    # Requires the paged cache (serve_lm --continuous-batching).
    kv_dtype: str = 'bf16'
    # Qwen2-family variant: biases on the q/k/v projections (the only
    # architectural delta from Llama; o_proj and the MLP stay
    # bias-free).
    qkv_bias: bool = False

    @classmethod
    def llama3_8b(cls, **kw) -> 'LlamaConfig':
        return cls(**kw)

    @classmethod
    def llama3_70b(cls, **kw) -> 'LlamaConfig':
        return cls(num_layers=80, num_heads=64, num_kv_heads=8,
                   embed_dim=8192, mlp_dim=28672, **kw)

    @classmethod
    def tiny(cls, **kw) -> 'LlamaConfig':
        return cls(vocab_size=512, max_seq_len=256, num_layers=2,
                   num_heads=4, num_kv_heads=2, embed_dim=128, mlp_dim=384,
                   **kw)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def page_layout(self):
        """A cached token's row in the page pool: K and V."""
        from skypilot_tpu.ops import paged_attention as paged_ops
        return paged_ops.kv_layout(self.num_kv_heads, self.head_dim,
                                   self.kv_page_size, self.kv_total_pages)


def rope_inv_freq(d_half: int, theta: float,
                  scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Per-pair inverse frequencies [d_half], with optional rescaling."""
    freqs = 1.0 / (theta ** (jnp.arange(d_half, dtype=jnp.float32) / d_half))
    if scaling is None:
        return freqs
    if scaling.rope_type == 'linear':
        return freqs / scaling.factor
    if scaling.rope_type != 'llama3':
        raise ValueError(f'unsupported rope_type {scaling.rope_type!r}')
    old_ctx = float(scaling.original_max_position_embeddings)
    low_wavelen = old_ctx / scaling.low_freq_factor
    high_wavelen = old_ctx / scaling.high_freq_factor
    wavelen = 2.0 * jnp.pi / freqs
    smooth = (old_ctx / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor)
    interp = ((1.0 - smooth) * freqs / scaling.factor + smooth * freqs)
    scaled = jnp.where(wavelen > low_wavelen, freqs / scaling.factor,
                       jnp.where(wavelen < high_wavelen, freqs, interp))
    return scaled


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[RopeScaling] = None) -> jax.Array:
    """x: [B, S, H, D]; rotary embedding on the last dim."""
    d_half = x.shape[-1] // 2
    freqs = rope_inv_freq(d_half, theta, scaling)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # B,S,1,Dh
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            'scale',
            nn.with_logical_partitioning(nn.initializers.ones_init(),
                                         ('norm',)),
            (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + self.eps) * scale
        return out.astype(self.dtype)


def _proj(features: int, axes, dtype, name: str,
          use_bias: bool = False) -> nn.Dense:
    return nn.Dense(
        features, use_bias=use_bias, dtype=dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (axes[-1],)))


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 lora: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 lora_scale: Optional[jax.Array] = None,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        batch, seq, _ = x.shape
        hd = cfg.head_dim

        def _lora(name, y, inp):
            # LoRA delta on a projection output (models/lora.py):
            # single-adapter in training, per-row adapter gather in
            # the serving engine. No-op (and no extra compute) when
            # this layer/projection carries no adapter factors.
            if lora is None or name not in lora:
                return y
            return lora_lib.apply_delta(y, inp, lora[name],
                                        adapter_ids, lora_scale)

        q = _proj(cfg.num_heads * hd, ('embed', 'heads'),
                  cfg.dtype, 'wq', cfg.qkv_bias)(x)
        k = _proj(cfg.num_kv_heads * hd, ('embed', 'heads'),
                  cfg.dtype, 'wk', cfg.qkv_bias)(x)
        v = _proj(cfg.num_kv_heads * hd, ('embed', 'heads'),
                  cfg.dtype, 'wv', cfg.qkv_bias)(x)
        # Multi-tenant QKV LoRA: when the fused kernel path is active
        # (an int8 pool on a TPU: ops/pallas_paged.lora_fusion_impl,
        # at trace time) and all three projections carry stacked
        # per-slot factors, the three gather+matmul chains collapse
        # into ONE pallas dispatch. The caller-side scale/cast below
        # matches lora.apply_delta numerics exactly; wq/wk/wv fall
        # back to per-projection apply_delta otherwise (training,
        # single-adapter, the XLA route).
        fused_lora = None
        if (lora is not None and adapter_ids is not None
                and all(t in lora for t in ('wq', 'wk', 'wv'))):
            from skypilot_tpu.ops import pallas_paged
            fused_lora = pallas_paged.lora_fusion_impl(
                cfg.kv_dtype == 'int8')
        if fused_lora is not None:
            from skypilot_tpu.ops import pallas_paged
            dq, dk, dv = pallas_paged.fused_qkv_lora_delta(
                x, lora['wq'], lora['wk'], lora['wv'], adapter_ids,
                interpret=fused_lora == 'fused_interpret')
            q = q + (lora_scale * dq).astype(q.dtype)
            k = k + (lora_scale * dk).astype(k.dtype)
            v = v + (lora_scale * dv).astype(v.dtype)
        else:
            q = _lora('wq', q, x)
            k = _lora('wk', k, x)
            v = _lora('wv', v, x)
        q = q.reshape(batch, seq, cfg.num_heads, hd)
        k = k.reshape(batch, seq, cfg.num_kv_heads, hd)
        v = v.reshape(batch, seq, cfg.num_kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

        kv_quant = cfg.kv_dtype == 'int8'
        if cfg.kv_dtype not in ('bf16', 'int8'):
            raise ValueError(f'unsupported kv_dtype {cfg.kv_dtype!r} '
                             f"(choices: 'bf16', 'int8')")
        if kv_quant and decode and page_indices is None:
            raise ValueError(
                'kv_dtype=int8 requires the paged KV cache (the dense '
                'per-slot cache has no scale storage); serve with '
                '--continuous-batching and a paged-capable pool')

        def _page_vars():
            layout = cfg.page_layout()
            k_pages, v_pages = (
                self.variable('cache', a.name, jnp.zeros, layout.shape(a),
                              jnp.int8 if kv_quant else cfg.dtype)
                for a in layout.arrays)
            if not kv_quant:
                return k_pages, v_pages, None, None
            # Parallel scale pages: one f32 per cached token (page
            # slot), shared across KV heads — scales travel with
            # their physical page so alloc/free/prefix-sharing need
            # no storage-format awareness.
            sshape = (cfg.kv_total_pages, cfg.kv_page_size)
            return (k_pages, v_pages,
                    self.variable('cache', 'k_scales', jnp.zeros,
                                  sshape, jnp.float32),
                    self.variable('cache', 'v_scales', jnp.zeros,
                                  sshape, jnp.float32))

        if decode and seq > 1:
            # CHUNKED decode: many tokens in one forward pass, both
            # paged and dense — `prefill` (static) selects chunk-local
            # attention (empty-cache contract, flash-eligible);
            # otherwise the chunk attends the full history (speculative
            # verification chunks at arbitrary per-row offsets).
            # `page_aligned` (static): the caller's promise that the
            # chunk starts on a page boundary (every prefill chunk),
            # so the pool takes it page by page (write_kv_chunk).
            if page_indices is not None:
                from skypilot_tpu.ops import paged_attention as paged_ops
                k_pages, v_pages, k_sc, v_sc = _page_vars()
                if kv_quant:
                    (k_pages.value, v_pages.value, k_sc.value,
                     v_sc.value) = paged_ops.write_kv_chunk_quant(
                        k_pages.value, v_pages.value, k_sc.value,
                        v_sc.value, k, v, positions, page_indices,
                        page_aligned=page_aligned)
                else:
                    k_pages.value, v_pages.value = \
                        paged_ops.write_kv_chunk(
                            k_pages.value, v_pages.value, k, v,
                            positions, page_indices,
                            page_aligned=page_aligned)
                if prefill:
                    # Chunk-local attention reads the chunk's own
                    # bf16 K/V (exact); later chunks/decodes read the
                    # quantized pages — the storage contract.
                    out = attention_ops.dot_product_attention(
                        q, k, v, causal=True)
                else:
                    out = paged_ops.paged_chunk_attention(
                        q, k_pages.value, v_pages.value, positions,
                        page_indices,
                        k_scales=k_sc.value if kv_quant else None,
                        v_scales=v_sc.value if kv_quant else None,
                        ).astype(cfg.dtype)
            else:
                cached_k = self.variable(
                    'cache', 'cached_key', jnp.zeros,
                    (batch, cfg.max_seq_len, cfg.num_kv_heads, hd),
                    cfg.dtype)
                cached_v = self.variable(
                    'cache', 'cached_value', jnp.zeros,
                    (batch, cfg.max_seq_len, cfg.num_kv_heads, hd),
                    cfg.dtype)
                # `prefill` (static): the caller guarantees the cache
                # holds nothing below this chunk, so attention stays
                # chunk-local (S x S, flash-eligible) instead of
                # materializing S x max_seq_len f32 scores.
                out, cached_k.value, cached_v.value = \
                    attention_ops.chunked_cache_attention(
                        q, k, v, cached_k.value, cached_v.value,
                        positions, chunk_only=prefill)
                out = out.astype(cfg.dtype)
        elif decode:
            # Incremental decoding: one token in, KV cache with PER-ROW
            # write positions — the shared serving-cache contract
            # (ops.attention.cached_decode_attention), which is what
            # lets continuous batching decode slots at different depths
            # in one step (models/batching.py).
            if page_indices is not None:
                # Paged KV (vLLM-style): K/V live in a shared physical
                # page pool; this sequence's pages come from the
                # engine-provided table (ops/paged_attention.py).
                from skypilot_tpu.ops import paged_attention as paged_ops
                k_pages, v_pages, k_sc, v_sc = _page_vars()
                if kv_quant:
                    (k_pages.value, v_pages.value, k_sc.value,
                     v_sc.value) = paged_ops.write_kv_quant(
                        k_pages.value, v_pages.value, k_sc.value,
                        v_sc.value, k[:, 0], v[:, 0],
                        positions[:, 0], page_indices)
                else:
                    k_pages.value, v_pages.value = paged_ops.write_kv(
                        k_pages.value, v_pages.value, k[:, 0], v[:, 0],
                        positions[:, 0], page_indices)
                out = paged_ops.paged_decode_attention(
                    q[:, 0], k_pages.value, v_pages.value,
                    lengths=positions[:, 0] + 1,
                    page_indices=page_indices,
                    k_scales=k_sc.value if kv_quant else None,
                    v_scales=v_sc.value if kv_quant else None)
                out = out[:, None].astype(cfg.dtype)
            else:
                cached_k = self.variable(
                    'cache', 'cached_key', jnp.zeros,
                    (batch, cfg.max_seq_len, cfg.num_kv_heads, hd),
                    cfg.dtype)
                cached_v = self.variable(
                    'cache', 'cached_value', jnp.zeros,
                    (batch, cfg.max_seq_len, cfg.num_kv_heads, hd),
                    cfg.dtype)
                out, cached_k.value, cached_v.value = \
                    attention_ops.cached_decode_attention(
                        q, k, v, cached_k.value, cached_v.value,
                        positions[:, 0])
                out = out.astype(cfg.dtype)
        else:
            q = nn.with_logical_constraint(q,
                                           ('batch', 'seq', 'heads', 'kv'))
            k = nn.with_logical_constraint(k,
                                           ('batch', 'seq', 'heads', 'kv'))
            v = nn.with_logical_constraint(v,
                                           ('batch', 'seq', 'heads', 'kv'))
            out = attention_ops.dot_product_attention(q, k, v, causal=True)
        out = out.reshape(batch, seq, cfg.num_heads * hd)
        return _lora('wo',
                     _proj(cfg.embed_dim, ('heads', 'embed'), cfg.dtype,
                           'wo')(out), out)


class FeedForward(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 lora: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 lora_scale: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config

        def _lora(name, y, inp):
            if lora is None or name not in lora:
                return y
            return lora_lib.apply_delta(y, inp, lora[name],
                                        adapter_ids, lora_scale)

        gate = _lora('w_gate',
                     _proj(cfg.mlp_dim, ('embed', 'mlp'), cfg.dtype,
                           'w_gate')(x), x)
        up = _lora('w_up',
                   _proj(cfg.mlp_dim, ('embed', 'mlp'), cfg.dtype,
                         'w_up')(x), x)
        h = nn.silu(gate) * up
        h = nn.with_logical_constraint(h, ('batch', 'seq', 'mlp'))
        return _lora('w_down',
                     _proj(cfg.embed_dim, ('mlp', 'embed'), cfg.dtype,
                           'w_down')(h), h)


class Block(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 lora: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 lora_scale: Optional[jax.Array] = None,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        x = x + Attention(cfg, name='attn')(
            RMSNorm(cfg.norm_eps, cfg.dtype, name='attn_norm')(x), positions,
            decode, page_indices, prefill, lora, adapter_ids, lora_scale,
            page_aligned)
        x = x + FeedForward(cfg, name='mlp')(
            RMSNorm(cfg.norm_eps, cfg.dtype, name='mlp_norm')(x),
            lora, adapter_ids, lora_scale)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))


def embed_tokens(params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Functional input embedding (shared with the pipeline trainer's
    stage-0 op, parallel/pipeline.py — mirrors gpt.embed_tokens)."""
    return params['tok_embed'].astype(cfg.dtype)[tokens]


@jax.named_scope('lm_head')
def final_norm_logits(params, x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Functional final RMSNorm + untied LM head (the pipeline
    trainer's last-stage op; numerics mirror Llama.__call__)."""
    scale = params['final_norm']['scale'].astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x_n = (x32 * jax.lax.rsqrt(var + cfg.norm_eps) * scale).astype(
        cfg.dtype)
    return jnp.einsum('bse,ev->bsv', x_n,
                      params['lm_head'].astype(cfg.dtype),
                      preferred_element_type=(cfg.logits_dtype or
                                              jnp.float32))


class Llama(nn.Module):
    """Llama decoder; __call__ returns logits [B, S, vocab] (f32).

    `return_hidden=True` returns the post-final_norm hidden states
    [B, S, embed] instead — the trainer's fused blockwise loss
    (ops/fused_xent.py) consumes them against `lm_head` directly, so
    the [B, S, vocab] logits (the HBM high-water mark at 128k+
    vocabs) are never formed.
    """
    config: LlamaConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 return_hidden: bool = False,
                 lora: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        batch, seq = tokens.shape
        # `lora` = {'scale': f32, 'layers': {'layer_i': {target:
        # {'a', 'b'}}}} (models/lora.py). Per-layer factors thread
        # into each block; `adapter_ids` [batch] selects each row's
        # adapter from stacked factors (None = single-adapter mode).
        lora_scale = lora['scale'] if lora is not None else None
        lora_layers = lora['layers'] if lora is not None else {}
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        embed = self.param(
            'tok_embed',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'table_embed')),
            (cfg.vocab_size, cfg.embed_dim), jnp.float32)
        x = embed.astype(cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))

        block = Block
        if cfg.remat:
            block = nn.remat(Block, prevent_cse=False,
                             static_argnums=(3, 5, 9))
        for i in range(cfg.num_layers):
            x = block(cfg, name=f'layer_{i}')(x, positions, decode,
                                              page_indices, prefill,
                                              lora_layers.get(f'layer_{i}'),
                                              adapter_ids, lora_scale,
                                              page_aligned)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'vocab')),
            (cfg.embed_dim, cfg.vocab_size), jnp.float32)
        if return_hidden:
            # Head param is registered above so init() is identical
            # with or without the fused-loss path.
            return nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
        # bf16 operands, accumulation dtype from cfg.logits_dtype
        # (None = f32: MXU-native rate, f32-safe softmax numerics).
        with jax.named_scope('lm_head'):
            logits = jnp.einsum(
                'bse,ev->bsv', x.astype(cfg.dtype),
                head.astype(cfg.dtype),
                preferred_element_type=(cfg.logits_dtype or
                                        jnp.float32))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))


class LlamaStage(nn.Module):
    """One pipeline stage of the Llama decoder (staged serving).

    Runs layers [lo, hi) with ABSOLUTE layer names (`layer_{i}`), so a
    full `Llama` param/cache tree splits into per-stage trees by key
    and the wire-format keys of the paged KV pool (kv_transfer chain
    export) are the union of the stage trees — identical to the
    unstaged layout. The first stage owns `tok_embed` and maps tokens
    [B, S] -> hidden [B, S, embed]; the last stage owns `final_norm` +
    `lm_head` and maps hidden -> logits [B, S, vocab]; interior stages
    are hidden -> hidden. Layer application is sequential and
    dtype-identical to `Llama.__call__`, so chaining the S stages on
    the same weights reproduces the full model bit-for-bit.
    """
    config: LlamaConfig
    lo: int
    hi: int
    first: bool
    last: bool

    @nn.compact
    def __call__(self, x: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 lora: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        # The WHOLE lora stack threads through every stage; each stage
        # gathers only its own layers' factors below (the rest are
        # dead inputs XLA drops), so the engine passes one pytree.
        lora_scale = lora['scale'] if lora is not None else None
        lora_layers = lora['layers'] if lora is not None else {}
        if self.first:
            tokens = x
            batch, seq = tokens.shape
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(seq),
                                             (batch, seq))
            embed = self.param(
                'tok_embed',
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02),
                    ('vocab', 'table_embed')),
                (cfg.vocab_size, cfg.embed_dim), jnp.float32)
            x = embed.astype(cfg.dtype)[tokens]
        else:
            batch, seq = x.shape[:2]
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(seq),
                                             (batch, seq))
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))

        block = Block
        if cfg.remat:
            block = nn.remat(Block, prevent_cse=False,
                             static_argnums=(3, 5, 9))
        for i in range(self.lo, self.hi):
            x = block(cfg, name=f'layer_{i}')(x, positions, decode,
                                              page_indices, prefill,
                                              lora_layers.get(f'layer_{i}'),
                                              adapter_ids, lora_scale,
                                              page_aligned)
        if not self.last:
            return x
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'vocab')),
            (cfg.embed_dim, cfg.vocab_size), jnp.float32)
        with jax.named_scope('lm_head'):
            logits = jnp.einsum(
                'bse,ev->bsv', x.astype(cfg.dtype),
                head.astype(cfg.dtype),
                preferred_element_type=(cfg.logits_dtype or
                                        jnp.float32))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))
