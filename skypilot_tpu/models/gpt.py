"""GPT-2 (nanoGPT-class) in flax.linen with logical sharding axes.

Recipe model #1 (BASELINE.md config 1). Every parameter carries
logical axis names (`embed`, `mlp`, `heads`, `vocab`, ...) via
`nn.with_logical_partitioning`; `parallel/train.py` maps them onto a
mesh (DP×FSDP×TP) with `parallel/mesh.py` rules. Compute is bf16,
params f32 (standard mixed precision for the MXU).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.ops import attention as attention_ops

Dtype = Any


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # nanoGPT's padded GPT-2 vocab
    block_size: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    dropout_rate: float = 0.0
    # GPT-2's LayerNorm epsilon (HF layer_norm_epsilon); flax's default
    # is 1e-6 — matching 1e-5 matters for HF-checkpoint parity.
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # Logits match the compute dtype unless overridden. bf16 logits
    # halve the LM head's HBM traffic — at GPT-2 scale the [B,S,50k]
    # logits are the largest array in the step. The loss upcasts to f32
    # inside its logsumexp fusion (parallel/train.py), so softmax
    # numerics stay f32 without an f32 array in HBM.
    logits_dtype: Optional[Dtype] = None
    remat: bool = False
    # Paged KV cache for serving (see llama.LlamaConfig).
    kv_page_size: int = 16
    kv_total_pages: int = 128

    @classmethod
    def gpt2_124m(cls, **kw) -> 'GPTConfig':
        return cls(num_layers=12, num_heads=12, embed_dim=768, **kw)

    @classmethod
    def tiny(cls, **kw) -> 'GPTConfig':
        return cls(vocab_size=512, block_size=128, num_layers=2,
                   num_heads=4, embed_dim=128, **kw)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def page_layout(self):
        """A cached token's row in the page pool: K and V."""
        from skypilot_tpu.ops import paged_attention as paged_ops
        return paged_ops.kv_layout(self.num_heads, self.head_dim,
                                   self.kv_page_size, self.kv_total_pages)

    @property
    def max_seq_len(self) -> int:
        """Alias matching the llama/mixtral configs (serving engines
        read model.config.max_seq_len)."""
        return self.block_size

    def num_params(self) -> int:
        wpe = self.block_size * self.embed_dim
        wte = self.vocab_size * self.embed_dim
        per_layer = (12 * self.embed_dim ** 2 + 13 * self.embed_dim)
        return wte + wpe + self.num_layers * per_layer + 2 * self.embed_dim


def _dense(features: int, logical_axes, dtype, name: str,
           use_bias: bool = True) -> nn.Dense:
    return nn.Dense(
        features, dtype=dtype, use_bias=use_bias, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), logical_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (logical_axes[-1],)))


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        batch, seq, _ = x.shape
        qkv = _dense(3 * cfg.embed_dim, ('embed', 'mlp'), cfg.dtype,
                     'c_attn')(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (batch, seq, cfg.num_heads, cfg.head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        def _page_vars():
            layout = cfg.page_layout()
            return tuple(self.variable('cache', a.name, jnp.zeros,
                                       layout.shape(a), cfg.dtype)
                         for a in layout.arrays)

        if decode and seq > 1:
            # CHUNKED decode (same contract as models/llama.py):
            # `prefill` (static) = chunk-local attention; otherwise the
            # chunk attends the full history (speculative verification).
            assert positions is not None
            if page_indices is not None:
                from skypilot_tpu.ops import paged_attention as paged_ops
                k_pages, v_pages = _page_vars()
                k_pages.value, v_pages.value = paged_ops.write_kv_chunk(
                    k_pages.value, v_pages.value, k, v, positions,
                    page_indices, page_aligned=page_aligned)
                if prefill:
                    out = attention_ops.dot_product_attention(
                        q, k, v, causal=True)
                else:
                    out = paged_ops.paged_chunk_attention(
                        q, k_pages.value, v_pages.value, positions,
                        page_indices).astype(cfg.dtype)
            else:
                cached_k = self.variable(
                    'cache', 'cached_key', jnp.zeros,
                    (batch, cfg.block_size, cfg.num_heads, cfg.head_dim),
                    cfg.dtype)
                cached_v = self.variable(
                    'cache', 'cached_value', jnp.zeros,
                    (batch, cfg.block_size, cfg.num_heads, cfg.head_dim),
                    cfg.dtype)
                # `prefill` (static): empty-cache contract — attention
                # stays chunk-local (S x S, flash-eligible) instead of
                # S x block_size f32 scores.
                out, cached_k.value, cached_v.value = \
                    attention_ops.chunked_cache_attention(
                        q, k, v, cached_k.value, cached_v.value,
                        positions, chunk_only=prefill)
                out = out.astype(cfg.dtype)
        elif decode:
            # One token in, KV cache with a PER-ROW write index
            # (positions[:, 0]) — the shared serving-cache contract
            # (ops.attention.cached_decode_attention), so the generate
            # and continuous-batching engines drive GPT unchanged.
            assert positions is not None
            if page_indices is not None:
                # Paged KV (same contract as models/llama.py).
                from skypilot_tpu.ops import paged_attention as paged_ops
                k_pages, v_pages = _page_vars()
                k_pages.value, v_pages.value = paged_ops.write_kv(
                    k_pages.value, v_pages.value, k[:, 0], v[:, 0],
                    positions[:, 0], page_indices)
                out = paged_ops.paged_decode_attention(
                    q[:, 0], k_pages.value, v_pages.value,
                    lengths=positions[:, 0] + 1,
                    page_indices=page_indices)
                out = out[:, None].astype(cfg.dtype)
            else:
                cached_k = self.variable(
                    'cache', 'cached_key', jnp.zeros,
                    (batch, cfg.block_size, cfg.num_heads, cfg.head_dim),
                    cfg.dtype)
                cached_v = self.variable(
                    'cache', 'cached_value', jnp.zeros,
                    (batch, cfg.block_size, cfg.num_heads, cfg.head_dim),
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
        out = out.reshape((batch, seq, cfg.embed_dim))
        out = _dense(cfg.embed_dim, ('mlp', 'embed'), cfg.dtype, 'c_proj')(out)
        if cfg.dropout_rate > 0:
            out = nn.Dropout(cfg.dropout_rate)(out, deterministic)
        return out


class MLP(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        cfg = self.config
        h = _dense(4 * cfg.embed_dim, ('embed', 'mlp'), cfg.dtype, 'c_fc')(x)
        h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ('batch', 'seq', 'mlp'))
        h = _dense(cfg.embed_dim, ('mlp', 'embed'), cfg.dtype, 'c_proj')(h)
        if cfg.dropout_rate > 0:
            h = nn.Dropout(cfg.dropout_rate)(h, deterministic)
        return h


class Block(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        ln = lambda name: nn.LayerNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name,
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ('norm',)),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ('norm',)))
        x = x + CausalSelfAttention(cfg, name='attn')(
            ln('ln_1')(x), deterministic, positions=positions,
            decode=decode, page_indices=page_indices, prefill=prefill,
            page_aligned=page_aligned)
        x = x + MLP(cfg, name='mlp')(ln('ln_2')(x), deterministic)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))


def embed_tokens(params, tokens: jax.Array, cfg: GPTConfig) -> jax.Array:
    """Functional form of GPT's input embedding (wte + wpe over
    training positions). Shared with the pipeline trainer's stage-0 op
    (parallel/pipeline.py) so head/embedding changes cannot silently
    diverge between the sequential and pipelined paths."""
    wte = params['wte'].astype(cfg.dtype)
    wpe = params['wpe'].astype(cfg.dtype)
    return wte[tokens] + wpe[:tokens.shape[1]]


@jax.named_scope('lm_head')
def final_norm_logits(params, x: jax.Array, cfg: GPTConfig) -> jax.Array:
    """Functional form of GPT's ln_f + tied LM head (the pipeline
    trainer's last-stage op)."""
    scale = params['ln_f']['scale'].astype(jnp.float32)
    bias = params['ln_f']['bias'].astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    x_n = ((x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps) * scale +
           bias).astype(cfg.dtype)
    return jnp.einsum('bse,ve->bsv', x_n, params['wte'].astype(cfg.dtype),
                      preferred_element_type=(cfg.logits_dtype or
                                              cfg.dtype))


class GPT(nn.Module):
    """GPT-2 decoder; __call__ returns logits [B, S, vocab].

    `return_hidden=True` returns the post-ln_f hidden states
    [B, S, embed] instead, skipping the LM-head matmul entirely — the
    trainer's fused blockwise cross-entropy (ops/fused_xent.py) takes
    it from there against the tied `wte` without ever materializing
    [B, S, vocab].
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 deterministic: bool = True,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 return_hidden: bool = False,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        batch, seq = tokens.shape
        assert seq <= cfg.block_size, (seq, cfg.block_size)
        explicit_positions = positions is not None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        wte = self.param(
            'wte',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('vocab', 'table_embed')),
            (cfg.vocab_size, cfg.embed_dim), jnp.float32)
        wpe = self.param(
            'wpe',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.01), ('seq', 'table_embed')),
            (cfg.block_size, cfg.embed_dim), jnp.float32)
        # Training fast path: the default positions are a broadcast
        # arange — slice wpe instead of a batch-sized gather.
        pos_embed = (wpe.astype(cfg.dtype)[positions] if explicit_positions
                     else wpe.astype(cfg.dtype)[:seq])
        x = wte.astype(cfg.dtype)[tokens] + pos_embed
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))

        if cfg.remat:
            assert not decode, 'remat is a training-path option'
            # decode stays OUT of the remat arg list: jax.checkpoint
            # would trace the bool and break Python-level branching.
            block = nn.remat(Block, prevent_cse=False,
                             static_argnums=(2,))
            for i in range(cfg.num_layers):
                x = block(cfg, name=f'h_{i}')(x, deterministic, positions)
        else:
            for i in range(cfg.num_layers):
                x = Block(cfg, name=f'h_{i}')(x, deterministic,
                                              positions=positions,
                                              decode=decode,
                                              page_indices=page_indices,
                                              prefill=prefill,
                                              page_aligned=page_aligned)
        x = nn.LayerNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype, name='ln_f',
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ('norm',)),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ('norm',)))(x)
        if return_hidden:
            return nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
        # Tied output head (nanoGPT style): logits = x @ wte^T. bf16
        # operands keep the matmul on the MXU's native bf16 path
        # (~4-8x the f32 rate); cfg.logits_dtype picks the output
        # precision (bf16 default — see GPTConfig).
        with jax.named_scope('lm_head'):
            logits = jnp.einsum(
                'bse,ve->bsv', x.astype(cfg.dtype),
                wte.astype(cfg.dtype),
                preferred_element_type=(cfg.logits_dtype or
                                        cfg.dtype))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))
