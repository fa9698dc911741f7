"""Mixtral-family sparse-MoE decoder with expert parallelism.

Recipe model #3 (BASELINE.md config 5: Mixtral 8x7B expert-parallel on
v5p-128). Llama backbone (RMSNorm/RoPE/GQA) with a top-k routed MoE
FFN. Experts live in stacked weights with a leading `expert` logical
axis → sharded over the mesh's `expert` axis; token dispatch/combine
are capacity-bounded einsums (the TPU-native MoE formulation — XLA
lowers the sharded einsums to all-to-alls over ICI), not per-expert
Python loops.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama as llama_lib

Dtype = Any


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    max_seq_len: int = 8192
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    embed_dim: int = 4096
    mlp_dim: int = 14336
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_loss_weight: float = 0.02
    rope_theta: float = 1_000_000.0
    rope_scaling: Optional[llama_lib.RopeScaling] = None
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    # LM-head logits precision; None = f32 (see llama.LlamaConfig).
    logits_dtype: Optional[Dtype] = None
    remat: bool = False
    # Paged KV cache for serving (see llama.LlamaConfig).
    kv_page_size: int = 16
    kv_total_pages: int = 128

    @classmethod
    def mixtral_8x7b(cls, **kw) -> 'MixtralConfig':
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> 'MixtralConfig':
        return cls(vocab_size=512, max_seq_len=256, num_layers=2,
                   num_heads=4, num_kv_heads=2, embed_dim=128, mlp_dim=256,
                   num_experts=4, experts_per_token=2, **kw)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def page_layout(self):
        """A cached token's row in the page pool: K and V."""
        return self.as_llama().page_layout()

    def as_llama(self) -> llama_lib.LlamaConfig:
        return llama_lib.LlamaConfig(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, embed_dim=self.embed_dim,
            mlp_dim=self.mlp_dim, rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            norm_eps=self.norm_eps, dtype=self.dtype, remat=self.remat,
            kv_page_size=self.kv_page_size,
            kv_total_pages=self.kv_total_pages)


class MoEFeedForward(nn.Module):
    """Top-k routed SwiGLU experts via capacity-bounded dispatch."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        batch, seq, dim = x.shape
        num_exp, top_k = cfg.num_experts, cfg.experts_per_token

        router = nn.Dense(
            num_exp, use_bias=False, dtype=jnp.float32, name='router',
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'expert')))
        gate_logits = router(x.astype(jnp.float32))          # [B,S,E]
        gate_probs = jax.nn.softmax(gate_logits, axis=-1)

        # Top-k routing weights, renormalized over the chosen experts.
        top_w, top_idx = jax.lax.top_k(gate_probs, top_k)    # [B,S,K]
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        # Capacity per expert (tokens an expert processes per batch row).
        capacity = int(cfg.capacity_factor * seq * top_k / num_exp)
        capacity = max(capacity, top_k)

        # Build dispatch/combine tensors [B,S,E,C].
        expert_onehot = jax.nn.one_hot(top_idx, num_exp,
                                       dtype=jnp.float32)   # [B,S,K,E]
        # Position of each (token, k) within its expert's queue:
        # cumulative count of prior assignments to the same expert.
        flat = expert_onehot.reshape(batch, seq * top_k, num_exp)
        positions = jnp.cumsum(flat, axis=1) - flat          # [B,S*K,E]
        positions = positions.reshape(batch, seq, top_k, num_exp)
        within_capacity = positions < capacity
        pos_onehot = jax.nn.one_hot(
            jnp.sum(positions * expert_onehot, axis=-1).astype(jnp.int32),
            capacity, dtype=jnp.float32)                     # [B,S,K,C]
        dispatch = jnp.einsum(
            'bske,bskc->bsec',
            expert_onehot * within_capacity.astype(jnp.float32),
            pos_onehot)                                      # [B,S,E,C]
        combine = jnp.einsum('bsk,bske,bskc->bsec',
                             top_w,
                             expert_onehot *
                             within_capacity.astype(jnp.float32),
                             pos_onehot)

        dispatch = nn.with_logical_constraint(
            dispatch, ('batch', 'seq', 'expert', None))
        # Route tokens to experts: [E,B,C,D] — expert-major layout puts
        # the all-to-all on the expert axis.
        expert_in = jnp.einsum('bsec,bsd->ebcd', dispatch,
                               x.astype(jnp.float32)).astype(cfg.dtype)
        expert_in = nn.with_logical_constraint(
            expert_in, ('expert', 'batch', None, 'act_embed'))

        def stacked(name: str, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), axes),
                shape, jnp.float32).astype(cfg.dtype)

        w_gate = stacked('w_gate', (num_exp, dim, cfg.mlp_dim),
                         ('expert', 'embed', 'mlp'))
        w_up = stacked('w_up', (num_exp, dim, cfg.mlp_dim),
                       ('expert', 'embed', 'mlp'))
        w_down = stacked('w_down', (num_exp, cfg.mlp_dim, dim),
                         ('expert', 'mlp', 'embed'))

        h = nn.silu(jnp.einsum('ebcd,edf->ebcf', expert_in, w_gate)) * \
            jnp.einsum('ebcd,edf->ebcf', expert_in, w_up)
        h = nn.with_logical_constraint(h, ('expert', 'batch', None, 'mlp'))
        expert_out = jnp.einsum('ebcf,efd->ebcd', h, w_down)

        out = jnp.einsum('bsec,ebcd->bsd',
                         combine, expert_out.astype(jnp.float32))
        out = out.astype(cfg.dtype)

        # Load-balancing auxiliary loss (Switch-style): mean prob x
        # mean assignment fraction per expert.
        assign_frac = jnp.mean(
            jnp.sum(expert_onehot, axis=2), axis=(0, 1))     # [E]
        prob_frac = jnp.mean(gate_probs, axis=(0, 1))        # [E]
        aux_loss = num_exp * jnp.sum(assign_frac * prob_frac) / top_k
        return out, aux_loss


class Block(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        lcfg = cfg.as_llama()
        x = x + llama_lib.Attention(lcfg, name='attn')(
            llama_lib.RMSNorm(cfg.norm_eps, cfg.dtype, name='attn_norm')(x),
            positions, decode=decode, page_indices=page_indices,
            prefill=prefill, page_aligned=page_aligned)
        moe_out, aux = MoEFeedForward(cfg, name='moe')(
            llama_lib.RMSNorm(cfg.norm_eps, cfg.dtype, name='moe_norm')(x))
        x = x + moe_out
        return nn.with_logical_constraint(
            x, ('batch', 'seq', 'act_embed')), aux


class Mixtral(nn.Module):
    """Returns (logits [B,S,V] f32, aux_loss scalar)."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 return_hidden: bool = False,
                 page_aligned: bool = False):
        """Training: (logits, aux_loss). decode=True (serving): logits
        only — the KV-cache path of the shared llama attention, so the
        generate/continuous-batching engines drive Mixtral unchanged.
        `return_hidden=True` swaps logits for the post-final_norm
        hidden states (the fused-loss path, ops/fused_xent.py)."""
        cfg = self.config
        batch, seq = tokens.shape
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
            assert not decode, 'remat is a training-path option'
            block = nn.remat(Block, prevent_cse=False)
        total_aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            x, aux = block(cfg, name=f'layer_{i}')(x, positions,
                                                   decode=decode,
                                                   page_indices=page_indices,
                                                   prefill=prefill,
                                                   page_aligned=page_aligned)
            total_aux = total_aux + aux
        x = llama_lib.RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'vocab')),
            (cfg.embed_dim, cfg.vocab_size), jnp.float32)
        if return_hidden:
            hidden = nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
            if decode:
                return hidden
            aux_loss = (cfg.router_aux_loss_weight * total_aux /
                        cfg.num_layers)
            return hidden, aux_loss
        # bf16 operands; accumulation dtype from cfg.logits_dtype
        # (None = f32 — same knob as the other families).
        logits = jnp.einsum('bse,ev->bsv', x.astype(cfg.dtype),
                            head.astype(cfg.dtype),
                            preferred_element_type=(cfg.logits_dtype or
                                                    jnp.float32))
        logits = nn.with_logical_constraint(logits,
                                            ('batch', 'seq', 'vocab'))
        if decode:
            return logits  # aux loss is a training-only signal
        aux_loss = cfg.router_aux_loss_weight * total_aux / cfg.num_layers
        return logits, aux_loss


def moe_next_token_loss(outputs, tokens: jax.Array) -> jax.Array:
    """Loss fn for ShardedTrainer: CE + router aux loss."""
    from skypilot_tpu.parallel.train import next_token_loss
    logits, aux_loss = outputs
    return next_token_loss(logits, tokens) + aux_loss


# The fused blockwise-xent trainer path handles the (hidden, aux)
# tuple generically — flag this loss as fused-compatible so
# ShardedTrainer's auto-detection keeps Mixtral on the fast path.
moe_next_token_loss.fused_ok = True
