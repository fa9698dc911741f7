"""DeepSeek-family decoder: Multi-head Latent Attention (MLA), and for
DeepSeek-V3.2 the lightning indexer and routed experts by share.

MLA compresses the KV cache into a per-token latent (`kv_lora_rank`
dims) plus a small shared rotary key (`rope_head_dim` dims) — 576
cached values a token and layer where Llama-3-8B caches 2048. The
serving paths use the ABSORBED formulation (score = (W_uk^T q)·c,
output = W_uv (Σ p·c)): attention runs directly against the cached
latent and the per-head K/V are never materialized.

Two cache forms. Without a page pool (`page_indices` None) the latent
lives in a dense per-slot cache (DeepSeek-V2-Lite as the registry has
had it). With one, the MODEL supplies the pool's layout
(`DeepseekConfig.page_layout()`, ops/paged_attention.PageLayout kind
'latent'): a `latent_pages` array holding kv_lora_rank + rope_head_dim
values (`latent_width`: V3's 576 in a 640-wide row) and, with an
indexer, an `index_k_pages` array of width `index_head_dim`, a layer;
the engine allocates, writes in place, shares prefixes and pipelines
them as it does K/V pages, and the three reads are
ops/sparse_latent.py.

DeepSeek-V3.2 (`index_n_heads` > 0, `n_routed_experts` > 0) adds:
  - q through a low-rank bottleneck (`q_lora_rank`), YaRN frequencies
    and its softmax scale, RoPE on interleaved pairs;
  - the lightning indexer: `index_n_heads` small heads score every
    earlier token, I(t,s) = Σ_j w_j ReLU(q_j·k_s) in float32, and
    attention runs over the `index_topk` best only (all of them below
    that many);
  - `MoEByShare`: sigmoid scores, group-limited choice with the
    `e_score_correction_bias`, 8 of 256 experts a token; the layer is
    TOLD which experts it holds (`expert_offset`, `experts_held`),
    routes over all of them, normalizes over all chosen and adds its
    own experts' parts and the shared expert. Dropless: tokens sorted
    by expert, one grouped pass over the experts that received any,
    the tokens per expert known on the device. What absent experts
    would add is left out (one chip's share of an expert-parallel
    deployment; no code stands in for the other chips).

The reference orchestrator ships DeepSeek only as a user recipe
(`llm/deepseek-r1/`); here the family is a first-class model with the
same logical-axis sharding scheme as models/{gpt,llama,mixtral}.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.llama import (FeedForward as SwiGLU, RMSNorm,
                                       _proj)
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import paged_attention as paged_ops
from skypilot_tpu.ops import pallas_experts
from skypilot_tpu.ops import sparse_latent

Dtype = Any


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """HF config.json `rope_scaling` of type `yarn` (V3's values)."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 102400
    max_seq_len: int = 4096
    num_layers: int = 27
    num_heads: int = 16
    embed_dim: int = 2048
    mlp_dim: int = 10944
    # MLA dims (DeepSeek-V2-Lite defaults): latent cache rank, the
    # decoupled rotary dims, and the no-position ("nope") head dims.
    kv_lora_rank: int = 512
    q_lora_rank: int = 0        # 0 = full-rank queries (V2-Lite)
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_theta: float = 10_000.0
    # YaRN (V3): blended inverse frequencies, always on as at the
    # published context, and mscale^2 on the softmax scale.
    rope_scaling: Optional[YarnScaling] = None
    # RoPE pairs of MLA's rotary dims: (x0,x1),(x2,x3).. as V3
    # publishes, or the two halves (V2-Lite as the registry has it).
    rope_interleaved: bool = False
    norm_eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    # LM-head logits precision; None = f32 (see llama.LlamaConfig).
    logits_dtype: Optional[Dtype] = None
    remat: bool = False
    # Lightning indexer (V3.2): 0 heads = every causal position is
    # attended (dense MLA).
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 2048
    # Routed experts (V3): 0 = a dense SwiGLU of `mlp_dim` in every
    # layer. `n_routed_experts` is the ROUTER's width, the published
    # count; this chip holds `experts_held` of them from
    # `expert_offset` on (0 held = all). The first `first_k_dense`
    # layers keep the dense SwiGLU.
    n_routed_experts: int = 0
    num_experts_per_tok: int = 8
    moe_dim: int = 2048
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    first_k_dense: int = 1
    experts_held: int = 0
    expert_offset: int = 0
    # Page pool for serving (see llama.LlamaConfig); 0 pages = the
    # dense per-slot latent cache. bf16 rows only.
    kv_page_size: int = 16
    kv_total_pages: int = 0
    kv_dtype: str = 'bf16'

    @classmethod
    def v2_lite(cls, **kw) -> 'DeepseekConfig':
        return cls(**kw)

    @classmethod
    def v32(cls, **kw) -> 'DeepseekConfig':
        """DeepSeek-V3.2 as published (config.json, `deepseek_v32`)."""
        base = dict(
            vocab_size=129280, max_seq_len=163840, num_layers=61,
            num_heads=128, embed_dim=7168, mlp_dim=18432,
            kv_lora_rank=512, q_lora_rank=1536, rope_head_dim=64,
            nope_head_dim=128, v_head_dim=128,
            rope_scaling=YarnScaling(), rope_interleaved=True,
            index_n_heads=64, index_head_dim=128, index_topk=2048,
            n_routed_experts=256, num_experts_per_tok=8, moe_dim=2048,
            n_shared_experts=1, n_group=8, topk_group=4,
            routed_scaling_factor=2.5, first_k_dense=3)
        base.update(kw)
        return cls(**base)

    @classmethod
    def v32_l5_ep16(cls, **kw) -> 'DeepseekConfig':
        """One chip's share of a 16-way expert-parallel deployment at
        every published width: experts 0-15 of 256 held, an eighth of
        the vocabulary, one dense and four expert layers
        (perfbench/configs/deepseek-v32-l5-ep16.json has the cut)."""
        base = dict(num_layers=5, first_k_dense=1, experts_held=16,
                    expert_offset=0, vocab_size=16160, max_seq_len=16384,
                    kv_total_pages=2048)
        base.update(kw)
        return cls.v32(**base)

    @classmethod
    def v32_tiny(cls, **kw) -> 'DeepseekConfig':
        """Every V3.2 mechanism at a size a CPU test holds: one dense
        and two expert layers, 16 routed experts in 4 groups (2 groups
        and 4 experts chosen), `index_topk` 16, YaRN on. Both pool
        arrays are one lane tile wide (112 + 16, and 128), as the
        published widths are whole tiles: narrower ones XLA:TPU lays
        out anew, with a pool-shaped copy (chip_smoke.py, PR 33)."""
        base = dict(
            vocab_size=512, max_seq_len=256, num_layers=3, num_heads=4,
            embed_dim=128, mlp_dim=384, kv_lora_rank=112, q_lora_rank=48,
            rope_head_dim=16, nope_head_dim=32, v_head_dim=32,
            rope_scaling=YarnScaling(
                factor=4.0, original_max_position_embeddings=64),
            rope_interleaved=True, index_n_heads=4, index_head_dim=128,
            index_topk=16, n_routed_experts=16, num_experts_per_tok=4,
            moe_dim=64, n_group=4, topk_group=2, first_k_dense=1,
            experts_held=16, kv_total_pages=128)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw) -> 'DeepseekConfig':
        return cls(vocab_size=512, max_seq_len=256, num_layers=2,
                   num_heads=4, embed_dim=128, mlp_dim=384,
                   kv_lora_rank=32, q_lora_rank=0, rope_head_dim=16,
                   nope_head_dim=32, v_head_dim=32, **kw)

    @property
    def qk_head_dim(self) -> int:
        return self.nope_head_dim + self.rope_head_dim

    @property
    def num_held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def softmax_scale(self) -> float:
        """qk_head_dim^-1/2, times YaRN's mscale^2."""
        scale = self.qk_head_dim ** -0.5
        y = self.rope_scaling
        if y is not None and y.factor > 1:
            mscale = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
            scale *= mscale * mscale
        return scale

    @property
    def latent_width(self) -> int:
        """Width of the latent pool array: kv_lora_rank +
        rope_head_dim values and, above one lane tile, zeros up to a
        whole number of 128-lane tiles (V3's 576 in 640). XLA:TPU lays
        a [1, pages, 16, 576] array out pages-minor to save the lanes
        the tiling would pad, and then copies the WHOLE array before a
        decode round's row gather, in every layer (seen in the program
        compiled for the chip, PR 33); a whole number of tiles keeps
        the pool's own layout and the write in place."""
        used = self.kv_lora_rank + self.rope_head_dim
        return used if used <= 128 else -(-used // 128) * 128

    def page_layout(self) -> paged_ops.PageLayout:
        """A cached token's row in the page pool: the latent row
        [c_kv | k_rope] (in `latent_width`) and, with an indexer, its
        key; one head each, for all query heads."""
        arrays = [paged_ops.PoolArray('latent_pages', 1,
                                      self.latent_width)]
        if self.index_n_heads:
            # float32: the selection is a discrete choice, and a key
            # rounded to bf16 moves positions across its boundary.
            arrays.append(paged_ops.PoolArray(
                'index_k_pages', 1, self.index_head_dim, jnp.float32))
        return paged_ops.PageLayout('latent', tuple(arrays),
                                    self.kv_page_size, self.kv_total_pages)


def rope_inv_freq(cfg: DeepseekConfig) -> jax.Array:
    """Inverse frequencies of the rotary pairs, [rope_head_dim / 2];
    with `rope_scaling` YaRN's blend of the published and the
    interpolated ones (DeepSeek-V3 `precompute_freqs_cis`)."""
    dim = cfg.rope_head_dim
    freqs = 1.0 / (cfg.rope_theta ** (
        jnp.arange(dim // 2, dtype=jnp.float32) / (dim // 2)))
    y = cfg.rope_scaling
    if y is None:
        return freqs

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(y.original_max_position_embeddings /
                               (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / y.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
               interleaved: bool) -> jax.Array:
    """x [B, S, H, D] rotated at `positions` [B, S]: on the pairs
    (x0,x1),(x2,x3).. when `interleaved`, else on the two halves."""
    angles = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(x32, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    return out.astype(x.dtype)


def _proj32(features: int, axes, name: str, **kw) -> nn.Dense:
    """A projection in float32 all the way: float32 operands at full
    precision (a TPU's default float32 product rounds them to bf16)."""
    return nn.Dense(
        features, use_bias=False, dtype=jnp.float32, name=name,
        precision=jax.lax.Precision.HIGHEST,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), axes), **kw)


class Indexer(nn.Module):
    """The lightning indexer's projections: per token the index queries
    [B,S,Hi,Di], the one index key [B,S,Di] (cached beside the latent
    row) and the heads' weights [B,S,Hi], ALL IN FLOAT32 from the
    block's float32 norm output `x` and query latent `c_q`: the
    selection they feed is a discrete choice, and where attention
    carries a token's state one key moved across the `index_topk`
    boundary by a rounded score moves the logits by nats (PERF.md,
    PR 33). RoPE on the first `rope_head_dim` dims of each, on the two
    halves, as published for the indexer. The published code also
    rotates queries and keys by a Hadamard matrix and quantizes them
    to FP8; the rotation is orthogonal and cancels in q·k, and both
    are left out."""
    config: DeepseekConfig

    @nn.compact
    def __call__(self, x: jax.Array, c_q: jax.Array,
                 positions: jax.Array):
        cfg = self.config
        batch, seq, _ = x.shape
        heads, dim, rot = (cfg.index_n_heads, cfg.index_head_dim,
                           cfg.rope_head_dim)
        inv_freq = rope_inv_freq(cfg)
        q = _proj32(heads * dim, ('kv', 'heads'), 'wq_b')(c_q)
        q = q.reshape(batch, seq, heads, dim)
        q = jnp.concatenate(
            [apply_rope(q[..., :rot], positions, inv_freq, False),
             q[..., rot:]], axis=-1)
        k = _proj32(dim, ('embed', 'kv'), 'wk')(x)
        k = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                         name='k_norm')(k)
        k = jnp.concatenate(
            [apply_rope(k[:, :, None, :rot], positions, inv_freq,
                        False)[:, :, 0], k[..., rot:]], axis=-1)
        w = _proj32(heads, ('embed', None), 'weights_proj')(x)
        return q, k, w * (heads ** -0.5) * (dim ** -0.5)


class MLAttention(nn.Module):
    """Multi-head latent attention with an absorbed decode path.

    Cache contract (decode=True): per-token latents only —
    'latent_cache' [B, T, kv_lora_rank] + 'rope_cache'
    [B, T, rope_head_dim] — written at per-row `positions`, the same
    positions semantics as the other families so `models/generate.py`
    and the batching engine drive this model unchanged.
    """
    config: DeepseekConfig

    def _queries(self, x: jax.Array, x32: Optional[jax.Array] = None):
        """[B,S,H,d_nope], [B,S,H,d_rope] (rope not yet applied), and
        the normed query latent c_q [B,S,q_lora_rank] the indexer
        shares (None with full-rank queries): in float32, from the
        float32 `x32`, where there is an indexer to feed."""
        cfg = self.config
        batch, seq, _ = x.shape
        c_q = None
        if cfg.q_lora_rank and x32 is not None:
            c_q = _proj32(cfg.q_lora_rank, ('embed', 'kv'), 'wq_a')(x32)
            c_q = RMSNorm(cfg.norm_eps, jnp.float32, name='q_norm')(c_q)
            q = _proj(cfg.num_heads * cfg.qk_head_dim, ('kv', 'heads'),
                      cfg.dtype, 'wq_b')(c_q.astype(cfg.dtype))
        elif cfg.q_lora_rank:
            c_q = _proj(cfg.q_lora_rank, ('embed', 'kv'), cfg.dtype,
                        'wq_a')(x)
            c_q = RMSNorm(cfg.norm_eps, cfg.dtype, name='q_norm')(c_q)
            q = _proj(cfg.num_heads * cfg.qk_head_dim, ('kv', 'heads'),
                      cfg.dtype, 'wq_b')(c_q)
        else:
            q = _proj(cfg.num_heads * cfg.qk_head_dim, ('embed', 'heads'),
                      cfg.dtype, 'wq')(x)
        q = q.reshape(batch, seq, cfg.num_heads, cfg.qk_head_dim)
        return (q[..., :cfg.nope_head_dim],
                q[..., cfg.nope_head_dim:], c_q)

    def _latents(self, x: jax.Array, positions: jax.Array):
        """Compressed per-token cache entries: c_kv [B,S,d_c] (normed)
        and the shared rotary key k_rope [B,S,d_rope] (rope applied)."""
        cfg = self.config
        kv = _proj(cfg.kv_lora_rank + cfg.rope_head_dim, ('embed', 'kv'),
                   cfg.dtype, 'wkv_a')(x)
        c_kv = RMSNorm(cfg.norm_eps, cfg.dtype, name='kv_norm')(
            kv[..., :cfg.kv_lora_rank])
        k_rope = kv[..., None, cfg.kv_lora_rank:]          # [B,S,1,d_r]
        k_rope = apply_rope(k_rope, positions, rope_inv_freq(cfg),
                            cfg.rope_interleaved)[:, :, 0]
        return c_kv, k_rope

    def _wkv_b(self) -> jax.Array:
        """[d_c, H, d_nope + d_v] decompression weight (split into
        W_uk / W_uv by the callers)."""
        cfg = self.config
        return self.param(
            'wkv_b',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                ('kv', 'heads', None)),
            (cfg.kv_lora_rank, cfg.num_heads,
             cfg.nope_head_dim + cfg.v_head_dim), jnp.float32)

    def _paged(self, q_nope, q_rope, c_kv, k_rope, index, w_uk,
               positions, page_indices, page_aligned, live):
        """The page-pool path: write the chunk's rows in place, then
        read through ops/sparse_latent.py (one token a row: gathered
        selection; a chunk: the blocked walk). Returns the context in
        latent terms, f32[B,S,H,kv_lora_rank]."""
        cfg = self.config
        layout = cfg.page_layout()
        pools = [self.variable('cache', a.name, jnp.zeros,
                               layout.shape(a), a.dtype or cfg.dtype)
                 for a in layout.arrays]
        latent, index_k = pools[0], (pools[1] if index else None)
        q_idx, k_idx, w_idx = index or (None, None, None)
        # Row and query end in the same zeros up to `latent_width`:
        # they add nothing to a score.
        pad = cfg.latent_width - cfg.kv_lora_rank - cfg.rope_head_dim
        widen = lambda x: jnp.pad(  # noqa: E731
            x.astype(cfg.dtype), [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        latent.value, index_pages = sparse_latent.write_rows(
            latent.value, index_k.value if index else None,
            widen(jnp.concatenate([c_kv, k_rope], axis=-1)),
            k_idx, positions, page_indices, page_aligned=page_aligned)
        if index:
            index_k.value = index_pages
        # The query in the cached row's own terms: [q_nope W_uk | q_rope].
        q = widen(jnp.concatenate(
            [jnp.einsum('bshn,chn->bshc', q_nope, w_uk,
                        preferred_element_type=jnp.float32
                        ).astype(cfg.dtype), q_rope], axis=-1))
        kw = dict(scale=cfg.softmax_scale, value_dim=cfg.kv_lora_rank)
        if q.shape[1] > 1:
            return sparse_latent.sparse_latent_chunk(
                q, q_idx, w_idx, latent.value, index_pages, positions,
                page_indices, topk=cfg.index_topk, **kw)
        # A lane that holds no request has no context: the reads skip it.
        lengths = positions[:, 0] + 1
        if live is not None:
            lengths = jnp.where(live[:, 0], lengths, 0)
        if index:
            scores = sparse_latent.index_scores_decode(
                q_idx[:, 0], w_idx[:, 0], index_pages, page_indices,
                lengths)
            idx, valid = sparse_latent.select_topk(scores, cfg.index_topk)
        else:
            idx = jnp.broadcast_to(
                jnp.arange(page_indices.shape[1] * layout.page_size),
                (q.shape[0], page_indices.shape[1] * layout.page_size))
            valid = idx < lengths[:, None]
        return sparse_latent.sparse_latent_decode(
            q[:, 0], latent.value, page_indices, idx, valid, **kw)[:, None]

    def _uncached(self, q_nope, q_rope, c_kv, k_rope, index, w_uk, w_uv):
        """The whole sequence at once, no cache (tests, training of a
        V3 block): decompressed K/V, YaRN's scale, and with an indexer
        each query over its `index_topk` best only. [B,S,H,d_v]."""
        cfg = self.config
        seq = c_kv.shape[1]
        f32 = jnp.float32
        k_nope = jnp.einsum('btc,chn->bthn', c_kv, w_uk)
        v = jnp.einsum('btc,chv->bthv', c_kv, w_uv)
        scores = (jnp.einsum('bshn,bthn->bhst', q_nope, k_nope,
                             preferred_element_type=f32) +
                  jnp.einsum('bshr,btr->bhst', q_rope, k_rope,
                             preferred_element_type=f32)
                  ) * cfg.softmax_scale
        keep = jnp.tril(jnp.ones((seq, seq), bool))[None]
        if index:
            q_idx, k_idx, w_idx = index
            pick = jnp.einsum('bshd,btd->bsht', q_idx, k_idx,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=f32)
            pick = jnp.sum(jax.nn.relu(pick) * w_idx[..., None], axis=2)
            keep = keep & sparse_latent.topk_mask(
                jnp.where(keep, pick, -jnp.inf), cfg.index_topk)
        probs = jax.nn.softmax(
            jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum('bhst,bthv->bshv', probs.astype(v.dtype), v,
                          preferred_element_type=f32).astype(cfg.dtype)

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False,
                 x32: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq, _ = x.shape
        if not cfg.index_n_heads:
            x32 = None
        q_nope, q_rope, c_q = self._queries(x, x32)
        q_rope = apply_rope(q_rope, positions, rope_inv_freq(cfg),
                            cfg.rope_interleaved)
        c_kv, k_rope = self._latents(x, positions)
        index = None
        if cfg.index_n_heads:
            if c_q is None:
                raise ValueError('the indexer reads the query latent: '
                                 'index_n_heads needs q_lora_rank')
            if x32 is None:
                x32, c_q = x.astype(jnp.float32), c_q.astype(jnp.float32)
            index = Indexer(cfg, name='index_proj')(x32, c_q, positions)
        wkv_b = self._wkv_b().astype(cfg.dtype)
        w_uk = wkv_b[..., :cfg.nope_head_dim]       # [d_c, H, d_n]
        w_uv = wkv_b[..., cfg.nope_head_dim:]       # [d_c, H, d_v]

        if decode and page_indices is not None:
            ctx_lat = self._paged(q_nope, q_rope, c_kv, k_rope, index,
                                  w_uk, positions, page_indices,
                                  page_aligned, live)
            out = jnp.einsum('bshc,chv->bshv', ctx_lat.astype(cfg.dtype),
                             w_uv, preferred_element_type=jnp.float32
                             ).astype(cfg.dtype)
        elif decode and (index or cfg.rope_scaling is not None):
            raise ValueError(
                'DeepSeek-V3 attention (indexer, YaRN) serves through '
                'the page pool only: give the config kv_total_pages '
                '(serve_lm --continuous-batching --kv-pool-bytes); the '
                'dense per-slot latent cache is DeepSeek-V2-Lite\'s')
        elif not decode and (index or cfg.rope_scaling is not None):
            out = self._uncached(q_nope, q_rope, c_kv, k_rope, index,
                                 w_uk, w_uv)
        elif decode:
            # ABSORBED attention against the latent cache, for any
            # chunk size: S=1 incremental decode, S=P chunked prefill,
            # S=k+1 speculative verification. The chunk's latents are
            # written at per-row offsets BEFORE attending, so stale
            # entries from rejected drafts are always overwritten
            # first (same contract as ops.chunked_cache_attention).
            latent = self.variable(
                'cache', 'latent_cache', jnp.zeros,
                (batch, cfg.max_seq_len, cfg.kv_lora_rank), cfg.dtype)
            ropes = self.variable(
                'cache', 'rope_cache', jnp.zeros,
                (batch, cfg.max_seq_len, cfg.rope_head_dim), cfg.dtype)
            start = positions[:, 0]                              # [B]

            def write_rows(cache_row, new_rows, p):
                return jax.lax.dynamic_update_slice(
                    cache_row, new_rows, (p, 0))

            latent.value = jax.vmap(write_rows)(
                latent.value, c_kv.astype(cfg.dtype), start)
            ropes.value = jax.vmap(write_rows)(
                ropes.value, k_rope.astype(cfg.dtype), start)
            # q absorbed into latent space: [B,S,H,d_c]
            q_eff = jnp.einsum('bshn,chn->bshc',
                               q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            if prefill:
                # PREFILL fast path (static; empty-cache contract):
                # attend only within the chunk — S x S instead of
                # S x max_seq_len f32 scores.
                k_lat = c_kv.astype(jnp.float32)
                k_rop = k_rope.astype(jnp.float32)
                mask = (jnp.arange(seq)[None, :]
                        <= jnp.arange(seq)[:, None])[None, None]
            else:
                k_lat = latent.value.astype(jnp.float32)
                k_rop = ropes.value.astype(jnp.float32)
                mask = (jnp.arange(cfg.max_seq_len)[None, None, :]
                        <= positions[:, :, None])[:, None]  # [B,1,S,T]
            scores = (
                jnp.einsum('bshc,btc->bhst', q_eff, k_lat) +
                jnp.einsum('bshr,btr->bhst',
                           q_rope.astype(jnp.float32), k_rop)
            ) / jnp.sqrt(float(cfg.qk_head_dim))
            scores = jnp.where(mask, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            # Context in latent space, decompressed once per head.
            ctx_lat = jnp.einsum('bhst,btc->bshc', probs, k_lat)
            out = jnp.einsum('bshc,chv->bshv', ctx_lat,
                             w_uv.astype(jnp.float32))
            out = out.astype(cfg.dtype)              # [B,S,H,d_v]
        else:
            # Training: decompress K and V from the chunk's latents
            # (no cache) and run standard causal attention at
            # qk_head_dim.
            k_nope = jnp.einsum('btc,chn->bthn', c_kv, w_uk)
            v = jnp.einsum('btc,chv->bthv', c_kv, w_uv)
            k = jnp.concatenate([
                k_nope,
                jnp.broadcast_to(k_rope[:, :, None],
                                 (batch, seq, cfg.num_heads,
                                  cfg.rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            q = nn.with_logical_constraint(q,
                                           ('batch', 'seq', 'heads', 'kv'))
            k = nn.with_logical_constraint(k,
                                           ('batch', 'seq', 'heads', 'kv'))
            v = nn.with_logical_constraint(v,
                                           ('batch', 'seq', 'heads', 'kv'))
            out = attention_ops.dot_product_attention(q, k, v, causal=True)
        out = out.reshape(batch, seq, cfg.num_heads * cfg.v_head_dim)
        return _proj(cfg.embed_dim, ('heads', 'embed'), cfg.dtype,
                     'wo')(out)


#: Rows of the sorted tokens one step of the grouped expert pass takes
#: (a prefill chunk; a decode round's few tokens are one step an
#: expert).
EXPERT_ROW_BLOCK = 128


def route(cfg: DeepseekConfig, logits: jax.Array, bias: jax.Array):
    """V3's group-limited choice: logits f32[N, E] -> (experts i32[N, K],
    weights f32[N, K]). Scores are sigmoids; the CHOICE is made on
    score + bias: a group scores the sum of its two best, the
    `topk_group` best groups stay, the K best experts among them are
    chosen; the WEIGHTS are the plain scores of the chosen, normalized
    over the K and times `routed_scaling_factor`."""
    n, experts = logits.shape
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias
    per_group = experts // cfg.n_group
    grouped = choice.reshape(n, cfg.n_group, per_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, min(2, per_group))[0],
                          axis=-1)
    _, best_groups = jax.lax.top_k(group_score, cfg.topk_group)
    in_group = jnp.zeros((n, cfg.n_group), bool).at[
        jnp.arange(n)[:, None], best_groups].set(True)
    choice = jnp.where(jnp.repeat(in_group, per_group, axis=1), choice,
                       -jnp.inf)
    _, chosen = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * cfg.routed_scaling_factor


def _expert_fn(act: str):
    """One expert, [rows, d] -> [rows, d]: `pallas_experts.expert_hidden`
    of the inner matrices, rounded to the rows' dtype, times `w_down`,
    products accumulated in float32."""
    def expert(x, *matrices):
        h = pallas_experts.expert_hidden(act, x, *matrices[:-1])
        return jnp.dot(h.astype(x.dtype), matrices[-1],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return expert


_swiglu = _expert_fn('swiglu')

#: An expert's function by name, with its matrices' names in the order
#: it takes them: `swiglu`, three of them (w_gate, w_up [d, f]; w_down
#: [f, d]); `relu2`, two (w_up [d, f]; w_down [f, d]), the square of
#: the ReLU between them.
EXPERT_FNS = {'swiglu': (_swiglu, ('w_gate', 'w_up', 'w_down')),
              'relu2': (_expert_fn('relu2'), ('w_up', 'w_down'))}


def expert_route(cfg: Any, act: str, width: int) -> str:
    """The route `MoEByShare`'s grouped pass takes for held experts of
    `act` on rows `width` wide: `pallas_experts.resolve_route`'s answer
    for the matrices' static shapes ('grouped_pallas' or
    'switch_loop')."""
    return pallas_experts.resolve_route(
        width, cfg.moe_dim, len(EXPERT_FNS[act][1]), cfg.dtype)


def grouped_experts(x_sorted: jax.Array, counts: jax.Array,
                    experts, row_block: int,
                    expert_fn=_swiglu) -> jax.Array:
    """Experts over tokens sorted by expert, dropless: route
    'switch_loop', taken on the CPU backend and for a shape
    `pallas_experts.kernel_refusal` refuses, and `kernel_check`'s
    reference for the kernel of ops/pallas_experts.py.

    x_sorted [M, d]: expert 0's `counts[0]` rows first, then expert
    1's, ...; rows past sum(counts) belong to nobody. `experts`: the
    matrices of each expert held, in the order `expert_fn(x, *matrices)`
    takes them ([rows, d] -> [rows, d]: `swiglu` with three, `relu2`
    with two). One loop over blocks of `row_block` rows, each
    inside ONE expert's rows, so an expert is multiplied as often as
    its tokens need and one that received none is never read; the trip
    count is known on the device only. Returns [M, d] (rows past
    sum(counts): zeros)."""
    held = counts.shape[0]
    rows, dim = x_sorted.shape
    starts = jnp.cumsum(counts) - counts
    blocks = -(-counts // row_block)
    first_block = jnp.cumsum(blocks) - blocks
    x_pad = jnp.pad(x_sorted, ((0, row_block), (0, 0)))

    def step(b, out):
        e = jnp.sum(b >= first_block + blocks).astype(jnp.int32)
        e = jnp.minimum(e, held - 1)
        offset = (b - first_block[e]) * row_block
        row0 = starts[e] + offset
        x = jax.lax.dynamic_slice(x_pad, (row0, 0), (row_block, dim))
        # One branch an expert, over that expert's OWN arrays: XLA:TPU
        # copies a slice of stacked weights out whole before it
        # multiplies by it, at a traced index and at a static one
        # alike (as many times an expert's bytes moved as it has
        # matrices). A program carries every branch of every layer,
        # and no read overlaps the previous expert's product: on a TPU
        # the kernel of ops/pallas_experts.py takes the pass instead.
        y = jax.lax.switch(
            e, [lambda x, w=tuple(w): expert_fn(x, *w) for w in experts],
            x)
        # The block's tail may reach into the next expert's rows:
        # those keep what they hold.
        mine = (offset + jnp.arange(row_block) < counts[e])[:, None]
        old = jax.lax.dynamic_slice(out, (row0, 0), (row_block, dim))
        return jax.lax.dynamic_update_slice(
            out, jnp.where(mine, y, old), (row0, 0))

    out = jax.lax.fori_loop(
        0, jnp.sum(blocks), step,
        jnp.zeros((rows + row_block, dim), x_sorted.dtype))
    return out[:rows]


class ExpertWeights(nn.Module):
    """One routed expert's matrices in the compute dtype, as
    `EXPERT_FNS[act]` names and orders them: `width` wide, on an input
    of `in_dim` values."""
    config: Any
    in_dim: int
    width: int
    act: str = 'swiglu'

    @nn.compact
    def __call__(self, drawn=None):
        """`drawn` ({matrix name: its values}, at initialisation only):
        this expert's slice of one draw for all of a layer's experts,
        in place of a draw of its own."""
        cfg = self.config

        def matrix(name, shape, axes):
            init = nn.initializers.normal(stddev=0.02)
            if drawn is not None:
                init = lambda key, shape, dtype: (  # noqa: E731
                    drawn[name].astype(dtype))
            return self.param(
                name, nn.with_logical_partitioning(init, axes),
                shape, jnp.float32).astype(cfg.dtype)

        return tuple(
            matrix(name, (self.width, self.in_dim), ('mlp', 'embed'))
            if name == 'w_down' else
            matrix(name, (self.in_dim, self.width), ('embed', 'mlp'))
            for name in EXPERT_FNS[self.act][1])


class Relu2MLP(nn.Module):
    """`w_down relu(w_up x)^2`, no bias: a shared expert of a model
    whose experts are `relu2`."""
    embed_dim: int
    width: int
    dtype: Dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = _proj(self.width, ('embed', 'mlp'), self.dtype, 'w_up')(x)
        h = nn.with_logical_constraint(jnp.square(nn.relu(h)),
                                       ('batch', 'seq', 'mlp'))
        return _proj(self.embed_dim, ('mlp', 'embed'), self.dtype,
                     'w_down')(h)


class MoEByShare(nn.Module):
    """A routed expert layer as ONE chip's share of an expert-parallel
    deployment holds it: the router over all `n_routed_experts`
    (sigmoid scores, `route`), the weights normalized over all chosen,
    and the parts of this chip's `experts_held` experts (from
    `expert_offset`) plus the shared expert. Dropless. What the absent
    experts would add is left out.

    The configuration gives the counts and the widths (`embed_dim`,
    `moe_dim`, `n_routed_experts`, `num_experts_per_tok`, `num_held`,
    `expert_offset`, `n_group`, `topk_group`, `routed_scaling_factor`,
    `n_shared_experts`, `dtype`); the module's own fields say what an
    expert is. `expert_act`: `swiglu` (three matrices) or `relu2`
    (two). `latent_dim` > 0: the routed experts work on a LATENT of
    that width (`latent_down`, no bias, before them; `latent_up` after
    their weighted sum, which is linear, so shares still add up); the
    router and the shared expert keep the full width. `shared_dim`:
    the shared expert's width (0: `moe_dim` x `n_shared_experts`; a
    layer where that is 0 has no shared expert, LFM2's).

    `live` (bool [B,S], or None = all) marks real tokens: a junk lane
    or a padded tail is sent to no expert, so it reads no weights and
    counts nowhere. With `count` the layer keeps device accumulators
    in the `cache` collection, [2, held] each, row 0 for one-token
    calls (decode) and row 1 for chunks (prefill): `expert_tokens`
    (assignments an expert received) and `expert_calls_touched` (calls
    in which it received any)."""
    config: Any
    expert_act: str = 'swiglu'
    latent_dim: int = 0
    shared_dim: int = 0
    #: Seeded weights of all held experts from ONE draw a matrix name
    #: (sliced an expert each) instead of a draw an expert: the values
    #: are as random, and the program that makes them compiles in
    #: seconds where 1,280 draws of their own took seven minutes
    #: (a threefry fusion a leaf; PERF.md, PR 35). Off: a model whose
    #: seeded weights a benchmark cell already has keeps them.
    stacked_init: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, live: Optional[jax.Array] = None,
                 count: bool = False,
                 x_router: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq, dim = x.shape
        n, top = batch * seq, cfg.num_experts_per_tok
        held, offset = cfg.num_held, cfg.expert_offset
        flat = x.reshape(n, dim)
        shared_dim = self.shared_dim or cfg.moe_dim * cfg.n_shared_experts
        shared = None
        if shared_dim:
            with jax.named_scope('shared_expert'):
                if self.expert_act == 'swiglu':
                    shared = SwiGLU(dataclasses.replace(
                        cfg, mlp_dim=shared_dim), name='shared')(x)
                else:
                    shared = Relu2MLP(dim, shared_dim, cfg.dtype,
                                      name='shared')(x)
        with jax.named_scope('router'):
            # The router's logits in float32 all the way, as
            # published: from the norm's float32 output where the
            # block hands it over (`x_router`), at full precision (a
            # TPU's default float32 product rounds its operands to
            # bf16). A rounded logit flips the last of the chosen
            # experts, and a flipped expert moves the output more than
            # all of a dense block's rounding.
            logits = nn.Dense(
                cfg.n_routed_experts, use_bias=False, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, name='router',
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), ('embed', None)))(
                        (flat if x_router is None else
                         x_router.reshape(n, dim)).astype(jnp.float32))
            bias = self.param(
                'e_score_correction_bias',
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), (None,)),
                (cfg.n_routed_experts,), jnp.float32)
            chosen, weights = route(cfg, logits, bias.astype(jnp.float32))

        if self.latent_dim:
            with jax.named_scope('latent_down'):
                flat = _proj(self.latent_dim, ('embed', 'kv'), cfg.dtype,
                             'latent_down')(flat)
        width = flat.shape[-1]
        # An expert's matrices are arrays of their own, under the
        # expert's number in the whole model (`expert_<n>`).
        drawn = None
        if self.stacked_init and self.is_initializing():
            # The draws wait for the layer's input, so after the layers
            # below it: the values are the same, and the one init
            # program holds one layer's draws at a time (LFM2's 8 layers
            # of 64 held experts held all of them at once, 9.7 GB of
            # scratch beside 10.5 GB of weights for a described v5e;
            # 2.0 GB so).
            key, _ = jax.lax.optimization_barrier(
                (self.make_rng('params'), x))
            drawn = {
                name: 0.02 * jax.random.normal(
                    jax.random.fold_in(key, j),
                    (held, cfg.moe_dim, width) if name == 'w_down'
                    else (held, width, cfg.moe_dim), jnp.float32)
                for j, name in enumerate(EXPERT_FNS[self.expert_act][1])}
        experts = [ExpertWeights(cfg, width, cfg.moe_dim, self.expert_act,
                                 name=f'expert_{offset + i}')(
                       None if drawn is None else
                       {name: values[i] for name, values in drawn.items()})
                   for i in range(held)]
        with jax.named_scope('experts'):
            here = (chosen >= offset) & (chosen < offset + held)
            if live is not None:
                here &= live.reshape(n, 1)
            # Sort the N x K assignments by expert held here; the rest
            # go last, under the number `held`, and are never computed.
            local = jnp.where(here, chosen - offset, held).reshape(-1)
            order = jnp.argsort(local, stable=True)
            counts = jnp.sum(
                local[:, None] == jnp.arange(held)[None, :], axis=0
            ).astype(jnp.int32)
            if expert_route(cfg, self.expert_act, width) == 'switch_loop':
                y_sorted = grouped_experts(
                    flat[order // top], counts, experts,
                    min(n, EXPERT_ROW_BLOCK),
                    EXPERT_FNS[self.expert_act][0])
            else:
                # Off a TPU only a test reaches this: the interpreter.
                y_sorted = pallas_experts.grouped_experts_pallas(
                    flat[order // top], counts, experts,
                    act=self.expert_act,
                    interpret=not pallas_experts.available())
            parts = y_sorted[jnp.argsort(order)].reshape(n, top, width)
            routed = jnp.sum(
                parts.astype(jnp.float32)
                * jnp.where(here, weights, 0.0)[..., None], axis=1)
        if count:
            phase = int(seq > 1)
            tokens = self.variable('cache', 'expert_tokens', jnp.zeros,
                                   (2, held), jnp.int32)
            touched = self.variable('cache', 'expert_calls_touched',
                                    jnp.zeros, (2, held), jnp.int32)
            tokens.value = tokens.value.at[phase].add(counts)
            touched.value = touched.value.at[phase].add(
                (counts > 0).astype(jnp.int32))
        routed = routed.astype(cfg.dtype)
        if self.latent_dim:
            with jax.named_scope('latent_up'):
                routed = _proj(dim, ('kv', 'embed'), cfg.dtype,
                               'latent_up')(routed)
        routed = routed.reshape(batch, seq, dim)
        return routed if shared is None else shared + routed


class Block(nn.Module):
    config: DeepseekConfig
    # Whether this block's feed-forward is the routed experts.
    routed: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        h32 = RMSNorm(cfg.norm_eps, jnp.float32, name='attn_norm')(x)
        x = x + MLAttention(cfg, name='attn')(
            h32.astype(cfg.dtype), positions, decode, page_indices,
            prefill, page_aligned, h32, live)
        if self.routed:
            h32 = RMSNorm(cfg.norm_eps, jnp.float32, name='mlp_norm')(x)
            x = x + MoEByShare(cfg, name='mlp')(
                h32.astype(cfg.dtype), live, count=decode, x_router=h32)
        else:
            h = RMSNorm(cfg.norm_eps, cfg.dtype, name='mlp_norm')(x)
            # llama's SwiGLU block is duck-typed on mlp_dim/embed_dim/
            # dtype (same reuse as mixtral.py).
            x = x + SwiGLU(cfg, name='mlp')(h)
        return nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))


class Deepseek(nn.Module):
    """DeepSeek decoder; __call__ returns logits [B, S, vocab].

    `return_hidden=True` returns the post-final_norm hidden states
    (the fused blockwise-loss path, ops/fused_xent.py — at DeepSeek's
    102k vocab the skipped [B, S, V] logits dominate training HBM).
    """
    config: DeepseekConfig
    #: The serving engine hands `live` (which tokens of a call are a
    #: request's own) to a model that declares this, and /stats
    #: fetches the cache leaves named here (models/batching.py).
    takes_live_mask = True
    counter_leaves = ('expert_tokens', 'expert_calls_touched',
                      'sparse_decode_tokens')

    def expert_impl(self) -> str:
        """The route of the routed layers' grouped expert pass
        (`expert_route`; /stats `expert_impl`)."""
        return expert_route(self.config, 'swiglu', self.config.embed_dim)

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 return_hidden: bool = False,
                 page_aligned: bool = False,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        embed = self.param(
            'tok_embed',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02),
                ('vocab', 'table_embed')),
            (cfg.vocab_size, cfg.embed_dim), jnp.float32)
        x = embed.astype(cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))

        if decode and cfg.index_n_heads and page_indices is not None:
            # Decoded tokens whose context exceeded `index_topk`: the
            # ones for which the selection left something out.
            sparse = self.variable('cache', 'sparse_decode_tokens',
                                   jnp.zeros, (), jnp.int32)
            if seq == 1:
                beyond = positions[:, 0] + 1 > cfg.index_topk
                if live is not None:
                    beyond &= live[:, 0]
                sparse.value = sparse.value + jnp.sum(beyond,
                                                      dtype=jnp.int32)

        block = Block
        if cfg.remat:
            block = nn.remat(Block, prevent_cse=False,
                             static_argnums=(3, 5, 6))
        for i in range(cfg.num_layers):
            routed = bool(cfg.n_routed_experts) and i >= cfg.first_k_dense
            x = block(cfg, routed, name=f'layer_{i}')(
                x, positions, decode, page_indices, prefill,
                page_aligned, live)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'vocab')),
            (cfg.embed_dim, cfg.vocab_size), jnp.float32)
        if return_hidden:
            return nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
        logits = jnp.einsum('bse,ev->bsv', x.astype(cfg.dtype),
                            head.astype(cfg.dtype),
                            preferred_element_type=(cfg.logits_dtype or
                                                    jnp.float32))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))
