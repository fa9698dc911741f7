"""Nemotron-H-family hybrid decoder: Mamba-2, attention and routed
experts, ONE mixer a block.

`hybrid_override_pattern` spells the model a letter a block: `M` a
Mamba-2 mixer, `*` grouped-query attention, `E` a routed expert layer
(`-`, a dense MLP, is what the older Nemotron-H models have and is not
built here). A block is `x + mixer(RMSNorm(x))`; after the last a final
RMSNorm and an untied head.

  - `M` (ops/ssm.py): `[z, xBC, dt] = in_proj(u)`; `xBC = silu(causal
    depthwise conv1d(xBC) + bias)` split into x [H, P], B and C [G, N];
    `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the recurrence
    `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t`, `y_t = h_t C_t
    + D x_t`; `y = RMSNorm_groups(y * silu(z))` (gate first, the norm
    over each of the G groups, a learned scale) and `out_proj`. State,
    A, dt and the recurrence in float32.
  - `*`: no bias, causal, scale 1/sqrt(head_dim), and NO rotary
    embedding (position comes from the Mamba layers). K and V live in
    the page pool (ops/paged_attention.py, kind 'kv').
  - `E` (models/deepseek.MoEByShare): sigmoid router over all
    `n_routed_experts` at the full width in float32, `relu2` experts
    of two matrices that work in a latent of `moe_latent_dim` (Nemotron
    3 Super's LatentMoE; 0: at the full width), a `relu2` shared expert
    at the full width; this chip holds `experts_held` experts from
    `expert_offset` (0 held = all).

Serving keeps a SECOND kind of cache beside the pages: a Mamba layer's
state is one row a sequence, not a row a token. `page_layout()` names
it (`slot_arrays`: `ssm_state` [P, N, H] float32 and `conv_state`
[(conv_kernel - 1) x conv width / H, H], ops/ssm.slot_shapes); the
engine allocates the rows with its slots, hands the model the slot a
prefill row belongs to (`slots`) and the `live` mask. A decode lane is
its slot; a prompt's first chunk (`prefill=True`) starts from zeros and
never reads the old row, so slot reuse and preemption-by-recompute need
no reset; a dead lane's rows are left as they are.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from skypilot_tpu.models.deepseek import MoEByShare, expert_route
from skypilot_tpu.models.llama import RMSNorm, _proj
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops import paged_attention as paged_ops
from skypilot_tpu.ops import pallas_ssm, ssm

Dtype = Any
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """HF `nemotron_h` config.json; the defaults are
    NVIDIA-Nemotron-3-Super-120B-A12B's published sizes."""
    vocab_size: int = 131072
    max_seq_len: int = 262144
    pattern: str = ('MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*'
                    'EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME')
    embed_dim: int = 4096
    norm_eps: float = 1e-5
    # Mamba-2 mixer.
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # Attention (no rotary embedding).
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Routed experts (models/deepseek.MoEByShare reads these names):
    # the ROUTER's width, the published count; this chip holds
    # `experts_held` of them from `expert_offset` on (0 held = all).
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_dim: int = 2688
    moe_latent_dim: int = 1024
    moe_shared_dim: int = 5376
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 5.0
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
    def super_l11_ep4(cls, **kw) -> 'NemotronHConfig':
        """One chip's share of a 4-way expert-parallel deployment at
        every published width: one period of 11 layers (the published
        layers 27-37: 5 Mamba-2, 5 expert, 1 attention), experts 0-127
        of 512 held, a quarter of the vocabulary
        (perfbench/configs/nemotron3-super-l11-ep4.json has the cut)."""
        base = dict(pattern='MEMEMEMEM*E', experts_held=128,
                    expert_offset=0, vocab_size=32768, max_seq_len=4096,
                    kv_total_pages=2048)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw) -> 'NemotronHConfig':
        """Every letter at a size a CPU test holds: two Mamba layers,
        `chunk_size` 8 so that a 32-token chunk crosses sub-chunk
        boundaries, 16 experts of which 4 a token, in a latent."""
        base = dict(
            vocab_size=512, max_seq_len=256, pattern='ME*EM',
            embed_dim=128, mamba_num_heads=8, mamba_head_dim=16,
            ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
            num_heads=4, num_kv_heads=2, head_dim=128,
            n_routed_experts=16, num_experts_per_tok=4, moe_dim=64,
            moe_latent_dim=32, moe_shared_dim=96, experts_held=16,
            kv_total_pages=128)
        base.update(kw)
        return cls(**base)

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def num_held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def slot_shapes(self):
        """A slot's rows of a Mamba layer (ops/ssm.slot_shapes)."""
        return ssm.slot_shapes(self.mamba_num_heads, self.mamba_head_dim,
                               self.ssm_state_size, self.conv_kernel,
                               self.conv_dim)

    def page_layout(self) -> paged_ops.PageLayout:
        """K and V of the attention layers by page; beside them, by
        SLOT, each Mamba layer's state and its convolution's tail."""
        kv = paged_ops.kv_layout(self.num_kv_heads, self.head_dim,
                                 self.kv_page_size, self.kv_total_pages)
        state, tail = self.slot_shapes()
        return dataclasses.replace(
            kv, layers=self.pattern.count('*'),
            slot_arrays=(
                # In the layout of ops/ssm.slot_shapes: the state [P,
                # N, H] float32, the tail's K - 1 inputs in rows of H.
                paged_ops.SlotArray('ssm_state', state, F32),
                paged_ops.SlotArray('conv_state', tail)),
            slot_layers=self.pattern.count('M'))


def _rows(live: Optional[jax.Array], batch: int, seq: int) -> jax.Array:
    """bool[B, S]: the tokens of a call that are a request's own."""
    return (jnp.ones((batch, seq), bool) if live is None
            else jnp.broadcast_to(live, (batch, seq)))


class Mamba2Mixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u: jax.Array, decode: bool = False,
                 prefill: bool = False,
                 live: Optional[jax.Array] = None,
                 slots: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        batch, seq, _ = u.shape
        heads, hd, n, g = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.ssm_state_size, cfg.n_groups)
        inner, conv_dim = cfg.mamba_inner, cfg.conv_dim
        taps = cfg.conv_kernel

        def vector(name, init, shape):
            return self.param(name, nn.with_logical_partitioning(
                init, (None,) * len(shape)), shape, F32)

        # Seeded as the published initialisation has them, but for D:
        # the convolution as PyTorch's Conv1d (uniform in
        # 1/sqrt(taps)), A in [1, 16], dt's bias the inverse softplus
        # of a step in [1e-3, 1e-1].
        conv_w = vector('conv_weight', lambda k, s, d: jax.random.uniform(
            k, s, d, -taps ** -0.5, taps ** -0.5), (taps, conv_dim))
        conv_b = vector('conv_bias', lambda k, s, d: jax.random.uniform(
            k, s, d, -taps ** -0.5, taps ** -0.5), (conv_dim,))
        a_log = vector('A_log', lambda k, s, d: jnp.log(
            jax.random.uniform(k, s, d, 1.0, 16.0)), (heads,))
        # D: zeros, where the published initialisation has ones. With
        # SEEDED B, C and dt the skip D x is 10 to 50 times the state's
        # term C h, and the gated norm then hides the state from the
        # logits (a chunk started from a zeroed state moves the tiny
        # model's log-probabilities by 0.005); at 0, all the mixer
        # hands on has passed through the state, so a comparison of
        # logits guards the recurrence (0.2 to 0.5 for that fault).
        d_skip = vector('D', nn.initializers.zeros_init(), (heads,))

        def dt_bias_init(key, shape, dtype):
            step = jnp.exp(jax.random.uniform(key, shape, dtype)
                           * (jnp.log(0.1) - jnp.log(0.001))
                           + jnp.log(0.001))
            return step + jnp.log(-jnp.expm1(-step))

        dt_bias = vector('dt_bias', dt_bias_init, (heads,))
        norm_scale = vector('norm_scale', nn.initializers.ones_init(),
                            (inner,))

        with jax.named_scope('ssm_in_proj'):
            zxbcdt = _proj(inner + conv_dim + heads, ('embed', 'mlp'),
                           cfg.dtype, 'in_proj')(u)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = jax.nn.softplus(zxbcdt[..., inner + conv_dim:].astype(F32)
                             + dt_bias)
        a = -jnp.exp(a_log)
        mask = _rows(live, batch, seq)
        lengths = jnp.sum(mask, axis=1, dtype=jnp.int32)

        state = tail = None
        if decode:
            # One row a SLOT (the engine's cache has its `num_slots`
            # rows: it is made by a call of one token a slot).
            state_row, tail_row = cfg.slot_shapes()
            state = self.variable('cache', 'ssm_state', jnp.zeros,
                                  (batch,) + state_row, F32)
            tail = self.variable('cache', 'conv_state', jnp.zeros,
                                 (batch,) + tail_row, cfg.dtype)
            # Device accumulators (/stats): live lane-steps this layer
            # updated in decode rounds, valid tokens it scanned in
            # prefill chunks. Both exist in every program, so the
            # cache's tree is one.
            counters = [self.variable('cache', name, jnp.zeros, (),
                                      jnp.int32)
                        for name in ('ssm_update_tokens',
                                     'ssm_scan_tokens')]
            counter = counters[int(seq > 1)]
            counter.value = counter.value + jnp.sum(lengths)
        if decode and seq == 1:
            # A decode round: a lane is its slot; the convolution is
            # part of the live rows' step (scope `ssm_update`).
            y, state.value, tail.value = ssm.ssm_update(
                state.value, tail.value, xbc[:, 0], dt[:, 0], a, d_skip,
                conv_w, conv_b, mask[:, 0], groups=g)
            y = y[:, None]
        else:
            if decode and slots is None:
                raise ValueError(
                    'a prefill chunk of a model with state by slot '
                    'needs `slots`, the slot of each of its rows')
            if decode and not prefill:
                # Row by row, as slices: a gather may be given a layout
                # of its own, and with it a copy of the whole array.
                h0, tail0 = (jnp.stack([
                    jax.lax.dynamic_index_in_dim(var.value, slots[row], 0,
                                                 keepdims=False)
                    for row in range(batch)]) for var in (state, tail))
                h0 = ssm.state_from_slot(h0)
                tail0 = ssm.tail_from_slot(tail0, taps)
            else:
                # A sequence's start: zeros, whatever the slot's rows
                # held (a finished or preempted request's).
                h0 = jnp.zeros((batch, heads, hd, n), F32)
                tail0 = jnp.zeros((batch, taps - 1, conv_dim), cfg.dtype)
            with jax.named_scope('ssm_conv'):
                xbc, tail1 = ssm.causal_conv(xbc, tail0, conv_w, conv_b,
                                             lengths)
                xbc = nn.silu(xbc).astype(cfg.dtype)
            y, h1 = ssm.ssm_scan(
                xbc[..., :inner].reshape(batch, seq, heads, hd), dt, a,
                xbc[..., inner:inner + g * n].reshape(batch, seq, g, n),
                xbc[..., inner + g * n:].reshape(batch, seq, g, n),
                d_skip, h0, lengths, cfg.chunk_size)
            if decode:
                h1 = ssm.state_to_slot(h1)
                tail1 = ssm.tail_to_slot(tail1, heads)
                for row in range(batch):
                    state.value = jax.lax.dynamic_update_index_in_dim(
                        state.value, h1[row], slots[row], 0)
                    tail.value = jax.lax.dynamic_update_index_in_dim(
                        tail.value, tail1[row], slots[row], 0)
        with jax.named_scope('ssm_gate_norm'):
            # Gate first, then the norm over each group, float32.
            gated = (y.reshape(batch, seq, g, inner // g)
                     * nn.silu(z.astype(F32)).reshape(batch, seq, g, -1))
            var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
            gated = (gated * jax.lax.rsqrt(var + cfg.norm_eps)
                     ).reshape(batch, seq, inner) * norm_scale
        with jax.named_scope('ssm_out_proj'):
            return _proj(cfg.embed_dim, ('mlp', 'embed'), cfg.dtype,
                         'out_proj')(gated.astype(cfg.dtype))


class Attention(nn.Module):
    """Grouped-query attention without position embedding, over the
    K/V page pool when serving (models/llama.Attention's paged reads
    and writes, unrotated)."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False,
                 page_aligned: bool = False) -> jax.Array:
        cfg = self.config
        batch, seq, _ = x.shape
        hd = cfg.head_dim
        q = _proj(cfg.num_heads * hd, ('embed', 'heads'), cfg.dtype,
                  'wq')(x).reshape(batch, seq, cfg.num_heads, hd)
        k = _proj(cfg.num_kv_heads * hd, ('embed', 'heads'), cfg.dtype,
                  'wk')(x).reshape(batch, seq, cfg.num_kv_heads, hd)
        v = _proj(cfg.num_kv_heads * hd, ('embed', 'heads'), cfg.dtype,
                  'wv')(x).reshape(batch, seq, cfg.num_kv_heads, hd)
        if not decode:
            out = attention_ops.dot_product_attention(q, k, v, causal=True)
        else:
            layout = cfg.page_layout()
            k_pages, v_pages = (
                self.variable('cache', a.name, jnp.zeros, layout.shape(a),
                              cfg.dtype) for a in layout.arrays)
            k_pages.value, v_pages.value = paged_ops.write_kv_chunk(
                k_pages.value, v_pages.value, k, v, positions,
                page_indices, page_aligned=page_aligned and seq > 1)
            if seq == 1:
                out = paged_ops.paged_decode_attention(
                    q[:, 0], k_pages.value, v_pages.value,
                    lengths=positions[:, 0] + 1,
                    page_indices=page_indices)[:, None]
            elif prefill:
                # The sequence starts with this chunk: chunk-local.
                out = attention_ops.dot_product_attention(q, k, v,
                                                          causal=True)
            else:
                out = paged_ops.paged_chunk_attention(
                    q, k_pages.value, v_pages.value, positions,
                    page_indices)
        out = out.astype(cfg.dtype).reshape(batch, seq,
                                            cfg.num_heads * hd)
        return _proj(cfg.embed_dim, ('heads', 'embed'), cfg.dtype,
                     'wo')(out)


class Block(nn.Module):
    config: NemotronHConfig
    kind: str                           # the block's letter

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_indices: Optional[jax.Array] = None,
                 prefill: bool = False, page_aligned: bool = False,
                 live: Optional[jax.Array] = None,
                 slots: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if self.kind == 'M':
            h = RMSNorm(cfg.norm_eps, cfg.dtype, name='norm')(x)
            x = x + Mamba2Mixer(cfg, name='mixer')(h, decode, prefill,
                                                   live, slots)
        elif self.kind == '*':
            h = RMSNorm(cfg.norm_eps, cfg.dtype, name='norm')(x)
            x = x + Attention(cfg, name='mixer')(
                h, positions, decode, page_indices, prefill, page_aligned)
        elif self.kind == 'E':
            h32 = RMSNorm(cfg.norm_eps, F32, name='norm')(x)
            x = x + MoEByShare(
                cfg, expert_act='relu2', latent_dim=cfg.moe_latent_dim,
                shared_dim=cfg.moe_shared_dim, stacked_init=True,
                name='mixer')(
                    h32.astype(cfg.dtype), live, count=decode,
                    x_router=h32)
        else:
            raise ValueError(
                f'hybrid_override_pattern letter {self.kind!r}: this '
                f'model builds M (Mamba-2), * (attention) and E '
                f'(routed experts)')
        return nn.with_logical_constraint(x, ('batch', 'seq', 'act_embed'))


class NemotronH(nn.Module):
    """Nemotron-H decoder; __call__ returns logits [B, S, vocab].

    `decode=False`: the whole sequence from empty state, no cache.
    `decode=True`: through the engine's cache (models/batching.py):
    `page_indices` the rows' page tables, `slots` the slot of each row
    of a prefill chunk (a decode round's lane is its slot), `live` the
    tokens that are a request's own, `prefill` a sequence's first
    chunk."""
    config: NemotronHConfig
    #: The serving engine hands `live` to a model that declares this,
    #: and /stats fetches the cache leaves named here.
    takes_live_mask = True
    counter_leaves = ('expert_tokens', 'expert_calls_touched',
                      'ssm_update_tokens', 'ssm_scan_tokens')

    def expert_impl(self) -> str:
        """The route of the expert layers' grouped pass, in the
        latent (models/deepseek.expert_route; /stats `expert_impl`)."""
        return expert_route(self.config, 'relu2',
                            self.config.moe_latent_dim)

    def ssm_update_impl(self, slots: int) -> str:
        """The route of a decode round's state update over `slots`
        lanes (ops/pallas_ssm.resolve_route; /stats `ssm_update_impl`)."""
        cfg = self.config
        return pallas_ssm.resolve_route(
            cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.n_groups, cfg.conv_dim, cfg.conv_kernel, cfg.dtype, slots)

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
                'NemotronH serves through the page pool only: give the '
                'config kv_total_pages (serve_lm --continuous-batching '
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
        for i, kind in enumerate(cfg.pattern):
            x = Block(cfg, kind, name=f'layer_{i}')(
                x, positions, decode, page_indices, prefill, page_aligned,
                live, slots)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name='final_norm')(x)
        head = self.param(
            'lm_head',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ('embed', 'vocab')),
            (cfg.embed_dim, cfg.vocab_size), F32)
        if return_hidden:
            return nn.with_logical_constraint(
                x, ('batch', 'seq', 'act_embed'))
        logits = jnp.einsum('bse,ev->bsv', x.astype(cfg.dtype),
                            head.astype(cfg.dtype),
                            preferred_element_type=(cfg.logits_dtype or
                                                    F32))
        return nn.with_logical_constraint(logits, ('batch', 'seq', 'vocab'))
