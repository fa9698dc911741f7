"""Plain reference for `"family": "llama"` configurations.

The decoder's forward pass in straightforward `jax.numpy` and float32:
no kernels, no cache, no batching, one sequence at a time. It follows the
published architecture (Llama / Mistral-7B: token embedding; per layer a
pre-norm RMSNorm, grouped-query causal attention with rotate-half RoPE
on queries and keys, a residual, a second RMSNorm, a SwiGLU feed-forward,
a residual; a final RMSNorm; an untied output head) and reads the sizes
from the configuration FILE, not from the program. Departures from the
published model: none in the mathematics; the weights are the ones the
server holds (bf16 values), upcast to float32 here, and every matmul runs
at `highest` precision (a TPU would otherwise round float32 operands to
bf16).

Independent of `skypilot_tpu/models/llama.py`, the engine, the paged
cache and the attention kernels; it takes from the program only the
parameter tree's names (`tok_embed`, `layer_<i>/attn/{wq,wk,wv,wo}`,
`layer_<i>/mlp/{w_gate,w_up,w_down}`, the three norms, `lm_head`).
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, theta):
    """x: [T, H, D]; rotate-half rotary embedding at positions 0..T-1."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(p: Dict[str, Any], x, *, heads: int, kv_heads: int,
          theta: float, eps: float):
    """One decoder layer on x: [T, d]."""
    t, d = x.shape
    hd = d // heads
    w = lambda path: p[path[0]][path[1]]['kernel'].astype(F32)
    h = rms_norm(x, p['attn_norm']['scale'], eps)
    q = rope((h @ w(('attn', 'wq'))).reshape(t, heads, hd), theta)
    k = rope((h @ w(('attn', 'wk'))).reshape(t, kv_heads, hd), theta)
    v = (h @ w(('attn', 'wv'))).reshape(t, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, d) @ w(('attn', 'wo'))
    h = rms_norm(x, p['mlp_norm']['scale'], eps)
    gate = jax.nn.silu(h @ w(('mlp', 'w_gate')))
    return x + (gate * (h @ w(('mlp', 'w_up')))) @ w(('mlp', 'w_down'))


_layer = jax.jit(layer, static_argnames=('heads', 'kv_heads', 'theta',
                                         'eps'))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def _head(x, scale, head, eps):
    return jax.nn.log_softmax(
        rms_norm(x, scale, eps) @ head.astype(F32), axis=-1)


def log_probs(params: Dict[str, Any], cfg: Dict[str, Any],
              tokens: List[int]):
    """[T, vocab] float32: row i holds log P(token i+1 | tokens 0..i)."""
    with jax.default_matmul_precision('highest'):
        x = _embed(params['tok_embed'], jnp.asarray(tokens, jnp.int32))
        for i in range(cfg['num_hidden_layers']):
            x = _layer(params[f'layer_{i}'], x,
                       heads=cfg['num_attention_heads'],
                       kv_heads=cfg['num_key_value_heads'],
                       theta=float(cfg['rope_theta']),
                       eps=float(cfg['rms_norm_eps']))
        return _head(x, params['final_norm']['scale'], params['lm_head'],
                     float(cfg['rms_norm_eps']))
