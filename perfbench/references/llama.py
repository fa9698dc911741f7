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

The control (`weights='int8'`): the same forward pass with every matrix
product taken in int8, the nearest precision below the bf16 the
configuration is served in: the matrix rounded to int8 per output
channel and the activations per row (symmetric, scale = the largest
magnitude / 127), multiplied back. It is what the margin
must refuse: `control_shortfall` reads, at each scored position, how
far below the float32 reference's best the int8 pass's first choice
scores.

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


def _int8(x, axis: int):
    """x rounded to 255 levels along `axis` and multiplied back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(h, w, weights: str):
    """h [T, in] @ w [in, out]: in float32 on what the server holds, or
    (the control) with the matrix rounded to int8 per output channel
    and the activations per row."""
    w = w.astype(F32)
    if weights == 'float32':
        return h @ w
    if weights != 'int8':
        raise ValueError(f'unknown weights {weights!r}')
    return _int8(h, -1) @ _int8(w, 0)


def layer(p: Dict[str, Any], x, *, heads: int, kv_heads: int,
          theta: float, eps: float, weights: str = 'float32'):
    """One decoder layer on x: [T, d]."""
    t, d = x.shape
    hd = d // heads
    mm = lambda h, part, name: matmul(h, p[part][name]['kernel'], weights)
    h = rms_norm(x, p['attn_norm']['scale'], eps)
    q = rope(mm(h, 'attn', 'wq').reshape(t, heads, hd), theta)
    k = rope(mm(h, 'attn', 'wk').reshape(t, kv_heads, hd), theta)
    v = mm(h, 'attn', 'wv').reshape(t, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum('qhd,khd->hqk', q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(scores, axis=-1), v)
    x = x + mm(attn.reshape(t, d), 'attn', 'wo')
    h = rms_norm(x, p['mlp_norm']['scale'], eps)
    gate = jax.nn.silu(mm(h, 'mlp', 'w_gate'))
    return x + mm(gate * mm(h, 'mlp', 'w_up'), 'mlp', 'w_down')


_layer = jax.jit(layer, static_argnames=('heads', 'kv_heads', 'theta',
                                         'eps', 'weights'))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def head(x, scale, head_w, eps, weights: str = 'float32'):
    return jax.nn.log_softmax(
        matmul(rms_norm(x, scale, eps), head_w, weights), axis=-1)


_head = jax.jit(head, static_argnames=('eps', 'weights'))


def log_probs(params: Dict[str, Any], cfg: Dict[str, Any],
              tokens: List[int], weights: str = 'float32'):
    """[T, vocab] float32: row i holds log P(token i+1 | tokens 0..i)."""
    with jax.default_matmul_precision('highest'):
        x = _embed(params['tok_embed'], jnp.asarray(tokens, jnp.int32))
        for i in range(cfg['num_hidden_layers']):
            x = _layer(params[f'layer_{i}'], x,
                       heads=cfg['num_attention_heads'],
                       kv_heads=cfg['num_key_value_heads'],
                       theta=float(cfg['rope_theta']),
                       eps=float(cfg['rms_norm_eps']), weights=weights)
        return _head(x, params['final_norm']['scale'], params['lm_head'],
                     float(cfg['rms_norm_eps']), weights=weights)


def control_shortfall(params: Dict[str, Any], cfg: Dict[str, Any],
                      tokens: List[int], first: int, last: int) -> float:
    """The widest gap, over positions first..last-1 of `tokens`, by
    which the int8 pass's first choice for that position scores below
    the float32 reference's best there."""
    ref = log_probs(params, cfg, tokens)[first - 1:last - 1]
    low = log_probs(params, cfg, tokens, weights='int8')[first - 1:last - 1]
    pick = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(ref, axis=-1) - chosen))
