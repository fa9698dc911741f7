"""Plain reference for `"family": "lfm2"` configurations.

LFM2's forward pass (`lfm2_moe`) in straightforward `jax.numpy` and
float32: no kernels, no cache, no batching, one sequence at a time, the
sizes read from the configuration FILE. A layer is x + mixer(RMSNorm(x)),
then x + ffn(RMSNorm(x)); after the last a final RMSNorm and the head,
the embedding's own table (tied).

  conv.  [B | C | x] = h W_in (three of `hidden_size`); u_t = B_t x_t;
    v_t = sum over j = 0..K-1 of w_j u_{t-K+1+j}, K = `conv_L_cache`
    taps, each a vector over the channels (depthwise), u zeros before
    the sequence, written out as that explicit sum; y = (C v) W_out.
    No bias, no activation.
  full_attention.  q, k, v = h W_q, h W_k, h W_v in heads of
    hidden_size / num_attention_heads; q and k each RMS-normed over a
    head's values with a learned scale (eps `norm_eps`), then RoPE
    (rotate-half over the whole head, theta `rope_parameters.rope_theta`);
    `num_attention_heads` query heads share `num_key_value_heads` K/V
    heads in order; causal softmax at scale head_dim^-1/2; W_o.
  ffn.  Below `num_dense_layers`: W_down (silu(h W_gate) (h W_up)) at
    `intermediate_size`. Above: s = sigmoid(h W_r) over the `num_experts`
    experts; the `num_experts_per_tok` largest of s + expert bias are
    chosen; weights s_i / sum s_i over the chosen, times
    `routed_scaling_factor`; y = sum over the chosen of w_i expert_i(h),
    an expert the same SwiGLU at `moe_intermediate_size`. No shared
    expert.

The weights are the ones the server holds (bf16 values), upcast to
float32 here, and every product runs at `highest` precision. The
experts are computed ONE AT A TIME over all positions, and attention a
block of heads at a time, so that 2,048 positions and 64 experts fit on
the chip beside the server.

The control (`weights='int8'`): the same pass with every matrix product
by a weight taken in int8 (the matrix rounded per output channel, the
activations per row), as references/llama.py has it; the convolution's
and the gates' elementwise arithmetic stays float32.

Independent of `skypilot_tpu/models/lfm2.py`, ops/, the engine and the
page pool; it takes from the program only the parameter tree's names.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8
VOCAB_BLOCK = 16384


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _int8(x, axis: int):
    """x rounded to 255 levels along `axis` and multiplied back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(h, w, weights: str):
    """h [T, in] @ w [in, out] in float32 on what the server holds, or
    (the control) both rounded to int8: the matrix per output channel,
    the activations per row."""
    w = w.astype(F32)
    if weights == 'float32':
        return h @ w
    if weights != 'int8':
        raise ValueError(f'unknown weights {weights!r}')
    return _int8(h, -1) @ _int8(w, 0)


def swiglu(w_gate, w_up, w_down, h, weights: str):
    return matmul(jax.nn.silu(matmul(h, w_gate, weights))
                  * matmul(h, w_up, weights), w_down, weights)


def rope(x, theta: float):
    """x [T, heads, D]: rotate-half RoPE at positions 0..T-1."""
    t, half = x.shape[0], x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def short_conv(p, h, s: Dict[str, Any], weights: str):
    t, d, taps = h.shape[0], s['hidden'], s['taps']
    bcx = matmul(h, p['in_proj']['kernel'], weights)
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = jnp.concatenate([jnp.zeros((taps - 1, d), F32), b * x])
    w = p['conv_weight'].astype(F32)                       # [K, hidden]
    v = sum(w[j] * u[j:j + t] for j in range(taps))
    return matmul(c * v, p['out_proj']['kernel'], weights)


def attention(p, h, s: Dict[str, Any], weights: str):
    t = h.shape[0]
    heads, kv, hd = s['heads'], s['kv_heads'], s['head_dim']
    q = matmul(h, p['wq']['kernel'], weights).reshape(t, heads, hd)
    k = matmul(h, p['wk']['kernel'], weights).reshape(t, kv, hd)
    v = matmul(h, p['wv']['kernel'], weights).reshape(t, kv, hd)
    q = rope(rms_norm(q, p['q_layernorm']['scale'], s['eps']), s['theta'])
    k = rope(rms_norm(k, p['k_layernorm']['scale'], s['eps']), s['theta'])
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for lo in range(0, heads, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, heads)
        of = jnp.arange(lo, hi) // (heads // kv)     # a head's K/V head
        scores = jnp.einsum('qhd,khd->hqk', q[:, lo:hi], k[:, of]
                            ) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        outs.append(jnp.einsum('hqk,khd->qhd', probs, v[:, of]))
    out = jnp.concatenate(outs, axis=1).reshape(t, heads * hd)
    return matmul(out, p['wo']['kernel'], weights)


def route(p, h, s: Dict[str, Any], weights: str):
    """f32[T, experts]: a chosen expert's weight, 0 elsewhere."""
    t = h.shape[0]
    score = jax.nn.sigmoid(matmul(h, p['router']['kernel'], weights))
    _, chosen = jax.lax.top_k(
        score + p['e_score_correction_bias'].astype(F32), s['per_token'])
    picked = jnp.take_along_axis(score, chosen, axis=1)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True) * s['scaling']
    return jnp.zeros((t, score.shape[1]), F32).at[
        jnp.arange(t)[:, None], chosen].set(picked)


def _mixer(p, x, sizes, weights, kind):
    s = dict(sizes)
    h = rms_norm(x, p['operator_norm']['scale'], s['eps'])
    if kind == 'conv':
        return x + short_conv(p['conv'], h, s, weights)
    return x + attention(p['self_attn'], h, s, weights)


def _dense(p, x, eps, weights):
    h = rms_norm(x, p['ffn_norm']['scale'], eps)
    f = p['feed_forward']
    return x + swiglu(f['w_gate']['kernel'], f['w_up']['kernel'],
                      f['w_down']['kernel'], h, weights)


_mixer_jit = jax.jit(_mixer, static_argnames=('sizes', 'weights', 'kind'))
_dense_jit = jax.jit(_dense, static_argnames=('eps', 'weights'))
_norm = jax.jit(rms_norm, static_argnames=('eps',))
_route = jax.jit(lambda p, h, sizes, weights: route(p, h, dict(sizes),
                                                    weights),
                 static_argnames=('sizes', 'weights'))
_swiglu = jax.jit(swiglu, static_argnames=('weights',))


@jax.jit
def _add_weighted(acc, part, weight):
    return acc + weight[:, None] * part


def experts(p, x, s: Dict[str, Any], weights: str):
    """x + the routed experts' weighted sum, an expert at a time over
    all positions."""
    h = _norm(x, p['ffn_norm']['scale'], eps=s['eps'])
    f = p['feed_forward']
    weight_of = _route({k: f[k] for k in ('router',
                                          'e_score_correction_bias')},
                       h, sizes=tuple(sorted(s.items())), weights=weights)
    out = x
    for e in range(s['experts']):
        w = f[f'expert_{e}']
        out = _add_weighted(out, _swiglu(w['w_gate'], w['w_up'],
                                         w['w_down'], h, weights=weights),
                            weight_of[:, e])
    return out


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def head(x, scale, table, eps, weights: str = 'float32'):
    """Log-probabilities through the tied head: the table, transposed,
    a block of `VOCAB_BLOCK` rows of it at a time (a float32 copy of
    the whole table beside the server's would be 537 MB more)."""
    h = rms_norm(x, scale, eps)
    logits = jnp.concatenate([
        matmul(h, table[lo:lo + VOCAB_BLOCK].T, weights)
        for lo in range(0, table.shape[0], VOCAB_BLOCK)], axis=-1)
    return jax.nn.log_softmax(logits, axis=-1)


_head = jax.jit(head, static_argnames=('eps', 'weights'))


def sizes_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    return {
        'eps': float(cfg['norm_eps']), 'hidden': d,
        'taps': cfg['conv_L_cache'], 'heads': heads,
        'kv_heads': cfg['num_key_value_heads'], 'head_dim': d // heads,
        'theta': float(cfg['rope_parameters']['rope_theta']),
        'experts': cfg['num_experts'],
        'per_token': cfg['num_experts_per_tok'],
        'scaling': float(cfg['routed_scaling_factor']),
    }


def log_probs(params: Dict[str, Any], cfg: Dict[str, Any],
              tokens: List[int], weights: str = 'float32'):
    """[T, vocab] float32: row i holds log P(token i+1 | tokens 0..i)."""
    s = sizes_of(cfg)
    sizes = tuple(sorted(s.items()))
    with jax.default_matmul_precision('highest'):
        x = _embed(params['tok_embed'], jnp.asarray(tokens, jnp.int32))
        for i, kind in enumerate(cfg['layer_types']):
            if kind not in ('conv', 'full_attention'):
                raise ValueError(f'no reference for a {kind!r} layer')
            p = params[f'layer_{i}']
            mixer = 'conv' if kind == 'conv' else 'self_attn'
            x = _mixer_jit({k: p[k] for k in ('operator_norm', mixer)}, x,
                           sizes=sizes, weights=weights, kind=kind)
            if i < cfg['num_dense_layers']:
                x = _dense_jit(p, x, eps=s['eps'], weights=weights)
            else:
                x = experts(p, x, s, weights)
        return _head(x, params['final_norm']['scale'], params['tok_embed'],
                     s['eps'], weights=weights)


def control_gaps(params: Dict[str, Any], cfg: Dict[str, Any],
                 tokens: List[int], first: int, last: int):
    """Per position first..last-1 of `tokens`, how far the int8 pass's
    first choice for it scores below the float32 reference's best."""
    ref = log_probs(params, cfg, tokens)[first - 1:last - 1]
    low = log_probs(params, cfg, tokens, weights='int8')[first - 1:last - 1]
    pick = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - chosen


def control_shortfall(params: Dict[str, Any], cfg: Dict[str, Any],
                      tokens: List[int], first: int, last: int) -> float:
    """The widest of `control_gaps`."""
    return float(jnp.max(control_gaps(params, cfg, tokens, first, last)))
