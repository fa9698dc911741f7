"""Plain reference for `"family": "nemotron_h"` configurations.

The Nemotron-H forward pass in straightforward `jax.numpy` and float32:
no kernels, no cache, no batching, one sequence at a time, the sizes read
from the configuration FILE. A block is `x + mixer(RMSNorm(x))`, ONE
mixer a block by its letter in `hybrid_override_pattern`; after the last
a final RMSNorm and the untied head.

  M, Mamba-2.  [z | xBC | dt] = u W_in (widths H P, H P + 2 G N, H).
    xBC = silu(conv(xBC) + bias): a causal depthwise convolution of
    `conv_kernel` taps, zeros before the sequence; split into x [H, P],
    B [G, N], C [G, N] (H / G heads share a group's B and C).
    dt = softplus(dt + dt_bias), A = -exp(A_log), a scalar a head. The
    state h [H, P, N], zeros before the sequence, STEP BY STEP over time
    (`lax.scan`; not the chunked form the program computes):
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
      y_t = h_t C_t + D x_t
    then y = RMSNorm over each of the G groups of (y * silu(z)), times a
    learned scale, and y W_out.
  *, attention.  q, k, v = h W_q, h W_k, h W_v; `num_attention_heads`
    query heads share `num_key_value_heads` K/V heads in order; NO
    rotary embedding; causal softmax at scale head_dim^-1/2; W_o.
  E, experts in a latent.  s = sigmoid(h W_r) over ALL published
    experts (`reduced_from.n_routed_experts`); the choice is the
    `num_experts_per_tok` best of s + e_score_correction_bias (`n_group`
    groups of which `topk_group` stay: one of one, as published);
    weights s_i / sum s_i over the chosen, times `routed_scaling_factor`.
    u = h W_down (`moe_latent_size`); an expert is W2 relu(u W1)^2; r =
    sum over the chosen experts HELD HERE (`expert_offset`,
    `experts_held`) of w_i expert_i(u): the share the configuration
    states; output r W_up + shared(h), the shared expert W2 relu(h W1)^2
    at the full width. What the absent experts would add is left out, as
    in the program.

The weights are the ones the server holds (bf16 values), upcast to
float32 here, and every product runs at `highest` precision. The experts
are computed ONE AT A TIME over all positions (a held expert's two
matrices in float32 are 22 MB), and attention a block of heads at a
time, so that 2,048 positions and 128 experts fit on the chip beside the
server.

The control (`weights='int8'`): the same pass with every matrix product
by a weight taken in int8 (the matrix rounded per output channel, the
activations per row), as references/llama.py has it; the recurrence's
own elementwise arithmetic stays float32.

Independent of `skypilot_tpu/models/nemotron_h.py`, ops/ssm.py, the
engine and the page pool; it takes from the program only the parameter
tree's names.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8


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


def relu2(h, w_up, w_down, weights: str):
    return matmul(jnp.square(jax.nn.relu(matmul(h, w_up, weights))),
                  w_down, weights)


def mamba(p, u, s: Dict[str, Any], weights: str):
    t = u.shape[0]
    heads, hd, n, g, taps = (s['mamba_heads'], s['mamba_head_dim'],
                             s['state'], s['groups'], s['conv_kernel'])
    inner, bc = heads * hd, g * n
    zxbcdt = matmul(u, p['in_proj']['kernel'], weights)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * bc],
                  zxbcdt[:, 2 * inner + 2 * bc:])
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32),
                              xbc])
    conv = p['conv_bias'].astype(F32) + sum(
        padded[k:k + t] * p['conv_weight'][k].astype(F32)
        for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, heads, hd)
    b = jnp.repeat(xbc[:, inner:inner + bc].reshape(t, g, n), heads // g, 1)
    c = jnp.repeat(xbc[:, inner + bc:].reshape(t, g, n), heads // g, 1)
    dt = jax.nn.softplus(dt + p['dt_bias'].astype(F32))        # [T, H]
    a = -jnp.exp(p['A_log'].astype(F32))

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = (h * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), F32),
                        (x, b, c, dt))
    y = y + p['D'].astype(F32)[None, :, None] * x
    gated = (y.reshape(t, g, inner // g)
             * jax.nn.silu(z).reshape(t, g, inner // g))
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    gated = (gated * jax.lax.rsqrt(var + s['eps'])).reshape(t, inner)
    return matmul(gated * p['norm_scale'].astype(F32),
                  p['out_proj']['kernel'], weights)


def attention(p, h, s: Dict[str, Any], weights: str):
    t = h.shape[0]
    heads, kv, hd = s['heads'], s['kv_heads'], s['head_dim']
    q = matmul(h, p['wq']['kernel'], weights).reshape(t, heads, hd)
    k = matmul(h, p['wk']['kernel'], weights).reshape(t, kv, hd)
    v = matmul(h, p['wv']['kernel'], weights).reshape(t, kv, hd)
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
    """f32[T, all experts]: a chosen expert's weight, 0 elsewhere."""
    t = h.shape[0]
    score = jax.nn.sigmoid(matmul(h, p['router']['kernel'], weights))
    choice = score + p['e_score_correction_bias'].astype(F32)
    n_exp, groups = choice.shape[1], s['n_group']
    grouped = choice.reshape(t, groups, n_exp // groups)
    group_score = jnp.sum(
        jax.lax.top_k(grouped, min(2, n_exp // groups))[0], axis=-1)
    _, best_groups = jax.lax.top_k(group_score, s['topk_group'])
    allowed = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], best_groups].set(True)
    choice = jnp.where(jnp.repeat(allowed, n_exp // groups, axis=1),
                       choice, -jnp.inf)
    _, chosen = jax.lax.top_k(choice, s['per_token'])
    picked = jnp.take_along_axis(score, chosen, axis=1)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True) * s['scaling']
    return jnp.zeros((t, n_exp), F32).at[
        jnp.arange(t)[:, None], chosen].set(picked)


_norm = jax.jit(rms_norm, static_argnames=('eps',))
_mamba = jax.jit(lambda p, x, sizes, weights: x + mamba(
    p['mixer'], rms_norm(x, p['norm']['scale'], dict(sizes)['eps']),
    dict(sizes), weights), static_argnames=('sizes', 'weights'))
_attention = jax.jit(lambda p, x, sizes, weights: x + attention(
    p['mixer'], rms_norm(x, p['norm']['scale'], dict(sizes)['eps']),
    dict(sizes), weights), static_argnames=('sizes', 'weights'))
_route = jax.jit(lambda p, h, sizes, weights: route(p, h, dict(sizes),
                                                    weights),
                 static_argnames=('sizes', 'weights'))
_matmul = jax.jit(matmul, static_argnames=('weights',))
_relu2 = jax.jit(relu2, static_argnames=('weights',))


@jax.jit
def _add_weighted(acc, part, weight):
    return acc + weight[:, None] * part


def experts(p, x, s: Dict[str, Any], weights: str):
    """x + shared(h) + (this share's routed experts' sum) W_up, an
    expert at a time over all positions."""
    h = _norm(x, p['norm']['scale'], eps=s['eps'])
    p = p['mixer']
    weight_of = _route({k: p[k] for k in ('router',
                                          'e_score_correction_bias')},
                       h, sizes=tuple(sorted(s.items())), weights=weights)
    u = _matmul(h, p['latent_down']['kernel'], weights=weights)
    r = jnp.zeros_like(u)
    for e in range(s['expert_offset'], s['expert_offset'] + s['held']):
        w = p[f'expert_{e}']
        r = _add_weighted(r, _relu2(u, w['w_up'], w['w_down'],
                                    weights=weights), weight_of[:, e])
    shared = _relu2(h, p['shared']['w_up']['kernel'],
                    p['shared']['w_down']['kernel'], weights=weights)
    return x + shared + _matmul(r, p['latent_up']['kernel'],
                                weights=weights)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def head(x, scale, head_w, eps, weights: str = 'float32'):
    return jax.nn.log_softmax(
        matmul(rms_norm(x, scale, eps), head_w, weights), axis=-1)


_head = jax.jit(head, static_argnames=('eps', 'weights'))


def sizes_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {
        'eps': float(cfg['norm_eps']),
        'mamba_heads': cfg['mamba_num_heads'],
        'mamba_head_dim': cfg['mamba_head_dim'],
        'state': cfg['ssm_state_size'], 'groups': cfg['n_groups'],
        'conv_kernel': cfg['conv_kernel'],
        'heads': cfg['num_attention_heads'],
        'kv_heads': cfg['num_key_value_heads'],
        'head_dim': cfg['head_dim'],
        'n_group': cfg['n_group'], 'topk_group': cfg['topk_group'],
        'per_token': cfg['num_experts_per_tok'],
        'scaling': float(cfg['routed_scaling_factor']),
        'held': cfg['experts_held'],
        'expert_offset': cfg['expert_offset'],
    }


def log_probs(params: Dict[str, Any], cfg: Dict[str, Any],
              tokens: List[int], weights: str = 'float32'):
    """[T, vocab] float32: row i holds log P(token i+1 | tokens 0..i)."""
    s = sizes_of(cfg)
    sizes = tuple(sorted(s.items()))
    with jax.default_matmul_precision('highest'):
        x = _embed(params['tok_embed'], jnp.asarray(tokens, jnp.int32))
        for i, kind in enumerate(cfg['hybrid_override_pattern']):
            p = params[f'layer_{i}']
            if kind == 'M':
                x = _mamba(p, x, sizes=sizes, weights=weights)
            elif kind == '*':
                x = _attention(p, x, sizes=sizes, weights=weights)
            elif kind == 'E':
                x = experts(p, x, s, weights)
            else:
                raise ValueError(f'no reference for a {kind!r} block')
        return _head(x, params['final_norm']['scale'], params['lm_head'],
                     float(cfg['norm_eps']), weights=weights)


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
