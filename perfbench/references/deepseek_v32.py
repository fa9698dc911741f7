"""Plain reference for `"family": "deepseek_v32"` configurations.

DeepSeek-V3.2's forward pass in straightforward `jax.numpy` and float32:
no kernels, no cache, no batching, one sequence at a time, the sizes read
from the configuration FILE. Every layer, on a token's hidden state x at
position t:

  MLA.  c_q = RMSNorm(x W_qa); q = c_q W_qb, a head's 192 values split
    into q_nope (128) and q_rope (64, RoPE on interleaved pairs).
    [c_kv | k_rope] = x W_kva; c_kv = RMSNorm(c_kv) (512), k_rope RoPE'd
    (64, one for all heads). k_nope_h = c_kv W_uk,h, v_h = c_kv W_uv,h.
    Score of query t on key s: (q_nope_h . k_nope_h,s + q_rope_h .
    k_rope,s) x 192^-1/2 x mscale^2, mscale = 0.1 ln(factor) + 1; the
    inverse frequencies are YaRN's blend (beta_fast 32, beta_slow 1 over
    the original 4096 positions), always on. Softmax over the SELECTED s
    only, sum_s p v_h,s, concatenated, times W_o.
  Indexer.  q^I = c_q W^I_qb (64 heads of 128), k^I = LayerNorm(x W^I_k)
    (128), RoPE on the first 64 of each, on the two halves; w = x W^I_w x
    64^-1/2 x 128^-1/2; I(t,s) = sum_j w_j ReLU(q^I_j . k^I_s), s <= t.
    Selected: the min(index_topk, t+1) largest (equal scores: the earlier
    position, `lax.top_k`'s order).
  Experts (layers from `first_k_dense_replace` on; a dense SwiGLU
    before).  s = sigmoid(x W_g) over ALL published experts; choice
    scores s + e_score_correction_bias; a group scores the sum of its two
    best; the `topk_group` best groups stay; the `num_experts_per_tok`
    best choice scores among them are chosen; weights s_i / sum s_i over
    the chosen, times `routed_scaling_factor`. Output shared(x) + sum over
    the chosen experts HELD HERE (`expert_offset`, `experts_held`) of
    w_i expert_i(x): the share the configuration states. What the absent
    experts would add is left out, as in the program.

Departures from the published model, all in the configuration file's
`assumed`: the indexer's Hadamard rotation and FP8 are left out (the
rotation is orthogonal and cancels in q . k); the weights are the ones the
server holds (bf16 values), upcast to float32 here, and every product runs
at `highest` precision. Attention is computed a block of heads at a time,
and the index scores a block of index heads at a time, so that 4,096
positions fit on the chip beside the server.

The control (`weights='int8'`): the same pass with every matrix product
by a weight taken in int8 (the matrix rounded per output channel, the
activations per row), as references/llama.py has it.

Independent of `skypilot_tpu/models/deepseek.py`, the engine, the page
pool and ops/sparse_latent.py; it takes from the program only the
parameter tree's names.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8
WIDTH_BLOCK = 4608


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32)
            + bias.astype(F32))


def yarn_inv_freq(dim: int, theta: float, scaling: Dict[str, Any]):
    """[dim / 2] inverse frequencies: the published ones where a pair
    turns more than `beta_fast` times over the original context, those
    divided by `factor` where it turns less than `beta_slow` times, a
    linear ramp between."""
    pair = jnp.arange(dim // 2, dtype=F32)
    freqs = 1.0 / (theta ** (pair / (dim // 2)))
    if not scaling:
        return freqs
    original = scaling['original_max_position_embeddings']

    def pair_turning(times):
        return (dim * math.log(original / (times * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(scaling['beta_fast'])), 0)
    high = min(math.ceil(pair_turning(scaling['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    return freqs / scaling['factor'] * ramp + freqs * (1.0 - ramp)


def rope(x, inv_freq, interleaved: bool):
    """x [T, H, D] rotated at positions 0..T-1: pairs (x0,x1),(x2,x3)..
    when `interleaved`, else (x_i, x_{i+D/2})."""
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def swiglu(p, h, weights: str, kernel=lambda leaf: leaf['kernel']):
    """silu(h W_gate) * (h W_up), times W_down; `WIDTH_BLOCK` of the
    inner width at a time, so that the dense layer's 18,432 never stand
    in float32 whole."""
    w_gate, w_up, w_down = (kernel(p[name])
                            for name in ('w_gate', 'w_up', 'w_down'))
    out = 0.0
    for lo in range(0, w_gate.shape[1], WIDTH_BLOCK):
        hi = lo + WIDTH_BLOCK
        gate = jax.nn.silu(matmul(h, w_gate[:, lo:hi], weights))
        out += matmul(gate * matmul(h, w_up[:, lo:hi], weights),
                      w_down[lo:hi], weights)
    return out


def selection(p, h, c_q, s: Dict[str, Any], weights: str):
    """bool[T, T]: which keys each query attends (causal, and its
    `index_topk` best by the lightning indexer's score)."""
    t = h.shape[0]
    heads, dim, rot = s['index_n_heads'], s['index_head_dim'], s['rope']
    inv_freq = yarn_inv_freq(rot, s['theta'], s['yarn'])
    q = matmul(c_q, p['wq_b']['kernel'], weights).reshape(t, heads, dim)
    q = jnp.concatenate([rope(q[..., :rot], inv_freq, False),
                         q[..., rot:]], -1)
    k = layer_norm(matmul(h, p['wk']['kernel'], weights),
                   p['k_norm']['scale'], p['k_norm']['bias'], s['eps'])
    k = jnp.concatenate([rope(k[:, None, :rot], inv_freq, False)[:, 0],
                         k[:, rot:]], -1)
    w = (matmul(h, p['weights_proj']['kernel'], weights)
         * heads ** -0.5 * dim ** -0.5)
    scores = jnp.zeros((t, t), F32)
    for lo in range(0, heads, HEAD_BLOCK):
        part = jnp.einsum('qhd,kd->qhk', q[:, lo:lo + HEAD_BLOCK], k)
        scores += jnp.sum(jax.nn.relu(part)
                          * w[:, lo:lo + HEAD_BLOCK, None], axis=1)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    _, best = jax.lax.top_k(scores, min(s['index_topk'], t))
    chosen = jnp.zeros((t, t), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    return chosen & causal


def attention(p, h, s: Dict[str, Any], weights: str):
    t = h.shape[0]
    heads, nope, rot, vdim, rank = (s['heads'], s['nope'], s['rope'],
                                    s['v'], s['kv_rank'])
    inv_freq = yarn_inv_freq(rot, s['theta'], s['yarn'])
    c_q = rms_norm(matmul(h, p['wq_a']['kernel'], weights),
                   p['q_norm']['scale'], s['eps'])
    q = matmul(c_q, p['wq_b']['kernel'], weights).reshape(
        t, heads, nope + rot)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv_freq, True)
    kv = matmul(h, p['wkv_a']['kernel'], weights)
    c_kv = rms_norm(kv[:, :rank], p['kv_norm']['scale'], s['eps'])
    k_rope = rope(kv[:, None, rank:], inv_freq, True)[:, 0]
    keep = selection(p['index_proj'], h, c_q, s, weights)
    scale = (nope + rot) ** -0.5
    if s['yarn']:
        mscale = (0.1 * s['yarn']['mscale_all_dim']
                  * math.log(s['yarn']['factor']) + 1.0)
        scale *= mscale * mscale
    outs = []
    for lo in range(0, heads, HEAD_BLOCK):
        n = min(HEAD_BLOCK, heads - lo)
        w_b = p['wkv_b'][:, lo:lo + n].reshape(rank, n * (nope + vdim))
        kv_b = matmul(c_kv, w_b, weights).reshape(t, n, nope + vdim)
        k_nope, v = kv_b[..., :nope], kv_b[..., nope:]
        scores = (jnp.einsum('qhd,khd->hqk', q_nope[:, lo:lo + n], k_nope)
                  + jnp.einsum('qhr,kr->hqk', q_rope[:, lo:lo + n], k_rope)
                  ) * scale
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum('hqk,khd->qhd', probs, v))
    out = jnp.concatenate(outs, axis=1).reshape(t, heads * vdim)
    return matmul(out, p['wo']['kernel'], weights)


def experts(p, h, s: Dict[str, Any], weights: str):
    """The shared expert plus this share's part of the routed ones."""
    t = h.shape[0]
    logits = matmul(h, p['router']['kernel'], weights)
    score = jax.nn.sigmoid(logits)
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
    # [T, all experts]: a chosen expert's weight, 0 elsewhere.
    weight_of = jnp.zeros((t, n_exp), F32).at[
        jnp.arange(t)[:, None], chosen].set(picked)
    out = swiglu(p['shared'], h, weights)
    for e in range(s['expert_offset'], s['expert_offset'] + s['held']):
        out += weight_of[:, e:e + 1] * swiglu(
            p[f'expert_{e}'], h, weights, kernel=lambda leaf: leaf)
    return out


def layer(p: Dict[str, Any], x, *, sizes, routed: bool,
          weights: str = 'float32'):
    """One decoder layer on x: [T, d]."""
    s = dict(sizes)
    s['yarn'] = dict(s['yarn']) if s['yarn'] else None
    x = x + attention(p['attn'], rms_norm(x, p['attn_norm']['scale'],
                                          s['eps']), s, weights)
    h = rms_norm(x, p['mlp_norm']['scale'], s['eps'])
    if routed:
        return x + experts(p['mlp'], h, s, weights)
    return x + swiglu(p['mlp'], h, weights)


_layer = jax.jit(layer, static_argnames=('sizes', 'routed', 'weights'))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def head(x, scale, head_w, eps, weights: str = 'float32'):
    return jax.nn.log_softmax(
        matmul(rms_norm(x, scale, eps), head_w, weights), axis=-1)


_head = jax.jit(head, static_argnames=('eps', 'weights'))


def sizes_of(cfg: Dict[str, Any]):
    """The file's sizes as a hashable tuple of pairs (a jit's static
    argument)."""
    yarn = cfg.get('rope_scaling')
    return tuple(sorted({
        'heads': cfg['num_attention_heads'],
        'nope': cfg['qk_nope_head_dim'], 'rope': cfg['qk_rope_head_dim'],
        'v': cfg['v_head_dim'], 'kv_rank': cfg['kv_lora_rank'],
        'theta': float(cfg['rope_theta']),
        'eps': float(cfg['rms_norm_eps']),
        'yarn': tuple(sorted(yarn.items())) if yarn else None,
        'index_n_heads': cfg['index_n_heads'],
        'index_head_dim': cfg['index_head_dim'],
        'index_topk': cfg['index_topk'],
        'n_group': cfg['n_group'], 'topk_group': cfg['topk_group'],
        'per_token': cfg['num_experts_per_tok'],
        'scaling': float(cfg['routed_scaling_factor']),
        'held': cfg['experts_held'],
        'expert_offset': cfg['expert_offset'],
    }.items()))


def log_probs(params: Dict[str, Any], cfg: Dict[str, Any],
              tokens: List[int], weights: str = 'float32'):
    """[T, vocab] float32: row i holds log P(token i+1 | tokens 0..i)."""
    sizes = sizes_of(cfg)
    with jax.default_matmul_precision('highest'):
        x = _embed(params['tok_embed'], jnp.asarray(tokens, jnp.int32))
        for i in range(cfg['num_hidden_layers']):
            x = _layer(params[f'layer_{i}'], x, sizes=sizes,
                       routed=i >= cfg['first_k_dense_replace'],
                       weights=weights)
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
