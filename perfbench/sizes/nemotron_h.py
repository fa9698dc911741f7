"""Sizes of `"family": "nemotron_h"` configurations
(models/nemotron_h.py): ONE mixer a block by its letter in
`hybrid_override_pattern`, each behind one RMSNorm. `M`: a Mamba-2
mixer (`in_proj` to z, x, B, C and dt; a depthwise convolution with
bias over x, B and C; `dt_bias`, `A_log` and `D` a head; a gated norm's
scale; `out_proj`). `*`: grouped-query attention, no bias. `E`: a
router over the PUBLISHED expert count (`reduced_from.n_routed_experts`)
with its correction bias, the latent's two projections, one shared
expert of two matrices at the full width and the `experts_held` routed
experts of two matrices in the latent that this chip holds. Untied head
over the vocabulary slice."""
from typing import Any, Dict


def _mamba(cfg: Dict[str, Any]):
    """(matrices, other parameters) of one Mamba-2 mixer."""
    d = cfg['hidden_size']
    heads = cfg['mamba_num_heads']
    inner = heads * cfg['mamba_head_dim']
    conv = inner + 2 * cfg['n_groups'] * cfg['ssm_state_size']
    matrices = d * (inner + conv + heads) + inner * d
    return matrices, conv * (cfg['conv_kernel'] + 1) + 3 * heads + inner


def _attention(cfg: Dict[str, Any]) -> int:
    d, hd = cfg['hidden_size'], cfg['head_dim']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    return 2 * d * heads * hd + 2 * d * kv * hd


def _router_width(cfg: Dict[str, Any]) -> int:
    return (cfg.get('reduced_from') or {}).get('n_routed_experts',
                                               cfg['n_routed_experts'])


def _experts_fixed(cfg: Dict[str, Any]) -> int:
    """The matrices of an expert layer every token multiplies: router,
    latent down and up, the shared expert."""
    d = cfg['hidden_size']
    return (d * _router_width(cfg) + 2 * d * cfg['moe_latent_size']
            + cfg['n_shared_experts'] * 2 * d
            * cfg['moe_shared_expert_intermediate_size'])


def _expert(cfg: Dict[str, Any]) -> int:
    return 2 * cfg['moe_latent_size'] * cfg['moe_intermediate_size']


def _count(cfg: Dict[str, Any], letter: str) -> int:
    pattern = cfg['hybrid_override_pattern']
    if len(pattern) != cfg['num_hidden_layers'] or set(pattern) - set('M*E'):
        raise ValueError(f'hybrid_override_pattern {pattern!r} is not '
                         f'{cfg["num_hidden_layers"]} letters of M, * and E')
    return pattern.count(letter)


def matrices(cfg: Dict[str, Any]) -> int:
    """Every matrix the chip holds (no norms, no vectors)."""
    return (2 * cfg['vocab_size'] * cfg['hidden_size']
            + _count(cfg, 'M') * _mamba(cfg)[0]
            + _count(cfg, '*') * _attention(cfg)
            + _count(cfg, 'E') * (_experts_fixed(cfg)
                                  + cfg['experts_held'] * _expert(cfg)))


def params(cfg: Dict[str, Any]) -> int:
    d = cfg['hidden_size']
    vectors = (cfg['num_hidden_layers'] * d + d
               + _count(cfg, 'M') * _mamba(cfg)[1]
               + _count(cfg, 'E') * _router_width(cfg))
    return matrices(cfg) + vectors


def state_bytes_per_slot(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """What a sequence keeps by slot over the Mamba layers: the float32
    state and the convolution's tail in the compute dtype."""
    heads = cfg['mamba_num_heads']
    inner = heads * cfg['mamba_head_dim']
    conv = inner + 2 * cfg['n_groups'] * cfg['ssm_state_size']
    return _count(cfg, 'M') * (inner * cfg['ssm_state_size'] * 4
                               + (cfg['conv_kernel'] - 1) * conv * itemsize)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        'no trainer cell runs a nemotron_h configuration: its state '
        'lives in the serving engine\'s slots and pages (serving only)')


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 x the matrices a token CERTAINLY multiplies in a forward pass:
    every Mamba mixer's two projections, the attention layer's four, and
    of an expert layer the router, the latent's two projections and the
    shared expert. Left out, so that this can only under-read: the
    routed experts (a token multiplies as many of the held ones as the
    router sends it to: under even routing num_experts_per_tok x
    experts_held / published experts = 5.5 of them a layer in
    nemotron3-super-l11-ep4, 30.3M of the 88.6M parameters of an expert
    layer a token then multiplies), the output head, the recurrence's
    own arithmetic and attention over the context."""
    return 2.0 * (_count(cfg, 'M') * _mamba(cfg)[0]
                  + _count(cfg, '*') * _attention(cfg)
                  + _count(cfg, 'E') * _experts_fixed(cfg))
