"""Sizes of `"family": "lfm2"` configurations (models/lfm2.py): a mixer
and a feed-forward a layer, each behind one RMSNorm. The mixer by its
`layer_types` entry: `conv`, the gated short convolution (W_in to B, C
and x, the taps of a depthwise convolution, W_out; no bias);
`full_attention`, grouped-query attention in heads of hidden /
num_attention_heads with a norm's scale over a head for q and for k (no
bias). The feed-forward: a SwiGLU of `intermediate_size` in the first
`num_dense_layers`, else a router over the `num_experts` experts with
its expert bias and the experts, each a SwiGLU of
`moe_intermediate_size`, no shared expert. The head is the embedding
(tied), counted once."""
from typing import Any, Dict


def _count(cfg: Dict[str, Any], kind: str) -> int:
    kinds = list(cfg['layer_types'])
    if (len(kinds) != cfg['num_hidden_layers']
            or set(kinds) - {'conv', 'full_attention'}):
        raise ValueError(f'layer_types is not {cfg["num_hidden_layers"]} '
                         f'entries of conv and full_attention')
    return kinds.count(kind)


def conv_matrices(cfg: Dict[str, Any]) -> int:
    """W_in (to B, C and x) and W_out of one conv mixer."""
    return 4 * cfg['hidden_size'] ** 2


def _attention(cfg: Dict[str, Any]) -> int:
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    kv = cfg['num_key_value_heads']
    hd = d // heads
    return 2 * d * heads * hd + 2 * d * kv * hd


def _expert(cfg: Dict[str, Any]) -> int:
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def _routed_layers(cfg: Dict[str, Any]) -> int:
    return cfg['num_hidden_layers'] - cfg['num_dense_layers']


def matrices(cfg: Dict[str, Any]) -> int:
    """Every matrix the chip holds (no norms, no vectors)."""
    d = cfg['hidden_size']
    return (cfg['vocab_size'] * d
            + _count(cfg, 'conv') * conv_matrices(cfg)
            + _count(cfg, 'full_attention') * _attention(cfg)
            + cfg['num_dense_layers'] * 3 * d * cfg['intermediate_size']
            + _routed_layers(cfg) * (d * cfg['num_experts']
                                     + cfg['num_experts'] * _expert(cfg)))


def params(cfg: Dict[str, Any]) -> int:
    d = cfg['hidden_size']
    hd = d // cfg['num_attention_heads']
    vectors = (cfg['num_hidden_layers'] * 2 * d + d
               + _count(cfg, 'conv') * cfg['conv_L_cache'] * d
               + _count(cfg, 'full_attention') * 2 * hd
               + _routed_layers(cfg) * cfg['num_experts'])
    return matrices(cfg) + vectors


def state_bytes_per_slot(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """What a sequence keeps by slot over the conv layers: the last
    `conv_L_cache` - 1 inputs of the convolution, in the compute dtype."""
    return (_count(cfg, 'conv') * (cfg['conv_L_cache'] - 1)
            * cfg['hidden_size'] * itemsize)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        'no trainer cell runs an lfm2 configuration: its state lives in '
        'the serving engine\'s slots and pages (serving only)')


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 x the matrices a token multiplies in a forward pass: every conv
    mixer's two projections, the attention layers' four, the dense
    SwiGLU, and of an expert layer the router and the
    `num_experts_per_tok` experts it is sent to (all experts are held
    here, so a token multiplies exactly that many). Left out, so that
    this can only under-read: the output head (134.2M at the published
    widths: a prefill chunk needs the last position's only), the
    convolution's taps and gates, and attention over the context."""
    d = cfg['hidden_size']
    return 2.0 * (_count(cfg, 'conv') * conv_matrices(cfg)
                  + _count(cfg, 'full_attention') * _attention(cfg)
                  + cfg['num_dense_layers'] * 3 * d
                  * cfg['intermediate_size']
                  + _routed_layers(cfg) * (d * cfg['num_experts']
                                           + cfg['num_experts_per_tok']
                                           * _expert(cfg)))
