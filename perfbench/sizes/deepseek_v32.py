"""Sizes of `"family": "deepseek_v32"` configurations
(models/deepseek.py): MLA with a query bottleneck, the lightning
indexer, a dense SwiGLU in the first `first_k_dense_replace` layers and
after them a router over the PUBLISHED expert count
(`reduced_from.n_routed_experts`), one shared expert and the
`experts_held` routed experts this chip holds; untied head over the
vocabulary slice."""
from typing import Any, Dict


def _attention(cfg: Dict[str, Any]) -> int:
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    q_rank, kv_rank = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    mla = (d * q_rank + q_rank * heads * (nope + rope)
           + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
           + heads * v * d)
    indexer = (q_rank * cfg['index_n_heads'] * cfg['index_head_dim']
               + d * cfg['index_head_dim'] + d * cfg['index_n_heads'])
    return mla + indexer


def _router_width(cfg: Dict[str, Any]) -> int:
    return (cfg.get('reduced_from') or {}).get('n_routed_experts',
                                               cfg['n_routed_experts'])


def _expert(cfg: Dict[str, Any]) -> int:
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def matrices(cfg: Dict[str, Any]) -> int:
    """Every matrix the chip holds (no norms, no router bias)."""
    d, layers = cfg['hidden_size'], cfg['num_hidden_layers']
    dense = cfg['first_k_dense_replace']
    return (2 * cfg['vocab_size'] * d + layers * _attention(cfg)
            + dense * 3 * d * cfg['intermediate_size']
            + (layers - dense) * (
                d * _router_width(cfg)
                + (cfg['n_shared_experts'] + cfg['experts_held'])
                * _expert(cfg)))


def params(cfg: Dict[str, Any]) -> int:
    d, layers = cfg['hidden_size'], cfg['num_hidden_layers']
    norms = layers * (2 * d + cfg['q_lora_rank'] + cfg['kv_lora_rank']
                      + 2 * cfg['index_head_dim']) + d
    bias = (layers - cfg['first_k_dense_replace']) * _router_width(cfg)
    return matrices(cfg) + norms + bias


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        'no trainer cell runs a deepseek_v32 configuration: its '
        'attention reads the page pool (serving only)')


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 x the matrices a token CERTAINLY multiplies in a forward pass:
    every layer's attention with its indexer, the dense SwiGLU or the
    shared expert, the router. Left out, so that this can only
    under-read: the routed experts (a token multiplies as many of the
    held ones as the router sends it to: under even routing
    num_experts_per_tok x experts_held / published experts = 0.5 of
    one a layer in deepseek-v32-l5-ep16, 88.1M of the 1,789M
    parameters a token then multiplies), the output head (115.8M) and
    attention and index scores over the context: 11.4% under before
    the context's part."""
    layers, dense = cfg['num_hidden_layers'], cfg['first_k_dense_replace']
    d = cfg['hidden_size']
    return 2.0 * (layers * _attention(cfg)
                  + dense * 3 * d * cfg['intermediate_size']
                  + (layers - dense) * (
                      cfg['n_shared_experts'] * _expert(cfg)
                      + d * _router_width(cfg)))
