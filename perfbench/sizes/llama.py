"""Sizes of `"family": "llama"` configurations (models/llama.py):
untied head, no biases, SwiGLU, grouped-query attention."""
from typing import Any, Dict


def _layer_matrices(cfg: Dict[str, Any]) -> int:
    d = cfg['hidden_size']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    head = cfg.get('head_dim') or d // heads
    return (d * heads * head + 2 * d * kv * head + heads * head * d
            + 3 * d * cfg['intermediate_size'])


def params(cfg: Dict[str, Any]) -> int:
    d, layers = cfg['hidden_size'], cfg['num_hidden_layers']
    return (2 * cfg['vocab_size'] * d
            + layers * (_layer_matrices(cfg) + 2 * d) + d)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """6 N plus 12 L S d (see sizes/gpt2.py)."""
    return (6.0 * params(cfg)
            + 12.0 * cfg['num_hidden_layers'] * seq * cfg['hidden_size'])


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 x the parameters a token multiplies in a forward pass: every
    layer's matrices and the output head. The embedding table is looked
    up, not multiplied; the norms' scales are left out; attention over
    the context is left out, so this can only under-read."""
    return 2.0 * (cfg['num_hidden_layers'] * _layer_matrices(cfg)
                  + cfg['vocab_size'] * cfg['hidden_size'])
