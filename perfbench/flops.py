"""Operations a training step needs, from the configuration's sizes.

The arithmetic is `bench.py`'s (6 N per token for the matmuls of the
forward and backward passes, plus 12 L S d for attention's QK^T and PV
over a causal-unaware full square, as the PaLM paper counts it);
recomputation does not count. N is counted here from the sizes, not
read from the program.
"""
from __future__ import annotations

from typing import Any, Dict


def gpt2_params(cfg: Dict[str, Any]) -> int:
    """GPT-2 with biases, tied input/output embedding, learned
    positions (models/gpt.py): wte + wpe + L(12 d^2 + 13 d) + 2 d."""
    d, layers = cfg['n_embd'], cfg['n_layer']
    return (cfg['vocab_size'] * d + cfg['n_positions'] * d
            + layers * (12 * d * d + 13 * d) + 2 * d)


def llama_params(cfg: Dict[str, Any]) -> int:
    """models/llama.py: untied head, no biases, SwiGLU, GQA."""
    d, layers = cfg['hidden_size'], cfg['num_hidden_layers']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    head = cfg.get('head_dim') or d // heads
    per_layer = (d * heads * head + 2 * d * kv * head + heads * head * d
                 + 3 * d * cfg['intermediate_size'] + 2 * d)
    return 2 * cfg['vocab_size'] * d + layers * per_layer + d


def params(cfg: Dict[str, Any]) -> int:
    return {'gpt2': gpt2_params, 'llama': llama_params}[cfg['family']](cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    if cfg['family'] == 'gpt2':
        layers, d = cfg['n_layer'], cfg['n_embd']
    else:
        layers, d = cfg['num_hidden_layers'], cfg['hidden_size']
    return 6.0 * params(cfg) + 12.0 * layers * seq * d
