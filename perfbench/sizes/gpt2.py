"""Sizes of `"family": "gpt2"` configurations (models/gpt.py): GPT-2
with biases, a tied input/output embedding and learned positions."""
from typing import Any, Dict


def params(cfg: Dict[str, Any]) -> int:
    """wte + wpe + L(12 d^2 + 13 d) + 2 d."""
    d, layers = cfg['n_embd'], cfg['n_layer']
    return (cfg['vocab_size'] * d + cfg['n_positions'] * d
            + layers * (12 * d * d + 13 * d) + 2 * d)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """6 N for the matmuls of the forward and backward passes plus
    12 L S d for attention's QK^T and PV over the full square, as the
    PaLM paper counts it; recomputation does not count."""
    return 6.0 * params(cfg) + 12.0 * cfg['n_layer'] * seq * cfg['n_embd']


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    """2 x the parameters a token multiplies in a forward pass: the
    blocks' matrices and the tied embedding as the output head. The
    two embedding tables are looked up, not multiplied; biases and
    norms are additions; attention over the context is left out, so
    this can only under-read."""
    d = cfg['n_embd']
    return 2.0 * (cfg['n_layer'] * 12 * d * d + cfg['vocab_size'] * d)
