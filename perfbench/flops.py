"""Parameters and operations per token, from a configuration's sizes.

A family is a file: `perfbench/sizes/<family>.py` with `params(cfg)`,
`train_flops_per_token(cfg, seq)` and `serve_flops_per_token(cfg)`,
found by the `family` the configuration file names. The arithmetic is
`bench.py`'s; N is counted from the sizes, not read from the program.
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench import manifest


def params(cfg: Dict[str, Any]) -> int:
    return manifest.sizes(cfg['family']).params(cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    return manifest.sizes(cfg['family']).train_flops_per_token(cfg, seq)


def serve_flops_per_token(cfg: Dict[str, Any]) -> float:
    return manifest.sizes(cfg['family']).serve_flops_per_token(cfg)
