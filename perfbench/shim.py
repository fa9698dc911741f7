"""Build a model from a configuration FILE through the program's own
registry function.

`serve_lm` takes a model only by registry name, and the registry
(`recipes.train_lm._build_model`) is program code. Until the program can
read a configuration file (`--model-config FILE`, PERF.md section 7),
this wraps that one function: a name that has a file in
perfbench/configs/ without a `registry_name` builds
`Llama(LlamaConfig(...))` from the file's published sizes, exactly as the
registry's own `llama3-8b-l8` entry does; any other name falls through.
`build_runtime` imports the function from its module at call time, so
the wrapper must be installed before `serve_lm.main()` runs.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from perfbench import manifest as manifest_lib

#: HuggingFace config.json key -> models/llama.py LlamaConfig field.
LLAMA_FIELDS = {
    'vocab_size': 'vocab_size',
    'num_hidden_layers': 'num_layers',
    'num_attention_heads': 'num_heads',
    'num_key_value_heads': 'num_kv_heads',
    'hidden_size': 'embed_dim',
    'intermediate_size': 'mlp_dim',
    'rope_theta': 'rope_theta',
    'rms_norm_eps': 'norm_eps',
}


def file_config(name: str) -> Optional[Dict[str, Any]]:
    """The configuration file for `name`, if it is one the shim builds."""
    if not manifest_lib.NAME_RE.match(name):
        return None
    path = os.path.join(manifest_lib.HERE, 'configs', f'{name}.json')
    if not os.path.isfile(path):
        return None
    with open(path, 'r', encoding='utf-8') as f:
        cfg = json.load(f)
    if cfg.get('registry_name') or cfg.get('family') != 'llama':
        return None
    return cfg


def llama_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    heads, hidden = cfg['num_attention_heads'], cfg['hidden_size']
    if cfg.get('head_dim', hidden // heads) != hidden // heads:
        raise ValueError('models/llama.py derives the head size as '
                         'hidden_size / num_attention_heads; this '
                         'configuration has another')
    if cfg.get('sliding_window') or cfg.get('tie_word_embeddings'):
        raise ValueError('models/llama.py has no sliding window and no '
                         'tied embeddings')
    return {field: cfg[key] for key, field in LLAMA_FIELDS.items()}


def install() -> None:
    from skypilot_tpu.recipes import train_lm
    original = train_lm._build_model  # pylint: disable=protected-access
    if getattr(original, 'perfbench_shim', False):
        return

    def build_model(name: str, seq: int, remat: bool):
        cfg = file_config(name)
        if cfg is None:
            return original(name, seq, remat)
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        config = LlamaConfig(**llama_kwargs(cfg),
                             max_seq_len=max(seq, 2048), remat=remat)
        return Llama(config), config.vocab_size, None

    build_model.perfbench_shim = True
    build_model.original = original
    train_lm._build_model = build_model  # pylint: disable=protected-access


def uninstall() -> None:
    from skypilot_tpu.recipes import train_lm
    fn = train_lm._build_model  # pylint: disable=protected-access
    if getattr(fn, 'perfbench_shim', False):
        train_lm._build_model = fn.original  # pylint: disable=protected-access
