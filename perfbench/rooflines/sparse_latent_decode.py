"""What decode attention over the SELECTED latent rows must read and
multiply over the traced span, whatever implements it.

For every token k >= 1 that reached the client inside the traced span
(rooflines/indexer_decode.py's count) a decode round attended over
min(context, index_topk) cached rows in every layer: each row, c_kv and
k_rope, is read once (rows x (kv_lora_rank + qk_rope_head_dim) x 2
bytes x layers) and multiplied by every head in the absorbed form, the
scores over the whole row and the weighted sum over c_kv (2 x rows x
heads x (2 kv_lora_rank + qk_rope_head_dim) x layers operations). The
queries' absorption by W_uk and the outputs' by W_uv are left out: the
share can only under-read."""
from typing import Any, Dict, Optional

from perfbench import manifest

ROW_BYTES = 2


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    contexts = manifest.roofline('indexer_decode').decoded_contexts(sources)
    if not contexts:
        return None
    cfg = sources['config']
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    heads, layers = cfg['num_attention_heads'], cfg['num_hidden_layers']
    rows = sum(min(c, cfg['index_topk']) for c in contexts)
    return {'bytes': float(rows * (rank + rope) * ROW_BYTES * layers),
            'flops': float(2 * rows * heads * (2 * rank + rope) * layers),
            'tokens': len(contexts), 'selected_rows': rows}
