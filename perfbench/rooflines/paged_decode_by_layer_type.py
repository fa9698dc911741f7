"""What the paged decode attention must read and multiply over the
traced span in a model whose layers are of several kinds: the count of
rooflines/paged_decode.py over the `full_attention` entries of
`layer_types` only.

Counted from the load generator's records: a request's token k >= 1 was
made by a decode round that attended over its prompt and the k tokens
before it (token 0 is the prefill's). For every such token that reached
the client inside [trace_t0 + EDGE_S, trace_t1] its context's keys and
values are read once in every attention layer: context x 2 x kv heads x
head size x 2 bytes (bf16) x layers; and multiplied by the query heads:
4 x context x heads x head size x layers operations (QK^T and PV); the
head size is hidden_size / num_attention_heads. Tokens near the span's
start are left out, and queries, outputs, page tables and whatever a
read fetches beyond the live context are not counted: the share can
only under-read."""
from typing import Any, Dict, Optional

EDGE_S = 0.25
KV_BYTES = 2            # the pool is bf16 in every cell that lists this


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    t0, t1 = sources.get('trace_t0'), sources.get('trace_t1')
    records = sources.get('records')
    cfg = sources['config']
    layers = list(cfg.get('layer_types') or []).count('full_attention')
    if t0 is None or t1 is None or not records or not layers:
        return None
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    head = cfg['hidden_size'] // heads
    tokens = context = 0
    for rec in records:
        for k, at in enumerate(rec.get('arrivals') or []):
            if k >= 1 and t0 + EDGE_S <= at <= t1:
                tokens += 1
                context += rec['prompt_tokens'] + k
    if not tokens:
        return None
    return {'bytes': float(context * 2 * kv * head * KV_BYTES * layers),
            'flops': float(4 * context * heads * head * layers),
            'tokens': tokens, 'context_tokens': context}
