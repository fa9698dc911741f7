"""What the paged decode attention must read and multiply over the
traced span, whatever implements it.

Counted from the load generator's records: a request's token k >= 1 was
made by a decode round that attended over its prompt and the k tokens
before it (token 0 is the prefill's). For every such token that reached
the client inside [trace_t0 + EDGE_S, trace_t1] its context's keys and
values are read once in every layer: context x 2 x kv heads x head
size x 2 bytes (bf16) x layers; and multiplied by the query heads:
4 x context x heads x head size x layers operations (QK^T and PV).
Tokens near the span's start are left out (their round may have run
before the profiler did), queries, outputs, page tables and whatever a
kernel reads beyond the live context are not counted: the share can
only under-read."""
from typing import Any, Dict, Optional

#: A token reaches the client one pipelined round and a commit after
#: its attention ran: well under this at any round the cells see.
EDGE_S = 0.25
KV_BYTES = 2            # the pool is bf16 in every cell that lists this


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    t0, t1 = sources.get('trace_t0'), sources.get('trace_t1')
    records = sources.get('records')
    if t0 is None or t1 is None or not records:
        return None
    cfg = sources['config']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    head = cfg.get('head_dim') or cfg['hidden_size'] // heads
    layers = cfg['num_hidden_layers']
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
