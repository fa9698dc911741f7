"""What the lightning indexer's decode read must read and multiply over
the traced span, whatever implements it.

Counted from the load generator's records, as rooflines/paged_decode.py
counts: a request's token k >= 1 was made by a decode round whose index
scores ran over its prompt and the k tokens before it. For every such
token that reached the client inside [trace_t0 + EDGE_S, trace_t1] the
context's indexer keys are read once in every layer (context x the
bytes of a cached key x layers; the program's page layout says what a
key takes, `/stats` `page_pool.row_layout.array_bytes`: 128 float32
values as served here, twice what ISSUE 33 reckoned for bf16) and
multiplied by every index head
(2 x context x index_n_heads x index_head_dim x layers operations).
The selection of the `index_topk` best runs inside the same scope and
adds nothing to the count; nor do the queries, the heads' weights, page
tables, or what a read takes beyond the live context: the share can
only under-read."""
from typing import Any, Dict, Optional

EDGE_S = 0.25
KEY_ARRAY = 'index_k_pages'


def key_bytes(sources: Dict[str, Any]) -> Optional[int]:
    """Bytes of one cached indexer key, as the program's page layout
    has it; None from a program that does not say."""
    layout = ((sources.get('stats_close') or {}).get('page_pool') or {}
              ).get('row_layout') or {}
    return (layout.get('array_bytes') or {}).get(KEY_ARRAY)


def decoded_contexts(sources: Dict[str, Any]):
    """[context length] of every token k >= 1 that reached the client
    inside the traced span (less its first EDGE_S)."""
    t0, t1 = sources.get('trace_t0'), sources.get('trace_t1')
    if t0 is None or t1 is None:
        return []
    return [rec['prompt_tokens'] + k
            for rec in sources.get('records') or []
            for k, at in enumerate(rec.get('arrivals') or [])
            if k >= 1 and t0 + EDGE_S <= at <= t1]


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    contexts = decoded_contexts(sources)
    key = key_bytes(sources)
    if not contexts or not key:
        return None
    cfg = sources['config']
    heads, dim = cfg['index_n_heads'], cfg['index_head_dim']
    layers, context = cfg['num_hidden_layers'], sum(contexts)
    return {'bytes': float(context * key * layers),
            'flops': float(2 * context * heads * dim * layers),
            'tokens': len(contexts), 'context_tokens': context}
