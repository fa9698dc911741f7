"""What the Mamba-2 layers' prefill scan must read, write and multiply
over the traced span, whatever implements it.

From the program's own device counter (`/stats` `ssm_scan_tokens`,
{block: valid tokens that Mamba layer scanned in prefill chunks}) as it
grew between the two `/stats` reads at the window's ends, scaled to the
traced span as rooflines/expert_mlp.py scales: by the prefill chunks
the trace holds (`by_program`, programs `^jit_prefill`) over the growth
of `prefill_chunks_run`. A scanned token of a layer is 6 operations a
state value (`mamba_num_heads` x `mamba_head_dim` x `ssm_state_size`),
the step form's count, which no form can go under; it reads its x, B
and C (bf16) and dt and writes its y (float32). A traced chunk reads
and writes the slot's float32 state once in every `M` layer. Padded
positions, the convolution and the sub-chunks' quadratic products are
not counted: the share can only under-read. More chunks in the span
than in the window that holds it is a miscount: no cost, nothing
capped."""
import re
from typing import Any, Dict, Optional


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    a, b = sources.get('stats_open'), sources.get('stats_close')
    trace = sources.get('trace')
    if not a or not b or not trace or 'ssm_scan_tokens' not in b:
        return None
    cfg = sources['config']
    before = a.get('ssm_scan_tokens') or {}
    tokens = float(sum(value - before.get(block, 0)
                       for block, value in b['ssm_scan_tokens'].items()))
    layers = len(b['ssm_scan_tokens'])
    chunks = b.get('prefill_chunks_run', 0) - a.get('prefill_chunks_run', 0)
    traced = sum(row[1] for name, row in trace['by_program'].items()
                 if re.search(r'^jit_prefill', name))
    if tokens <= 0 or chunks <= 0 or traced <= 0 or traced > chunks:
        return None
    share = traced / chunks
    inner = cfg['mamba_num_heads'] * cfg['mamba_head_dim']
    state = inner * cfg['ssm_state_size']
    per_token = ((inner + 2 * cfg['n_groups'] * cfg['ssm_state_size']) * 2
                 + cfg['mamba_num_heads'] * 4 + inner * 4)
    return {'flops': share * tokens * 6 * state,
            'bytes': share * tokens * per_token
            + traced * layers * 2 * state * 4,
            'placed': [traced, chunks, tokens, layers]}
