"""What routed experts of two matrices in a latent must read and
multiply over the traced span, whatever implements the layer.

As rooflines/expert_mlp.py, from the program's own device counters
(`/stats`: `expert_tokens` and `expert_calls_touched`, {block: [[decode,
an expert each], [prefill, ...]]}) as they grew between the two `/stats`
reads: here an assignment (a live token sent to an expert held here) is
two products, 4 x moe_latent_size x moe_intermediate_size operations,
and a (held expert, call) pair in which the expert received any token
is one read of its two matrices, 2 x moe_latent_size x
moe_intermediate_size x 2 bytes. Decode and prefill apart, each scaled
to the traced span by the share of its calls that ran there; a phase
whose calls cannot be placed in the span is left out, as are the
router, the latent's projections, the shared expert, the tokens' rows
and the results: the share can only under-read. More calls in the
trace than in the window is a miscount: no cost, nothing capped."""
import re
from typing import Any, Dict, Optional

WEIGHT_BYTES = 2
PROGRAMS = {0: (r'^jit_decode$', 'decode_calls'),
            1: (r'^jit_prefill', 'prefill_chunks_run')}


def _growth(a: Dict[str, Any], b: Dict[str, Any], key: str, phase: int
            ) -> Optional[float]:
    if key not in a or key not in b:
        return None
    return float(sum(sum(b[key][block][phase]) - sum(a[key][block][phase])
                     for block in b[key]))


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    a, b = sources.get('stats_open'), sources.get('stats_close')
    trace = sources.get('trace')
    cfg = sources['config']
    if not a or not b or not trace or 'moe_latent_size' not in cfg:
        return None
    matrix = cfg['moe_latent_size'] * cfg['moe_intermediate_size']
    flops = nbytes = 0.0
    placed = {}
    for phase, (pattern, calls_key) in PROGRAMS.items():
        tokens = _growth(a, b, 'expert_tokens', phase)
        touched = _growth(a, b, 'expert_calls_touched', phase)
        calls = b.get(calls_key, 0) - a.get(calls_key, 0)
        traced = sum(row[1] for name, row in trace['by_program'].items()
                     if re.search(pattern, name))
        if tokens is None or touched is None or calls <= 0 or traced <= 0:
            continue
        if traced > calls:
            return None
        share = traced / calls
        flops += share * tokens * 4 * matrix
        nbytes += share * touched * 2 * matrix * WEIGHT_BYTES
        placed[calls_key] = [traced, calls, tokens, touched]
    if not placed:
        return None
    return {'flops': flops, 'bytes': nbytes, 'placed': placed}
