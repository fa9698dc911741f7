"""What the Mamba-2 layers' decode update must read, write and multiply
over the traced span, whatever implements it.

Counted from the load generator's records, as rooflines/paged_decode.py
counts: a request's token k >= 1 was made by a decode round that
advanced the request's state by one step in every Mamba layer (token 0
is the prefill's). For every such token that reached the client inside
[trace_t0 + EDGE_S, trace_t1], in each `M` layer of
`hybrid_override_pattern`: the slot's state (`mamba_num_heads` x
`mamba_head_dim` x `ssm_state_size` float32 values) and its
convolution's tail (`conv_kernel` - 1 inputs of bf16) are read once and
written once, and the step multiplies 6 operations a state value
(decay, the input's outer product and its sum into the state, the
product with C and its sum). Tokens near the span's start are left out
(their round may have run before the profiler did); the token's own
inputs, the results and whatever an implementation reads of dead
lanes' rows are not counted: the share can only under-read."""
from typing import Any, Dict, Optional

EDGE_S = 0.25
STATE_BYTES, TAIL_BYTES = 4, 2      # float32 state, bf16 tail


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    t0, t1 = sources.get('trace_t0'), sources.get('trace_t1')
    records = sources.get('records')
    cfg = sources['config']
    layers = str(cfg.get('hybrid_override_pattern', '')).count('M')
    if t0 is None or t1 is None or not records or not layers:
        return None
    inner = cfg['mamba_num_heads'] * cfg['mamba_head_dim']
    state = inner * cfg['ssm_state_size']
    tail = (cfg['conv_kernel'] - 1) * (
        inner + 2 * cfg['n_groups'] * cfg['ssm_state_size'])
    tokens = sum(1 for rec in records
                 for k, at in enumerate(rec.get('arrivals') or [])
                 if k >= 1 and t0 + EDGE_S <= at <= t1)
    if not tokens:
        return None
    return {'bytes': float(tokens * layers * 2 * (state * STATE_BYTES
                                                  + tail * TAIL_BYTES)),
            'flops': float(tokens * layers * 6 * state),
            'tokens': tokens}
