"""What the short-convolution layers' decode rounds must read, write and
multiply over the traced span, whatever implements them.

The weights: a conv layer's two matrices are read once by every round
that runs it, `in_proj` (hidden x 3 hidden) and `out_proj` (hidden x
hidden) in bf16, with its `conv_L_cache` taps of `hidden` values. They
are counted from the trace, one read for each of the projections'
operations that scope `short_conv` of a decode program holds: a
projection that reads its own matrix inside the call (ops/
pallas_streamed, `streamed_matmul`) holds the read in the scope's time,
where XLA:TPU copies a parameter's slices into VMEM during the
operations before the product and the read lies outside any scope. A
trace with no such operation gives no cost (the metric is then left
out), and an operation at the span's edge is one the trace holds whole.

The rows: counted from the load generator's records, as
rooflines/paged_decode.py counts, a request's token k >= 1 was made by
a decode round (token 0 is the prefill's). For every such token that
reached the client inside [trace_t0 + EDGE_S, trace_t1], in each `conv`
layer of `layer_types`: the slot's tail (`conv_L_cache` - 1 inputs of
`hidden_size` bf16 values) is read once and written once, and the mixer
multiplies 2 x 4 hidden^2 operations in its two projections and 6
hidden in its gates and taps. Tokens near the span's start are left
out, and so are the lanes a round computes for no request, the tokens'
own inputs and the results: the share can only under-read."""
from typing import Any, Dict, Optional

EDGE_S = 0.25
WEIGHT_BYTES = TAIL_BYTES = 2        # bf16 weights and tails
PROGRAM = 'jit_decode'
SCOPE, KERNEL = 'short_conv', 'streamed_matmul'


def _reads(trace: Dict[str, Any], matrix: str) -> float:
    """Operations of the projection `matrix` in the scope, in decode."""
    return float(sum(
        row[3] for row in trace.get('by_path') or []
        if row[0] == PROGRAM and {SCOPE, KERNEL, matrix}
        <= set(row[1].split('/'))))


def cost(sources: Dict[str, Any]) -> Optional[Dict[str, float]]:
    t0, t1 = sources.get('trace_t0'), sources.get('trace_t1')
    records, trace = sources.get('records'), sources.get('trace')
    cfg = sources['config']
    layers = list(cfg.get('layer_types') or []).count('conv')
    if t0 is None or t1 is None or not records or not trace or not layers:
        return None
    hidden, taps = cfg['hidden_size'], cfg['conv_L_cache']
    tokens = sum(1 for rec in records
                 for k, at in enumerate(rec.get('arrivals') or [])
                 if k >= 1 and t0 + EDGE_S <= at <= t1)
    reads_in, reads_out = _reads(trace, 'in_proj'), _reads(trace, 'out_proj')
    if not tokens or not reads_in or not reads_out:
        return None
    weights = WEIGHT_BYTES * (reads_in * (3 * hidden * hidden + taps * hidden)
                              + reads_out * hidden * hidden)
    tail = (taps - 1) * hidden * TAIL_BYTES
    return {'bytes': float(weights + tokens * layers * 2 * tail),
            'flops': float(tokens * layers * (8 * hidden * hidden
                                              + 6 * hidden)),
            'tokens': tokens, 'weight_reads': [reads_in, reads_out]}
