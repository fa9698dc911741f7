"""The whole serving step's share of the chip's bf16 peak over the
window, in percent: (prompt tokens of the requests whose first token
reached the client inside the window + tokens the engine committed
between the two `/stats` reads) x the configuration's
`serve_flops_per_token` (perfbench/sizes/<family>.py: 2 x the
parameters a token multiplies; attention left out, so it under-reads)
over the span between the reads and the peak. None without the
counters; a share over 100 is refused."""
from perfbench import flops, peaks


def read(sources):
    a, b = sources.get('stats_open'), sources.get('stats_close')
    span_s = (sources.get('harness') or {}).get('stats_span_s')
    if not a or not b or not span_s or 'tokens_committed' not in b:
        return None
    window_s = sources['harness']['window_s']
    prompt = sum(r['prompt_tokens'] for r in sources.get('records') or []
                 if r.get('arrivals') and 0.0 <= r['arrivals'][0] < window_s)
    committed = b['tokens_committed'] - a.get('tokens_committed', 0)
    if prompt + committed <= 0:
        return None
    per_token = flops.serve_flops_per_token(sources['config'])
    peak = peaks.peak(sources['device']['kind'])['bf16_flops_per_s']
    share = 100.0 * (prompt + committed) * per_token / span_s / peak
    say = sources.get('say') or (lambda line: None)
    say(f'serve mfu: {prompt} prompt tokens + {committed} committed tokens '
        f'x {per_token:.4g} FLOP over {span_s:.3f}s and {peak:.4g} FLOP/s: '
        f'{share:.3f}%')
    if share > 100.0:
        raise ValueError(f'serve mfu {share:.1f}% of the peak')
    return share
