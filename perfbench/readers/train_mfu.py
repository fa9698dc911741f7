"""Model FLOP/s utilization of a training window, in percent: tokens
per second per chip times the benchmark's own FLOPs per token
(perfbench/flops.py: 6 N plus the attention term) over the chip's bf16
peak from perfbench/peaks.json. A device kind that is not in the table
is an error."""
from perfbench import flops, peaks


def read(sources):
    rate = (sources.get('end_to_end') or {}).get('train_tokens_per_s')
    if rate is None:
        return None
    seq = int(sources['mix']['train_lm']['--seq'])
    per_token = flops.train_flops_per_token(sources['config'], seq)
    peak = peaks.peak(sources['device']['kind'])['bf16_flops_per_s']
    return 100.0 * rate * per_token / peak
