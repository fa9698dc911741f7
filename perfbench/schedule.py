"""The open-loop schedule: a pure function of (mix, seed, rate, window).

One general generator reads a mix file's parameters; a new mix is a new
data file. The mix fixes ONE sequence of (gap to the next arrival,
prompt length, output length): the lengths are the mid-quantiles of the
mix's distributions (not draws) and the gaps those of the exponential
distribution, each shuffled once by a fixed seed. A
run's `--seed` draws the token ids and nothing else: every seed offers
the same sizes at the same due times. A 50 s window holds about 100
requests, and which of them meet its edges is then part of the work:
with the sequence rotated by the seed, the tokens that reached the
client inside the window spread by 3.5% of the median over six seeds
and by 0.02% between two runs of one seed (my chip run, PR 24; PERF.md
section 4).
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

_STD_NORMAL = NormalDist()


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> List[int]:
    """n values at the mid-quantiles (i + 0.5) / n of a lognormal with
    the given median and sigma, clipped to [lo, hi]."""
    out = []
    for i in range(n):
        z = _STD_NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def lengths(spec: Dict[str, Any], n: int) -> List[int]:
    if spec['dist'] == 'lognormal':
        return _lognormal_quantiles(n, spec['median'], spec['sigma'],
                                    spec['min'], spec['max'])
    raise ValueError(f'unknown length distribution {spec["dist"]!r}')


def gaps(spec: Dict[str, Any], n: int, seconds: float) -> List[float]:
    """n gaps between arrivals that fill `seconds`. `poisson`: the
    mid-quantiles of the exponential distribution, scaled to the
    window."""
    if spec['process'] != 'poisson':
        raise ValueError(f'unknown arrival process {spec["process"]!r}')
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(raw)
    return [g * scale for g in raw]


def sequence(mix: Dict[str, Any], n: int, seconds: float
             ) -> List[Tuple[float, int, int]]:
    """The mix's one sequence of (gap, prompt length, output length),
    the same for every run seed."""
    rng = random.Random('perfbench-mix-0')
    columns = [gaps(mix['arrivals'], n, seconds),
               lengths(mix['prompt_tokens'], n),
               lengths(mix['output_tokens'], n)]
    for column in columns[1:] + columns[:1]:
        rng.shuffle(column)
    return list(zip(*columns))


def build(mix: Dict[str, Any], seed: int, seconds: float,
          rate: float, vocab_size: int) -> List[Dict[str, Any]]:
    """The window's requests, in due order: id, due (s from the
    window's opening), prompt (token ids), max_new_tokens. Exactly
    round(rate * seconds) of them, all due in [0, seconds)."""
    n = max(1, int(round(rate * seconds)))
    rng = random.Random(f'perfbench-window-{seed}')
    out, due = [], 0.0
    for i, (gap, n_prompt, n_out) in enumerate(sequence(mix, n, seconds)):
        out.append({'id': i, 'due': due,
                    'prompt': [rng.randrange(1, vocab_size)
                               for _ in range(n_prompt)],
                    'max_new_tokens': n_out})
        due += gap
    return out


def _bucket(n: int, cap: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def warmup(mix: Dict[str, Any], seed: int, vocab_size: int,
           prefill_chunk: int) -> List[Dict[str, Any]]:
    """Requests that touch every prefill shape the mix can reach: a
    first chunk of every power-of-two bucket between the shortest
    prompt's and the chunk size, and, where prompts outgrow one chunk,
    a full chunk followed by a tail of every bucket from 8 up to the
    chunk. All are due at once, so the decode round runs with several
    slots as well."""
    rng = random.Random(f'perfbench-warmup-{seed}')
    lo, hi = mix['prompt_tokens']['min'], mix['prompt_tokens']['max']
    sizes, b = [], _bucket(lo, prefill_chunk)
    while b <= prefill_chunk:
        sizes.append(min(b, hi))
        b *= 2
    b = 8
    while b <= prefill_chunk and prefill_chunk + b <= hi:
        sizes.append(prefill_chunk + b)
        b *= 2
    new = int(mix.get('warmup_new_tokens', 8))
    return [{'id': -1 - i, 'due': 0.0,
             'prompt': [rng.randrange(1, vocab_size) for _ in range(n)],
             'max_new_tokens': new}
            for i, n in enumerate(dict.fromkeys(sizes))]
