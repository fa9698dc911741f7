"""From the load generator's records to attempted, failed and the
client-side latency metrics. Imports nothing but the standard library.

Latency is taken from the time a request was DUE, not from when it was
sent: a generator that runs late, or a server whose stall holds later
requests back, shows in the number.
"""
from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional

from perfbench import stats


def failed(rec: Dict[str, Any]) -> bool:
    """Error, refusal, short or garbled stream, or cut by the drain:
    anything but a complete stream of exactly max_new_tokens tokens."""
    return not (rec.get('end') == 'done' and rec.get('status') == 200
                and len(rec.get('tokens') or []) == rec['max_new_tokens'])


def ttft_s(rec: Dict[str, Any], window_s: float) -> float:
    """First token's arrival minus the due time. A failed request
    counts as the window's length, whatever it streamed first."""
    if failed(rec) or not rec.get('arrivals'):
        return window_s
    return rec['arrivals'][0] - rec['due']


def token_gaps_s(rec: Dict[str, Any]) -> List[float]:
    a = rec.get('arrivals') or []
    return [a[i + 1] - a[i] for i in range(len(a) - 1)]


def summarize(records: List[Dict[str, Any]], window_s: float
              ) -> Dict[str, Any]:
    """Every request in `records` was due inside the window."""
    n = len(records)
    n_failed = sum(1 for r in records if failed(r))
    ttfts = [ttft_s(r, window_s) for r in records]
    gaps = [g for r in records for g in token_gaps_s(r)]
    in_window = sum(1 for r in records for t in (r.get('arrivals') or [])
                    if 0.0 <= t < window_s)
    late = [r['sent'] - r['due'] for r in records
            if r.get('sent') is not None]
    return {
        'attempted': n,
        'failed': n_failed,
        'ttft_p95_ms': _ms(stats.percentile(ttfts, 0.95)),
        'ttft_p50_ms': _ms(stats.percentile(ttfts, 0.50)),
        'ttft_samples': len(ttfts),
        'ttft_tail_supported': stats.tail_is_supported(len(ttfts), 0.95),
        'itl_p95_ms': _ms(stats.percentile(gaps, 0.95)),
        'itl_p50_ms': _ms(stats.percentile(gaps, 0.50)),
        'itl_samples': len(gaps),
        'tokens_in_window': in_window,
        'serve_tokens_per_s': in_window / window_s if window_s else None,
        'lateness_p95_ms': _ms(stats.percentile(late, 0.95)),
        'lateness_max_ms': _ms(max(late)) if late else None,
        'ends': dict(collections.Counter(str(r.get('end'))
                                         for r in records)),
    }


def backlog(records: List[Dict[str, Any]], at_s: float) -> int:
    """Requests due by `at_s` that had not finished by then (the knee
    sweep compares the window's middle with its close)."""
    n = 0
    for r in records:
        if r['due'] > at_s:
            continue
        a = r.get('arrivals') or []
        done = (not failed(r)) and a and a[-1] <= at_s
        n += 0 if done else 1
    return n


def _ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else 1000.0 * v

