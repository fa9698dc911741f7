"""Device idle share of the traced span, in percent: 1 - (union of the
device-operation intervals) / (first operation's start to the last
one's end), averaged over the chips (perfbench/trace_reduce.py)."""


def read(sources):
    trace = sources.get('trace')
    return None if not trace else trace.get('idle_pct')
