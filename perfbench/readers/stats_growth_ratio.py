"""Growth between the two `/stats` reads of a signed sum of values over
the growth of another value, times a constant (e.g. the seconds one
scheduler phase took over the rounds it ran in, or the loop's time less
its waits over the loop's time). A value is named by its PATH, a list
of keys, because a phase's name holds dots:
`["phases", "engine.commit", "s"]`. A server that has no such top-level
key (one from before the phases) gives None; a phase that never ran
reads 0."""


def _value(stats, path):
    if path[0] not in stats:
        return None
    node = stats
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return 0.0
        node = node[key]
    return float(node)


def _growth(a, b, paths):
    total = 0.0
    for path in paths:
        lo, hi = _value(a, path), _value(b, path)
        if lo is None or hi is None:
            return None
        total += hi - lo
    return total


def read(sources, plus, over, minus=(), scale=1.0):
    a, b = sources.get('stats_open'), sources.get('stats_close')
    if not a or not b:
        return None
    num, sub, den = (_growth(a, b, plus), _growth(a, b, minus),
                     _growth(a, b, [over]))
    if num is None or sub is None or den is None or den <= 0:
        return None
    return scale * (num - sub) / den
