"""Time between the two `/stats` reads at the window's ends over the
growth of one counter between them (e.g. seconds per decode round)."""


def read(sources, counter, scale=1.0):
    a, b = sources.get('stats_open'), sources.get('stats_close')
    span_s = (sources.get('harness') or {}).get('stats_span_s')
    if not a or not b or not span_s:
        return None
    delta = b.get(counter, 0) - a.get(counter, 0)
    return scale * span_s / delta if delta > 0 else None
