"""A percentile of what one `/stats` histogram observed between the two
reads: the growth of its bucket counts, linear inside the bucket the
percentile falls in. The histogram sits at a PATH of keys
(`["latency", "queue_wait"]`) and reads `{"n", "sum_s", "ratio",
"buckets": {"<upper edge, ms>": count}}` with only its non-empty
buckets; a bucket spans (edge / ratio, edge]. None when the server has
no such histogram or it observed nothing in the window."""


def read(sources, path, q, scale=1.0):
    hists = []
    for stats in (sources.get('stats_open'), sources.get('stats_close')):
        node = stats or {}
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
        if not isinstance(node, dict) or 'buckets' not in node:
            return None
        hists.append(node)
    before, after = hists
    ratio = float(after['ratio'])
    grown = sorted(
        (float(edge), count - before['buckets'].get(edge, 0))
        for edge, count in after['buckets'].items())
    grown = [(edge, n) for edge, n in grown if n > 0]
    total = sum(n for _, n in grown)
    if total <= 0:
        return None
    rank, seen = q * total, 0
    for edge, n in grown:
        if seen + n >= rank:
            if edge == float('inf'):
                return None     # beyond the last edge: no upper bound
            lower = edge / ratio
            return scale * (lower + (edge - lower) * (rank - seen) / n)
        seen += n
    return None
