"""How unevenly the router loaded the experts held here, over the
window: the busiest expert's assignments over the mean expert's, from
the growth of `/stats` `expert_tokens` ({block: [[decode, an expert
each], [prefill, ...]]}) between the two reads, decode and prefill
and all layers together. 1 is even. None without the counter (a model
that routes nothing) or where nothing was routed."""


def read(sources):
    a, b = sources.get('stats_open'), sources.get('stats_close')
    if not a or not b or 'expert_tokens' not in b:
        return None
    before = a.get('expert_tokens') or {}
    load = None
    for block, phases in b['expert_tokens'].items():
        for phase, row in enumerate(phases):
            old = (before.get(block) or [[0] * len(row)] * len(phases))[phase]
            grown = [hi - lo for hi, lo in zip(row, old)]
            load = grown if load is None else [
                x + y for x, y in zip(load, grown)]
    if not load or sum(load) <= 0:
        return None
    return max(load) / (sum(load) / len(load))
