"""A named scope's share of its roofline over the traced span, in
percent: the least time the chip could take for the work
`perfbench/rooflines/<costs>.py` counts (`cost(sources)` -> `flops`,
`bytes`), i.e. the larger of operations over the peak FLOP/s and bytes
over the peak bytes/s (perfbench/peaks.json), over the device self
seconds of the operations whose path passes through the scope (the
reducer's `by_path`), inside the programs `program` matches.

None where the trace holds no such operation or the cost file finds
nothing to count. A share over 100 is refused, not printed: the work
was counted too high or the scope's time leaves part of it out."""
import re

from perfbench import manifest, peaks


def scope_seconds(trace, scope, program=None):
    rx = re.compile(program) if program else None
    rows = [row for row in trace.get('by_path') or []
            if scope in row[1].split('/')
            and (rx is None or rx.search(row[0]))]
    return sum(row[2] for row in rows), sum(row[3] for row in rows)


def read(sources, scope, costs, program=None):
    trace = sources.get('trace')
    if not trace:
        return None
    seconds, events = scope_seconds(trace, scope, program)
    cost = manifest.roofline(costs).cost(sources)
    if seconds <= 0 or not cost:
        return None
    peak = peaks.peak(sources['device']['kind'])
    by_flops = cost['flops'] / peak['bf16_flops_per_s']
    by_bytes = cost['bytes'] / peak['hbm_bytes_per_s']
    share = 100.0 * max(by_flops, by_bytes) / seconds
    say = sources.get('say') or (lambda line: None)
    say(f'scope {scope!r} in {program or "every program"}: {seconds:.6f} '
        f'device self seconds over {events:.0f} operations; '
        f'{costs} counts {cost}; least time by operations '
        f'{by_flops:.6f}s, by bytes {by_bytes:.6f}s '
        f'({"bytes" if by_bytes >= by_flops else "operations"} bound): '
        f'{share:.3f}% of the roofline')
    if share > 100.0:
        raise ValueError(
            f'{scope}: {share:.1f}% of the roofline: the counted work '
            f'needs longer than the scope ran, so {costs} counts too '
            f'much or the scope leaves out part of the work')
    return share
