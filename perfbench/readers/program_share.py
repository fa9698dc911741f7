"""Share of the device's busy time, in percent, that the jitted
programs whose name matches a pattern took in the traced span (the
reducer's `by_program`: self seconds of every operation by the program
it ran in). None without a trace or where no such program ran."""
import re


def read(sources, pattern):
    trace = sources.get('trace')
    if not trace or not trace.get('busy_s'):
        return None
    rx = re.compile(pattern)
    sec = sum(row[0] for name, row in trace['by_program'].items()
              if rx.search(name))
    return 100.0 * sec / trace['busy_s'] if sec > 0 else None
