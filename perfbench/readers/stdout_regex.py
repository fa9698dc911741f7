"""First group of a pattern on the first line of the program's own
output that matches it (e.g. `train_lm`'s `setup:` line)."""
import re


def read(sources, pattern):
    rx = re.compile(pattern)
    for line in sources.get('stdout') or []:
        m = rx.search(line)
        if m:
            return float(m.group(1))
    return None
