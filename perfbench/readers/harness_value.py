"""A value the harness took itself, by its key: the load generator's
lateness, the seconds to the first /readyz 200, the compile events
counted inside the window."""


def read(sources, key):
    return (sources.get('harness') or {}).get(key)
