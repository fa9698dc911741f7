"""Median of one field over the window's `--metrics-file` records."""
import statistics


def read(sources, field, scale=1.0):
    values = [r[field] for r in sources.get('records') or []
              if r.get(field) is not None]
    return scale * statistics.median(values) if values else None
