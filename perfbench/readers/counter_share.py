"""Growth of one `/stats` counter over the growth of another times a
constant the server reports (e.g. tokens committed per decode round,
over the number of slots: how full the batch ran)."""


def read(sources, numerator, denominator, per, scale=1.0):
    a, b = sources.get('stats_open'), sources.get('stats_close')
    if not a or not b:
        return None
    den = (b.get(denominator, 0) - a.get(denominator, 0)) * b.get(per, 0)
    num = b.get(numerator, 0) - a.get(numerator, 0)
    return scale * num / den if den > 0 else None
