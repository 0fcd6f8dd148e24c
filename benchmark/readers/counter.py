"""A count the loop made in the window, optionally per another count."""


def read(ctx, name, per=None):
    counters = ctx["counters"]
    if name not in counters:
        return None
    if per is None:
        return counters[name]
    return counters[name] / counters[per] if counters.get(per) else None
