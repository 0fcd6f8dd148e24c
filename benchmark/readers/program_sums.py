"""A sum of terms from the sums the program keeps for the whole process (a
``*_stats()`` function: ``jepsen_tpu.obs.hist:first_use_stats``) less a sum
of others.  A term is a key, or ``{"sum": key, "over": count_key, "less":
n}``: that sum divided by the count less ``n``, so the mean of a sum that
leaves the first ``n`` calls out.  ``None`` where the program has no such
function or lacks a key, or a count is not past ``n``: a program from before
the counter reads nothing."""

import importlib


def term(sums, t):
    if isinstance(t, str):
        return sums.get(t)
    total, count = sums.get(t["sum"]), sums.get(t["over"])
    if total is None or count is None or count <= t.get("less", 0):
        return None
    return total / (count - t.get("less", 0))


def reduce(sums, plus, minus=()):
    values = [term(sums, t) for t in (*plus, *minus)]
    if any(v is None for v in values):
        return None
    return sum(values[:len(plus)]) - sum(values[len(plus):])


def read(ctx, stats, plus, minus=()):
    module, attr = stats.split(":")
    try:
        sums = getattr(importlib.import_module(module), attr)()
    except (ImportError, AttributeError):
        return None
    return reduce(sums, plus, minus)
