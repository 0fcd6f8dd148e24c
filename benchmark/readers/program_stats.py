"""A ratio of two sums the program keeps for the whole process (a
``*_stats()`` function: ``jepsen_tpu.parallel:batch_stats``), times
``scale``.  The sums hold the warm-up call too; every call of a cell does
the same search, so the ratio is a call's.  ``None`` where the program has
no such function or key, or the denominator is 0: a program from before
the counter reads nothing."""

import importlib


def read(ctx, stats, numerator, denominator, scale=1.0):
    module, attr = stats.split(":")
    try:
        sums = getattr(importlib.import_module(module), attr)()
    except (ImportError, AttributeError):
        return None
    if not sums.get(denominator) or numerator not in sums:
        return None
    return scale * sums[numerator] / sums[denominator]
