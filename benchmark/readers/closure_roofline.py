"""The closure engine's share of the chip's matrix peak over the traced
window: the floating-point operations the algorithm needs for a call, from
the shapes and rounds the program counted (``elle_stats()``), over the
device's busy seconds a call times the published peak (``peaks.json``).

The operation count is the benchmark's (:func:`flops`), the program gives
only shapes and counts: a closure round squares an ``[n_pad, n_pad]`` 0/1
matrix (``2 n_pad^3``), a layer build is a one-hot product over ``e_pad``
edge slots (``2 e_pad n_pad^2``).  The rounds are those the program ran, so
a program that stops squaring early lowers the count and the busy time
together; the comparisons, clamps and reductions between the products are
not counted, so the share is of the matrix unit's peak alone and cannot
pass 100%.  Compute-bound: a round reads and writes ``3 x 4 n_pad^2`` bytes
for ``2 n_pad^3`` operations, 1,584 operations a byte at ``n_pad`` 9,504
against the chip's 240.

``None`` where the program has no such counter (a program from before it),
ran no call, or the trace shows no busy time.
"""

import importlib

from harness.manifest import peaks


def flops(stats):
    """Operations of everything ``stats`` counted, at its shapes."""
    n, e = stats["n_pad"], stats["e_pad"]
    return 2 * n ** 3 * stats["closure_rounds"] \
        + 2 * e * n ** 2 * stats["layer_builds"]


def read(ctx, stats, peak):
    module, attr = stats.split(":")
    try:
        sums = getattr(importlib.import_module(module), attr)()
    except (ImportError, AttributeError):
        return None
    trace, calls = ctx.get("trace"), ctx["counters"].get("calls")
    kind = ctx.get("device", {}).get("kind")
    if not sums.get("calls") or trace is None or not trace.busy_s \
            or not calls or kind is None:
        return None
    per_call = flops(sums) / sums["calls"]
    return 100.0 * per_call * calls / (trace.busy_s * peaks(kind)[peak])
