"""From the union of the device's op intervals over the traced window:
``idle_share`` (percent of the window with no op running) or, with
``counter``, that count per busy second."""


def read(ctx, what, counter=None):
    trace = ctx["trace"]
    if trace is None or not trace.window_s or not trace.busy_s:
        return None
    if what == "idle_share":
        return 100.0 * (1.0 - trace.busy_s / trace.window_s)
    if what == "per_busy_s" and counter in ctx["counters"]:
        return ctx["counters"][counter] / trace.busy_s
    return None
