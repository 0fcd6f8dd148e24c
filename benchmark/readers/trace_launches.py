"""Device program launches in the traced window (events of the device
plane's ``XLA Modules`` line) per count ``per`` of the loop."""


def read(ctx, per="calls"):
    trace = ctx["trace"]
    if trace is None or not trace.launches or not ctx["counters"].get(per):
        return None
    return trace.launches / ctx["counters"][per]
