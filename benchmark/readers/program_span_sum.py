"""``readers.program_span``'s reduction summed over several span names: a
layer the program marks as more than one span, one after another
(``elle.analyze`` then ``elle.encode``: the host's pass before a dispatch).
``None`` unless the window holds a span of every name, so a program from
before the spans reads nothing."""

from readers.program_span import reduce


def read(ctx, spans, what):
    trace = ctx.get("trace")
    if trace is None:
        return None
    parts = [reduce(trace.spans, trace.t0, trace.t1,
                    ctx["counters"].get("calls"), name, what)
             for name in spans]
    return None if any(p is None for p in parts) else sum(parts)
