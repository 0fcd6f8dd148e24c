"""Host-clock seconds of one of the loop's probes (a layer's host function
called on the cell's own inputs, outside the window), whole or per 1,000
ops covered."""


def read(ctx, probe, per_kop=False):
    fn = ctx["probes"].get(probe)
    if fn is None:
        return None
    got = fn()
    ctx["log"].say(f"probe {probe}: {got['seconds']:.4f} s over "
                   f"{got['ops']:.0f} ops")
    if not per_kop:
        return got["seconds"]
    return got["seconds"] / (got["ops"] / 1000.0) if got["ops"] else None
